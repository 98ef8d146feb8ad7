"""The traffic drivers: the one general generator that reads a traffic
file (`traffic/<mix>.json`) and drives the system under test with it.

`stream`   an open loop through `Scheduler.stream()` over `ApiServerLite`,
           with the loop's own admission settings: pods are created at
           their due times (spec.arrivals), and each burst is deleted
           once it is fully bound.
`extender` a closed loop playing a stock kube-scheduler with a
           nodeCacheCapable extender (core/extender.go:113-124): /filter
           with every node name, /prioritize with the names that passed,
           /bind on the top score, through `ExtenderHTTPServer` and
           `TPUExtenderBackend`.

Each driver builds the cluster from the seed (set-up), warms the shapes
its traffic uses, opens the window, and returns a `Record` of what it saw;
run.py turns that into metrics and checks. The calls into the program
are wrapped in `jax.profiler.TraceAnnotation`s named for what the host
was doing, so a traced run can name the device's idle gaps.
"""

from __future__ import annotations

import collections
import gc
import json
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

import spec
from adapter import PodFactory, pod_events, program_nodes


@dataclass
class Record:
    """What one run saw, for run.py's metrics and reference check."""
    t0: float = 0.0                      # window open, time.monotonic
    seconds: float = 0.0
    attempted: int = 0
    failed: int = 0
    latencies_s: np.ndarray = field(default_factory=lambda: np.zeros(0))
    bound_in_window: int = 0
    extender_s: List[float] = field(default_factory=list)
    creator_lag_s: List[float] = field(default_factory=list)
    intervals: List[Tuple[str, float, float, int]] = field(
        default_factory=list)           # (name, start, end, pods bound)
    spans: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    podtrace: Dict = field(default_factory=dict)
    compiles_in_window: int = 0
    memory_peak_bytes: Optional[int] = None
    trace_stop: float = 0.0
    checks: Dict[str, float] = field(default_factory=dict)
    notes: Dict = field(default_factory=dict)
    # for the reference, filled before the program's state is freed
    events: List[Tuple[str, str, str, int]] = field(default_factory=list)
    specs: Dict[str, spec.PodSpec] = field(default_factory=dict)
    setup_pods: List[Tuple[spec.PodSpec, int]] = field(default_factory=list)
    nodes: List[spec.NodeSpec] = field(default_factory=list)
    samples: List[Dict] = field(default_factory=list)


def _annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _spans_now() -> Dict[str, Tuple[int, float]]:
    from kubernetes_tpu.utils.trace import COUNTERS
    return COUNTERS.snapshot()


def spans_diff(a, b) -> Dict[str, Tuple[int, float]]:
    out = {}
    for k, (c, t) in b.items():
        c0, t0 = a.get(k, (0, 0.0))
        if c != c0 or t != t0:
            out[k] = (c - c0, t - t0)
    return out


class CompileCounter:
    """Counts JAX's compile events (tracing, backend compiles and loads
    from the persistent cache) while armed, and keeps the names of the
    programs compiled (JAX's own compile log): none should fall inside
    the window."""

    def __init__(self):
        import logging

        import jax
        self._armed = False
        self.count = 0
        self.names: collections.Counter = collections.Counter()
        self.programs: collections.Counter = collections.Counter()
        self.traced: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        counter = self

        class _Grab(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if not counter._armed:
                    return
                if msg.startswith("Compiling "):
                    import traceback
                    at = [f"{f.filename.rsplit('/', 2)[-1]}:{f.lineno}"
                          for f in traceback.extract_stack()
                          if "kubernetes_tpu" in f.filename][-4:]
                    counter.programs[msg[:3000] + " @ " + " < ".join(
                        reversed(at))] += 1
                elif msg.startswith("Finished tracing + transforming "):
                    counter.traced[msg.split(" ")[4]] += 1
        self._handler = _Grab()
        self._logger = logging.getLogger("jax")

    @property
    def armed(self) -> bool:
        return self._armed

    @armed.setter
    def armed(self, on: bool) -> None:
        import jax
        self._armed = on
        jax.config.update("jax_log_compiles", on)
        if on:
            self._logger.addHandler(self._handler)
            self._logger.propagate = False
        else:
            self._logger.removeHandler(self._handler)
            self._logger.propagate = True

    def _on(self, name, _secs, **_kw):
        if self._armed and "compil" in name:
            self.count += 1
            self.names[name] += 1


def _memory_peak() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _build_store(nodes, placed, fac, max_log):
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite
    api = ApiServerLite(max_log=max_log)
    for n in program_nodes(nodes):
        api.create("Node", n)
    for s, i in placed:
        api.create("Pod", fac.pod(s, nodes[i].name))
    return api


def _audit_cache(sched, api) -> int:
    """Cache against store (the audit of chip_smoke.py and
    testing/churn.audit_cache_vs_store): every pod the scheduler's cache
    counts on a node is bound there in the store, and every bound pod is
    counted. Returns the number of disagreements."""
    sched.sync()
    store = {p.key(): p.node_name for p in api.list("Pod")[0] if p.node_name}
    cache = {p.key(): name for name, info in sched.cache.node_infos().items()
             for p in info.pods}
    bad = sum(1 for k, n in cache.items() if store.get(k) != n)
    return bad + sum(1 for k in store if k not in cache)


# ----------------------------------------------------------------- stream


def _ladder(lo: int, hi: int) -> List[int]:
    out, s = [], lo
    while s <= hi:
        out.append(s)
        s *= 2
    return out


class _Retirer:
    """Deletes each burst once it is fully bound (a completed batch job's
    pods), from the creator's thread."""

    def __init__(self, api, arrivals):
        self.api = api
        self.left: Dict[int, int] = dict(collections.Counter(
            a.group for a in arrivals))
        self.members: Dict[int, List[Tuple[str, str]]] = \
            collections.defaultdict(list)
        self.group_of: Dict[str, int] = {}
        for a in arrivals:
            self.group_of[a.spec.key] = a.group
            self.members[a.group].append((a.spec.namespace, a.spec.name))
        self.ready: collections.deque = collections.deque()   # groups

    def on_bound(self, keys) -> None:
        """From the scheduler's wave_observer (the loop's thread)."""
        for k in keys:
            g = self.group_of.get(k)
            if g is None:
                continue
            self.left[g] -= 1
            if self.left[g] == 0:
                self.ready.append(g)

    def work(self, limit: int = 2048) -> int:
        """Delete up to `limit` pods that are due for deletion."""
        done = 0
        while self.ready and done < limit:
            g = self.ready[0]
            mem = self.members[g]
            with _annotation("delete"):
                while mem and done < limit:
                    ns, name = mem.pop()
                    self.api.delete("Pod", ns, name)
                    done += 1
            if not mem:
                self.ready.popleft()
        return done


def stream(cfg: dict, traffic: dict, seed: int, seconds: float,
           trace: bool, system=None) -> Record:
    """Set-up, window and hand-over of one stream run. `system`, when
    given, replaces the program's Scheduler (control.py's reference
    placer); it must offer start(), step(), idle, wait(), close() and
    observer."""
    import jax

    rec = Record(seconds=seconds)
    phases = rec.notes["setup_phases"] = {}
    t_phase = time.monotonic()
    nodes = spec.make_nodes(cfg)
    placed = spec.background(cfg, nodes, seed)
    arrivals = sorted(spec.arrivals(cfg, traffic, seed, seconds),
                      key=lambda a: a.due)
    fac = PodFactory()
    n_events = len(placed) + 4 * len(arrivals) + 400_000
    api = _build_store(nodes, placed, fac, max_log=n_events)
    rec.nodes, rec.setup_pods = nodes, placed
    phases["build"] = time.monotonic() - t_phase

    if system is None:
        from kubernetes_tpu.engine.scheduler import Scheduler
        t_phase = time.monotonic()
        sched = Scheduler(api, record_events=False)
        sched.start()
        loop = sched.stream()
        phases["hydrate"] = time.monotonic() - t_phase
        t_phase = time.monotonic()
        _warm_stream(loop, api, cfg, traffic, fac, seed)
        phases["warm"] = time.monotonic() - t_phase
        system = _ProgramStream(sched, loop)
    else:
        sched = None
        system.start(api, nodes, placed)

    progs = [fac.pod(a.spec) for a in arrivals]
    rec.specs = {a.spec.key: a.spec for a in arrivals}
    retire = _Retirer(api, arrivals)
    bind_ts: Dict[str, float] = {}
    lock = threading.Lock()

    def observe(ts, keys):
        with lock:
            for k in keys:
                bind_ts.setdefault(k, ts)
        retire.on_bound(keys)

    system.observer = observe
    counter = CompileCounter()
    gc.collect()
    gc.freeze()
    rv0 = api.current_rv()
    spans0 = _spans_now()
    tracer = None
    if trace:
        from kubernetes_tpu.observability.podtrace import TRACER
        tracer = TRACER
        tracer.clear()
        tracer.enable()
        _start_trace()
    counter.armed = True
    stop_create = threading.Event()
    lags = rec.creator_lag_s

    def creator():
        i, n = 0, len(arrivals)
        while i < n and not stop_create.is_set():
            now = time.monotonic() - t0
            if arrivals[i].due <= now:
                j = i
                with _annotation("create"):
                    while j < n and arrivals[j].due <= now and j - i < 1024:
                        api.create("Pod", progs[j])
                        j += 1
                t_c = time.monotonic() - t0
                lags.extend(t_c - arrivals[k].due for k in range(i, j))
                i = j
                retire.work(256)
                continue
            if retire.work(1024):
                continue
            time.sleep(min(0.001, max(arrivals[i].due - now, 0.0)))
        while not stop_create.is_set():
            if not retire.work(1024):
                time.sleep(0.001)

    with _annotation("bench.anchor"):
        t0 = time.monotonic()
    rec.t0 = t0
    rec.notes["anchor_mono"] = t0
    th = threading.Thread(target=creator, name="bench-creator", daemon=True)
    th.start()
    grace = float(traffic.get("grace_s", 60))
    keys = [a.spec.key for a in arrivals]
    try:
        while True:
            with _annotation("stream.step"):
                popped = system.step()
            now = time.monotonic()
            if now >= t0 + seconds:
                if counter.armed:
                    counter.armed = False
                    spans1 = _spans_now()
                if len(bind_ts) >= len(keys) or now > t0 + seconds + grace:
                    break
            if not popped and system.idle:
                with _annotation("stream.idle_wait"):
                    system.wait(0.002)
    finally:
        stop_create.set()
        th.join(timeout=30)
        system.close()
    rec.trace_stop = time.monotonic()
    if trace:
        # before the profiler's slow stop: the tracer rotates its phase
        # sums once its 60 s window is over
        tracer.disable()
        rec.podtrace = tracer.snapshot()
        _stop_trace()
    rec.compiles_in_window = counter.count
    rec.notes["compile_events"] = dict(counter.names)
    rec.notes["compiled_programs"] = dict(counter.programs)
    rec.notes["traced_functions"] = dict(counter.traced.most_common(10))
    rec.spans = spans_diff(spans0, spans1)
    rec.memory_peak_bytes = _memory_peak()

    due = np.array([a.due for a in arrivals])
    bound = np.array([bind_ts.get(k, np.nan) for k in keys]) - t0
    rec.attempted = len(keys)
    rec.failed = int(np.isnan(bound).sum())
    rec.latencies_s = (bound - due)[~np.isnan(bound)]
    rec.bound_in_window = int(((bound >= 0) & (bound <= seconds)).sum())
    # the intervals a traced run reduces over: each burst, from its due
    # time to its last bind
    groups = collections.defaultdict(list)
    for a, b in zip(arrivals, bound):
        groups[a.group].append((a.due, b))
    per = rec.notes["bursts"] = []
    for g, rows in sorted(groups.items()):
        ends = np.array([b for _, b in rows if not np.isnan(b)])
        if len(ends) and len(ends) == len(rows):
            rec.intervals.append((f"burst{g}", t0 + rows[0][0],
                                  t0 + ends.max(), len(rows)))
            lat = ends - rows[0][0]
            per.append([g, float(np.percentile(lat, 50) * 1e3),
                        float(np.percentile(lat, 99) * 1e3),
                        float(lat.max() * 1e3)])
    rec.notes["creator_lag_p99_ms"] = (
        float(np.percentile(lags, 99) * 1e3) if lags else 0.0)
    rec.notes["backlog_at_close"] = int((np.isnan(bound)
                                         | (bound > seconds)).sum())
    if sched is not None:
        rec.checks["cache_vs_store"] = _audit_cache(sched, api)
    rec.events = pod_events(api, rv0)
    # a bind of the slowest pod is always among those the gap is read at
    lat_by_key = {k: b - d for k, b, d in zip(keys, bound, due)
                  if not np.isnan(b)}
    rec.notes["slowest"] = max(lat_by_key, key=lat_by_key.get) \
        if lat_by_key else None
    del system, sched, api, progs, fac
    gc.unfreeze()
    gc.collect()
    return rec


class _ProgramStream:
    """The program's always-on loop, stepped by the driver so each step
    sits inside a TraceAnnotation (ScheduleLoop.run does the same: step,
    and park on the watch when nothing is queued)."""

    def __init__(self, sched, loop):
        self.sched, self.loop = sched, loop

    @property
    def observer(self):
        return self.sched.wave_observer

    @observer.setter
    def observer(self, fn):
        self.sched.wave_observer = fn

    def step(self) -> int:
        return self.loop.step()["popped"]

    @property
    def idle(self) -> bool:
        return self.loop.idle

    def wait(self, s: float) -> None:
        self.sched.sync(wait=s)

    def close(self) -> None:
        self.loop.close()


def _warm_stream(loop, api, cfg, traffic, fac, seed: int) -> None:
    """Compile every wave shape the window can use: one wave at each size
    of the loop's quantum ladder up to the first that holds a whole
    burst, of the cell's own mix and app names, and one of 1.4 times each
    size below the top (a partial wave: the same padded shape, but the
    strict tail's own sizes fall between the ladder's); then one whole
    burst of the traffic as the window sends it, in a seeded order (the
    quantum's walk and the class counts of shuffled waves with it). No
    warm wave holds more pods than a burst: a pop pads to the next ladder
    size, and a mix whose pods cannot all be placed at once never drains.
    The warm pods are deleted so the window starts from the set-up
    state."""
    burst = int(traffic["arrivals"]["pods"])
    ladder = _ladder(loop.min_quantum, loop.max_quantum)
    top = next((s for s in ladder if s >= burst), ladder[-1])
    for s in ladder:
        if s > top:
            break
        _warm_batch(loop, api, fac,
                    spec.mix_pods(cfg, min(s, burst), f"warm{s}-"),
                    quantum=s)
        m = int(1.4 * s)
        if 2 * s <= top and m <= burst:
            _warm_batch(loop, api, fac,
                        spec.mix_pods(cfg, m, f"warm{m}-"),
                        quantum=2 * s)
    pods = spec.mix_pods(cfg, burst, "rehearsal-")
    order = spec.rng_for(seed, "rehearsal").permutation(len(pods))
    _warm_batch(loop, api, fac, [pods[int(i)] for i in order],
                quantum=loop.min_quantum)
    loop.quantum = loop.min_quantum


def _warm_batch(loop, api, fac, pods, quantum: int) -> None:
    for p in pods:
        api.create("Pod", fac.pod(p))
    loop.quantum = quantum
    loop.drain()
    for p in pods:
        api.delete("Pod", p.namespace, p.name)
    loop.drain()


# --------------------------------------------------------------- extender


class StockClient:
    """A stock kube-scheduler's side of the extender protocol, one
    keep-alive connection, one scheduleOne at a time."""

    def __init__(self, port: int, node_names: List[str], prefix: str):
        import http.client
        self._mk = lambda: http.client.HTTPConnection("127.0.0.1", port,
                                                      timeout=120)
        self.conn = self._mk()
        self.names = node_names
        self.prefix = prefix
        self.rr = 0

    def post(self, verb: str, obj) -> object:
        body = json.dumps(obj)
        with _annotation(f"http.{verb}"):
            for attempt in (0, 1):
                try:
                    self.conn.request("POST", f"{self.prefix}/{verb}", body,
                                      {"Content-Type": "application/json"})
                    resp = self.conn.getresponse()
                    data = resp.read()
                    break
                except (ConnectionError, OSError):
                    self.conn.close()
                    self.conn = self._mk()
                    if attempt:
                        raise
        if resp.status != 200:
            raise RuntimeError(f"/{verb}: HTTP {resp.status} {data[:200]!r}")
        return json.loads(data)

    def schedule_one(self, pod, wire) -> Tuple[str, list, list]:
        """/filter, /prioritize, /bind; returns (node, passed, scores)."""
        r = self.post("filter", {"Pod": wire, "NodeNames": self.names})
        if r.get("Error"):
            raise RuntimeError(f"/filter: {r['Error']}")
        passed = r.get("NodeNames") or []
        if not passed:
            raise RuntimeError(f"/filter: no node fits {pod.key()}")
        scores = self.post("prioritize", {"Pod": wire, "NodeNames": passed})
        top = max(e["Score"] for e in scores)
        ties = [e["Host"] for e in scores if e["Score"] == top]
        node = ties[self.rr % len(ties)]   # selectHost's round robin
        self.rr += 1
        b = self.post("bind", {"PodName": pod.name,
                               "PodNamespace": pod.namespace,
                               "PodUID": pod.uid, "Node": node,
                               "Pod": wire})
        if b.get("Error"):
            raise RuntimeError(f"/bind: {b['Error']}")
        return node, passed, scores

    def close(self) -> None:
        self.conn.close()


def extender(cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, system=None) -> Record:
    from kubernetes_tpu.api import serde
    from kubernetes_tpu.server.extender import (
        ExtenderHTTPServer,
        TPUExtenderBackend,
    )
    from kubernetes_tpu.testing.churn import extender_store_binder

    rec = Record(seconds=seconds)
    phases = rec.notes["setup_phases"] = {}
    t_phase = time.monotonic()
    nodes = spec.make_nodes(cfg)
    placed = spec.background(cfg, nodes, seed)
    fac = PodFactory()
    api = _build_store(nodes, placed, fac, max_log=len(placed) + 2_000_000)
    rec.nodes, rec.setup_pods = nodes, placed
    names = [n.name for n in nodes]
    phases["build"] = time.monotonic() - t_phase
    t_phase = time.monotonic()
    backend = TPUExtenderBackend(binder=extender_store_binder(api))
    backend.sync_nodes(program_nodes(nodes))
    backend.sync_pods(api.list("Pod")[0])
    phases["hydrate"] = time.monotonic() - t_phase
    if system is not None:
        system.install(backend)
    srv = ExtenderHTTPServer(backend, prefix="/scheduler")
    srv.start()
    client = StockClient(srv.port, names, "/scheduler")
    try:
        t_phase = time.monotonic()
        _warm_extender(client, api, backend, cfg, fac, serde)
        phases["warm"] = time.monotonic() - t_phase
        pool = _extender_pool(cfg, seed)
        sample_rng = spec.rng_for(seed, "extender-sample")
        every = int(traffic.get("sample_every", 8))
        counter = CompileCounter()
        gc.collect()
        gc.freeze()
        rv0 = api.current_rv()
        routes0 = backend.eval_cache.oracle_routes
        spans0 = _spans_now()
        if trace:
            _start_trace()
        counter.armed = True
        lat = rec.extender_s
        slowest = None
        informer = _Informer(api, backend, float(traffic["informer_sync_s"]))
        with _annotation("bench.anchor"):
            t0 = time.monotonic()
        rec.t0 = t0
        rec.notes["anchor_mono"] = t0
        end = t0 + seconds
        i = 0
        informer.__enter__()
        while time.monotonic() < end:
            if i >= len(pool):
                raise RuntimeError("extender pool exhausted")
            s = pool[i]
            pod = fac.pod(s)
            with _annotation("create"):
                api.create("Pod", pod)
            wire = serde.encode_pod(pod)
            rv = api.current_rv()
            ts = time.perf_counter()
            node, passed, scores = client.schedule_one(pod, wire)
            dt = time.perf_counter() - ts
            lat.append(dt)
            rec.specs[s.key] = s
            row = None
            if sample_rng.integers(every) == 0:
                row = {"key": s.key, "rv": rv, "node": node,
                       "passed": passed, "scores": scores}
                rec.samples.append(row)
            if slowest is None or dt > slowest[0]:
                slowest = (dt, row or {"key": s.key, "rv": rv, "node": node,
                                       "passed": passed, "scores": scores})
            i += 1
        informer.__exit__()
        rec.notes["informer_syncs"] = informer.syncs
        rec.trace_stop = time.monotonic()
        counter.armed = False
        spans1 = _spans_now()
        if trace:
            _stop_trace()
        if slowest is not None and not any(r is slowest[1]
                                           for r in rec.samples):
            rec.samples.append(slowest[1])
        rec.compiles_in_window = counter.count
        rec.notes["compile_events"] = dict(counter.names)
        rec.notes["compiled_programs"] = dict(counter.programs)
        rec.notes["host_routes_in_window"] = \
            backend.eval_cache.oracle_routes - routes0
        rec.spans = spans_diff(spans0, spans1)
        rec.memory_peak_bytes = _memory_peak()
        rec.attempted = i
        rec.bound_in_window = i
        rec.intervals.append(("window", t0, end, i))
        rec.events = pod_events(api, rv0)
        rec.notes["rv0"] = rv0
        rec.checks["cache_vs_store"] = _audit_backend(backend, api)
    finally:
        client.close()
        srv.stop()
    del backend, srv, api, fac
    gc.unfreeze()
    gc.collect()
    return rec


def _audit_backend(backend, api) -> int:
    """The backend's cache against the store: every bound pod on the same
    node in both (BindVerb writes through the store)."""
    store = {p.key(): p.node_name for p in api.list("Pod")[0] if p.node_name}
    cache = {p.key(): name
             for name, info in backend.cache.node_infos().items()
             for p in info.pods}
    bad = sum(1 for k, n in cache.items() if store.get(k) != n)
    return bad + sum(1 for k in store if k not in cache)


def _extender_pool(cfg: dict, seed: int, n: int = 60_000
                   ) -> List[spec.PodSpec]:
    """The closed loop's pods: the mix, each run of 100 in seeded order."""
    pods = spec.mix_pods(cfg, n, "x-")
    rng = spec.rng_for(seed, "extender-order")
    out = []
    for b in range(0, n, 100):
        block = pods[b:b + 100]
        out += [block[int(j)] for j in rng.permutation(len(block))]
    return out


def _warm_extender(client, api, backend, cfg, fac, serde) -> None:
    """Every class of the mix once through /filter, /prioritize and /bind
    (the bind fence included); the informer's sync then confirms the warm
    pods, they are deleted, and a second sync drops them and interns their
    topology pairs; then each class once more through /filter and
    /prioritize, so the window's pods find the device path compiled."""
    seen, warm = set(), []
    for s in spec.mix_pods(cfg, 200, "warm-"):
        if (s.shape, s.labels) not in seen:
            seen.add((s.shape, s.labels))
            warm.append(s)
    for s in warm:
        pod = fac.pod(s)
        api.create("Pod", pod)
        client.schedule_one(pod, serde.encode_pod(pod))
    backend.sync_pods(api.list("Pod")[0])
    for s in warm:
        api.delete("Pod", s.namespace, s.name)
    backend.sync_pods(api.list("Pod")[0])
    for s in warm:
        pod = fac.pod(s)
        wire = serde.encode_pod(pod)
        passed = client.post("filter", {"Pod": wire,
                                        "NodeNames": client.names})
        client.post("prioritize", {"Pod": wire,
                                   "NodeNames": passed["NodeNames"]})


class _Informer:
    """The extender's own informer: every `every_s` it hands the backend
    the store's pods (TPUExtenderBackend.sync_pods, the bulk sync that
    confirms bound pods before the cache's 30 s assume TTL runs out)."""

    def __init__(self, api, backend, every_s: float):
        self.api, self.backend, self.every_s = api, backend, every_s
        self.stop = threading.Event()
        self.syncs = 0
        self.thread = threading.Thread(target=self._run, name="informer",
                                       daemon=True)

    def _run(self) -> None:
        while not self.stop.wait(self.every_s):
            with _annotation("informer.sync"):
                self.backend.sync_pods(self.api.list("Pod")[0])
            self.syncs += 1

    def __enter__(self):
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self.thread.join(timeout=60)
        return False


# ------------------------------------------------------------------ trace


TRACE_DIR = None


def _start_trace() -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def _stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


DRIVERS = {"stream": stream, "extender": extender}
