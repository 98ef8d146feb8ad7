"""Scheduler-extender HTTP sidecar: the integration seam into a real
kube-scheduler.

Implements the reference's extender wire contract verbatim so an unmodified
kube-scheduler with `--policy-config-file` pointing at an ExtenderConfig
(api/types.go:129) offloads findNodesThatFit / PrioritizeNodes here
(generic_scheduler.go:211-228,381-399 -> core/extender.go:100 Filter,
:157 Prioritize, :199 Bind, :226 send):

  POST {prefix}/filter      ExtenderArgs -> ExtenderFilterResult
  POST {prefix}/prioritize  ExtenderArgs -> HostPriorityList
  POST {prefix}/bind        ExtenderBindingArgs -> ExtenderBindingResult
  GET  /healthz, /metrics
  GET  /debug/vars          unified telemetry-registry snapshot (ISSUE 13
                            — identical content to the binary STATS verb
                            and the embedded debug_snapshot)
  GET  /debug/trace?last=N  the flight recorder's event tail
  GET  /debug/pods          pod-level black box (ISSUE 15): the tracer's
                            per-window critical-path aggregate + the
                            slowest-K tail-exemplar timelines
  GET  /debug/slo           the SLO engine's burn-rate/alert snapshot

Trace context (ISSUE 15): a POST /filter or /bind carrying an
``X-Pod-Trace: <id>`` header stamps one WIRE_HOP on that pod's podtrace
timeline — the HTTP twin of the binary wire's FLAG_TRACE field and the
embedded API's ``trace_ctx=``; header presence IS the sample decision.

JSON keys: the reference posts the *internal* structs (no json tags ->
capitalized keys: "Pod", "Nodes", "NodeNames"); Go's json.Unmarshal is
case-insensitive, so we accept either case and respond capitalized.

nodeCacheCapable mode (extender.go:113-124): only candidate node NAMES cross
the wire; the sidecar keeps full node/pod state in its own cache, synced via
the bulk endpoints POST /cache/nodes and /cache/pods (the "snapshot POSTs"
variant of SURVEY.md §7 step 3) and updated optimistically by bind calls.

Multi-frontend service (ISSUE 9) — the same verbs, hardened for a FLEET of
concurrent schedulers sharing one sidecar:

  - COALESCED DISPATCH: concurrent /filter + /prioritize evaluations ride
    a micro-batch window (server/coalescer.py) into ONE fused [C, N]
    kernel dispatch over the shared device-resident snapshot.
  - OPTIMISTIC CONCURRENCY (PAPERS.md §Omega): verdicts carry a
    "SnapshotGen"; each frontend evaluates against a possibly-stale
    snapshot (bounded by ``stale_window_s``) and /bind commits through a
    FENCE that re-validates capacity/ports/liveness/topology against
    current cache truth, answering a typed HTTP 409 CONFLICT (body carries
    "RetryAfterMs") the client retries with jittered backoff.
  - EXACTLY-ONCE BINDS: /bind accepts an "IdempotencyKey"; a timed-out-
    but-landed bind replays safely through the BindLedger (state/cache.py)
    — the retry converges on the recorded node instead of double-booking.
  - BACKPRESSURE: bounded coalescer queue + per-verb in-flight cap answer
    HTTP 429 + Retry-After past the dispatch budget; a request whose
    client deadline ("DeadlineMs") elapsed while queued is shed (504).

Optional request fields (ignored by a stock kube-scheduler, used by our
multi-frontend clients): /filter {"Compact": true} elides the echo of an
all-passed candidate list; /prioritize {"TopK": k} returns only the k
top-scored hosts (still a valid HostPriorityList); /bind {"SnapshotGen",
"IdempotencyKey", "DeadlineMs", "Pod": <spec>} — shipping the spec lets
the fence do exact capacity math instead of the identifiers-only wire's
zero-resource assume.
"""

from __future__ import annotations

import json
import random
import threading
from kubernetes_tpu.analysis import lockcheck
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Protocol, Tuple

from kubernetes_tpu.api import serde
from kubernetes_tpu.api.types import Node, Pod
from kubernetes_tpu.server.coalescer import (
    DeadlineExceeded,
    EvalCoalescer,
    Overloaded,
)
from kubernetes_tpu.utils.trace import timed_span


class ExtenderBackend(Protocol):
    def filter(self, pod: Pod, nodes: Optional[List[Node]],
               node_names: Optional[List[str]]
               ) -> Tuple[List[str], Dict[str, str]]: ...

    def prioritize(self, pod: Pod, nodes: Optional[List[Node]],
                   node_names: Optional[List[str]]
                   ) -> List[Tuple[str, int]]: ...

    def bind(self, pod_name: str, pod_namespace: str, pod_uid: str,
             node: str) -> str: ...

    def sync_nodes(self, nodes: List[Node]) -> None: ...

    def sync_pods(self, pods: List[Pod]) -> None: ...

    def metrics_text(self) -> str: ...


def _pod_key(payload: Dict) -> str:
    """A verb's pod as "namespace/name", for the metadata of its spans:
    the shipped pod's, else /bind's identifiers."""
    meta = (payload.get("Pod") or payload.get("pod") or {}).get(
        "metadata") or {}
    ns = meta.get("namespace") or payload.get("PodNamespace") or ""
    return f"{ns}/{meta.get('name') or payload.get('PodName') or ''}"


class _FleetHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer tuned for a fleet of keep-alive frontends
    (ISSUE 9 satellite): the stock accept backlog of 5 refuses connections
    the moment ~100 clients dial in together, and a non-daemon handler
    thread wedged on a dead client would block shutdown."""

    request_queue_size = 256
    daemon_threads = True


class ExtenderHTTPServer:
    def __init__(self, backend: ExtenderBackend, host: str = "127.0.0.1",
                 port: int = 0, prefix: str = "", max_inflight: int = 256):
        self.backend = backend
        self.prefix = prefix.rstrip("/")
        # per-verb in-flight admission (the HTTP half of the backpressure
        # story; the coalescer bounds its own queue below this)
        self.max_inflight = max_inflight
        self._inflight = 0
        self._adm_lock = lockcheck.make_lock("ExtenderHTTPServer._adm_lock")
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # a dead client's half-open socket must not pin a handler
            # thread forever (daemon_threads bounds shutdown, this bounds
            # the thread count)
            timeout = 120

            def log_message(self, *a):  # quiet
                pass

            def _read_raw(self):
                length = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(length) if length else b""

            # the POST's pod ("namespace/name") while a profiler capture
            # records its spans; "" otherwise
            trace_pod = ""

            def _write_json(self, obj, code: int = 200, headers=None):
                if self.command != "POST":
                    # introspection stays untimed: a span counted while
                    # serving /debug/vars would change the registry every
                    # transport serves
                    self._send_json(obj, code, headers)
                    return
                with timed_span("extender.http.encode") as span:
                    if span.recording and self.trace_pod:
                        span.annotate(pod=self.trace_pod)
                    self._send_json(obj, code, headers)

            def _send_json(self, obj, code, headers):
                # compact separators: a 5k-node HostPriorityList is ~230KB
                # of response; the default ", " padding costs measurable
                # serialize+wire time at compat-mode request rates
                body = json.dumps(obj, separators=(",", ":")).encode()
                try:
                    self.send_response(code)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    for k, v in (headers or {}).items():
                        self.send_header(k, v)
                    self.end_headers()
                    self.wfile.write(body)
                except (BrokenPipeError, ConnectionResetError):
                    # the client gave up (its timeout elapsed) — a fleet
                    # norm, not a server error: drop the socket quietly
                    # instead of letting ThreadingHTTPServer print a
                    # traceback per dead peer
                    self.close_connection = True

            def do_GET(self):
                if self.path == "/healthz":
                    body = b"ok"
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/metrics":
                    body = outer.backend.metrics_text().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/debug/vars":
                    # live introspection (ISSUE 13): the unified registry
                    # snapshot — identical content to the binary STATS
                    # verb and the embedded debug_snapshot, test-pinned
                    dv = getattr(outer.backend, "debug_vars", None)
                    if dv is None:
                        self._write_json({"error": "not found"}, 404)
                    else:
                        self._write_json(dv())
                elif self.path.split("?", 1)[0] == "/debug/trace":
                    dt = getattr(outer.backend, "debug_trace", None)
                    if dt is None:
                        self._write_json({"error": "not found"}, 404)
                    else:
                        from urllib.parse import parse_qs, urlsplit
                        q = parse_qs(urlsplit(self.path).query)
                        try:
                            # absent param -> a BOUNDED default tail (a
                            # full 65k-event ring is a multi-MB body);
                            # an explicit last (0 included) means
                            # exactly what it means on the other
                            # transports
                            last = int(q.get("last", ["256"])[0])
                        except ValueError:
                            last = 256
                        self._write_json(dt(last))
                elif self.path == "/debug/pods":
                    # pod-level black box (ISSUE 15) — identical content
                    # to the binary STATS verb's "pods" key and the
                    # embedded debug_snapshot, test-pinned
                    dp = getattr(outer.backend, "debug_pods", None)
                    if dp is None:
                        self._write_json({"error": "not found"}, 404)
                    else:
                        self._write_json(dp())
                elif self.path == "/debug/slo":
                    ds = getattr(outer.backend, "debug_slo", None)
                    if ds is None:
                        self._write_json({"error": "not found"}, 404)
                    else:
                        self._write_json(ds())
                else:
                    self._write_json({"error": "not found"}, 404)

            def do_POST(self):
                path = self.path
                if outer.prefix and path.startswith(outer.prefix):
                    path = path[len(outer.prefix):]
                # read the body FIRST, unconditionally: on a keep-alive
                # connection an unread body (unknown path, early error)
                # would desync every later request on the socket — the
                # head-of-line audit of the ISSUE 9 satellite
                raw = self._read_raw()
                self.trace_pod = ""
                try:
                    if path in ("/cache/nodes", "/cache/pods"):
                        # bulk sync: binary fast path (protobuf, SURVEY
                        # §5.8 — the --kube-api-content-type analog) or
                        # the JSON contract, picked by Content-Type
                        from kubernetes_tpu.api import protowire
                        ctype = self.headers.get("Content-Type", "")
                        is_nodes = path == "/cache/nodes"
                        if ctype == protowire.CONTENT_TYPE:
                            if not protowire.available():
                                # negotiable failure: tell the client to
                                # fall back to the JSON contract
                                self._write_json(
                                    {"Error": "protobuf unavailable; use "
                                     "application/json"}, 415)
                                return
                            items = (protowire.decode_nodes(raw) if is_nodes
                                     else protowire.decode_pods(raw))
                        else:
                            raw_items = json.loads(raw or b"{}").get(
                                "items", [])
                            items = [(serde.decode_node(o) if is_nodes
                                      else serde.decode_pod(o))
                                     for o in raw_items]
                        if is_nodes:
                            outer.backend.sync_nodes(items)
                        else:
                            outer.backend.sync_pods(items)
                        self._write_json({"synced": len(items)})
                        return
                    if path not in ("/filter", "/prioritize", "/bind"):
                        self._write_json(
                            {"error": f"unknown path {self.path}"}, 404)
                        return
                    if not outer._admit():
                        # jittered Retry-After: a fleet shed together must
                        # not return together (thundering-herd starvation
                        # of the same unlucky clients every window)
                        self._write_json(
                            {"Error": "overloaded",
                             "RetryAfterMs": random.randint(10, 80)},
                            429, headers={"Retry-After": "1"})
                        return
                    tid = self.headers.get("X-Pod-Trace")
                    if tid and path in ("/filter", "/bind"):
                        # trace-context hop (ISSUE 15): header presence
                        # is the client's head decision — honor it
                        from kubernetes_tpu.observability import podtrace
                        if podtrace.TRACER.enabled:
                            podtrace.TRACER.wire_hop(
                                tid, podtrace.WIRE_HTTP,
                                podtrace.HOP_FILTER if path == "/filter"
                                else podtrace.HOP_BIND)
                    try:
                        with timed_span("extender.http.decode") as span:
                            payload = json.loads(raw or b"{}")
                            if span.recording:
                                self.trace_pod = _pod_key(payload)
                                span.annotate(pod=self.trace_pod)
                        if path == "/filter":
                            out, code = outer.handle_filter(payload), 200
                        elif path == "/prioritize":
                            out, code = outer.handle_prioritize(payload), 200
                        else:
                            out, code = outer.handle_bind(payload)
                            if tid and code == 200 \
                                    and not out.get("Error"):
                                # complete the wire-path trace: the
                                # sidecar has no scheduler bind path to
                                # terminate the timeline (embedded.py
                                # trace_bound docstring)
                                from kubernetes_tpu.server.embedded \
                                    import VerdictService
                                VerdictService.trace_bound(tid)
                        self._write_json(out, code)
                    finally:
                        outer._release()
                except Overloaded as e:
                    self._write_json(
                        {"Error": "overloaded",
                         "RetryAfterMs": int(e.retry_after_s * 1e3)},
                        429, headers={"Retry-After": "1"})
                except DeadlineExceeded:
                    self._write_json({"Error": "DEADLINE_EXCEEDED"}, 504)
                except Exception as e:  # wire errors surface in-band, like the
                    # reference's ExtenderFilterResult.Error (types.go:177)
                    self._write_json({"Error": f"{type(e).__name__}: {e}"}, 500)

        self.httpd = _FleetHTTPServer((host, port), Handler)
        self._thread: Optional[threading.Thread] = None
        # transport-agnostic service core (ISSUE 11): the verdict-capable
        # paths below delegate here, the SAME core the async binary wire
        # (server/asyncwire.py) and the embedded mode (server/embedded.py)
        # serve — no transport owns a semantic. Local import: embedded.py
        # imports this module for TPUExtenderBackend.
        self.service = None
        if getattr(backend, "fused_verdict", None) is not None \
                and getattr(backend, "filter_verdict", None) is not None:
            from kubernetes_tpu.server.embedded import VerdictService
            self.service = VerdictService(backend)

    # ------------------------------------------------------- admission gate

    def _admit(self) -> bool:
        with self._adm_lock:
            if self._inflight >= self.max_inflight:
                count = getattr(self.backend, "_count", None)
                if count is not None:
                    count("admission_shed")
                return False
            self._inflight += 1
            return True

    def _release(self) -> None:
        with self._adm_lock:
            self._inflight -= 1

    # -------------------------------------------------------------- handlers

    @staticmethod
    def _get(payload: Dict, *names):
        for n in names:
            if n in payload:
                return payload[n]
        return None

    def _parse_args(self, payload: Dict) -> Tuple[Pod, Optional[List[Node]],
                                                  Optional[List[str]]]:
        with timed_span("extender.http.decode"):
            pod_obj = self._get(payload, "Pod", "pod") or {}
            pod = serde.decode_pod(pod_obj)
            nodes_obj = self._get(payload, "Nodes", "nodes")
            nodes = None
            if nodes_obj:
                nodes = [serde.decode_node(n)
                         for n in (nodes_obj.get("Items")
                                   or nodes_obj.get("items") or [])]
            names = self._get(payload, "NodeNames", "nodenames",
                              "nodeNames")
        return pod, nodes, names

    @staticmethod
    def _deadline_of(payload: Dict) -> Optional[float]:
        ms = payload.get("DeadlineMs")
        return float(ms) / 1e3 if ms else None

    def handle_filter(self, payload: Dict) -> Dict:
        pod, nodes, names = self._parse_args(payload)
        top_k = int(payload.get("TopK") or 0)
        if self.service is None or nodes is not None:
            passed, failed = self.backend.filter(pod, nodes, names)
            if nodes is not None:
                by_name = {n.name: n for n in nodes}
                return {
                    "Nodes": {"Items": [serde.encode_node(by_name[nm])
                                        for nm in passed if nm in by_name]},
                    "FailedNodes": failed,
                    "Error": "",
                }
            return {"NodeNames": passed, "FailedNodes": failed, "Error": ""}
        # verdict-capable cache mode: ONE service-core call answers the
        # verb (and, with TopK, the fused top scores of the same window
        # ticket — a fleet scheduleOne skips /prioritize entirely); this
        # JSON shaping is all that stays transport-specific
        v = self.service.filter(
            pod, node_names=names, top_k=top_k,
            deadline_s=self._deadline_of(payload),
            compact=bool(payload.get("Compact")))
        out = {"NodeNames": v.passed, "FailedNodes": v.failed, "Error": ""}
        if v.snapshot_gen is not None:
            out["SnapshotGen"] = v.snapshot_gen
        if v.top_scores is not None:
            out["TopScores"] = [{"Host": h, "Score": int(s)}
                                for h, s in v.top_scores]
        if v.passed is None:
            # multi-frontend compact mode: the echo of an all-passed 5k-
            # name candidate list costs more wire time than the verdict —
            # "everything passed" is one bit + a count
            out["AllPassed"] = True
            out["PassedCount"] = v.passed_count
        return out

    def handle_prioritize(self, payload: Dict) -> List[Dict]:
        pod, nodes, names = self._parse_args(payload)
        top_k = int(payload.get("TopK") or 0)
        pv = getattr(self.backend, "prioritize_verdict", None)
        if pv is None or nodes is not None:
            scores = self.backend.prioritize(pod, nodes, names)
        else:
            # TopK resolves server-side, vectorized (prioritize_verdict):
            # truncation stays a valid HostPriorityList; our frontends
            # pick among the max-score entries, so shipping the tail is
            # pure wire cost (PAPERS.md §Sparrow: sample, don't census)
            scores, _gen = pv(
                pod, names, deadline_s=self._deadline_of(payload),
                top_k=top_k if names is None else 0)
        if top_k and len(scores) > top_k:
            import heapq
            scores = heapq.nlargest(top_k, scores, key=lambda e: e[1])
        return [{"Host": h, "Score": int(s)} for h, s in scores]

    def handle_bind(self, payload: Dict) -> Tuple[Dict, int]:
        pod_name = self._get(payload, "PodName", "podName") or ""
        pod_ns = self._get(payload, "PodNamespace", "podNamespace") or ""
        pod_uid = str(self._get(payload, "PodUID", "podUID") or "")
        node = self._get(payload, "Node", "node") or ""
        if self.service is None \
                or getattr(self.backend, "bind_verdict", None) is None:
            return {"Error": self.backend.bind(
                pod_name, pod_ns, pod_uid, node)}, 200
        spec = None
        spec_obj = self._get(payload, "Pod", "pod")
        if spec_obj:
            with timed_span("extender.http.decode"):
                spec = serde.decode_pod(spec_obj)
        gen = payload.get("SnapshotGen")
        res = self.service.bind(
            pod_name, pod_ns, pod_uid, node,
            snapshot_gen=int(gen) if gen is not None else None,
            idem_key=payload.get("IdempotencyKey") or None,
            deadline_s=self._deadline_of(payload), pod=spec)
        out: Dict = {"Error": res.error}
        if res.retryable:
            out["Conflict"] = True
            out["RetryAfterMs"] = max(int(res.retry_after_s * 1e3), 1)
            return out, 409
        if res.kind == "shed":
            return out, 504
        return out, 200

    # ------------------------------------------------------------- lifecycle

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        if self._thread:
            self._thread.join(timeout=5)


class _Verdict:
    """One pod's evaluation against the shared snapshot, captured with the
    node order / index / generation of the SAME critical section — so the
    HTTP response builds outside every lock without torn state."""

    __slots__ = ("m", "s", "names", "idx", "gen")

    def __init__(self, m, s, names, idx, gen):
        self.m = m
        self.s = s
        self.names = names
        self.idx = idx
        self.gen = gen


class TPUExtenderBackend:
    """The TPU-offload backend: sidecar-owned SchedulerCache + fused kernels.

    Filter/prioritize evaluate the pod against the sidecar's cached cluster
    state (or against the Nodes shipped in the args when not cache-capable),
    restricted to the candidate set the scheduler sent — exactly the
    contract of extender.go:100-198. Bind assumes into the local cache and
    delegates the apiserver write to `binder` (None = extender not configured
    with BindVerb).

    Warm fast lane (the cache-capable path): cluster state lives DEVICE-
    resident between requests. The backend owns its SchedulerCache
    exclusively — every mutation arrives through sync_nodes / sync_pods /
    bind — so it tracks staleness itself instead of re-deriving it per
    request:

      - sync_* marks a FULL refresh (membership/spec may have moved) and
        invalidates the EvalCache (on_sync);
      - bind marks a TARGETED refresh of just the bound node
        (snapshot.refresh changed_hint — one dynamic row, not an N-node
        generation walk);
      - a request with nothing dirty touches no cluster state at all: the
        snapshot, the uploaded node arrays, the encoded classes and the
        (fits, scores) result memo are all valid, so /prioritize after
        /filter is a dict hit.

    Node arrays ride SchedulingEngine._nodes_on_device (incremental
    dirty-only host->HBM sync), so a bind re-uploads three small dynamic
    arrays, not the 40MB+ snapshot."""

    def __init__(self, binder=None, stale_window_s: float = 0.0,
                 coalesce_window_s: float = 0.0, coalesce_max_batch: int = 64,
                 coalesce_max_depth: int = 512):
        # jax-dependent imports are local so the wire layer stays importable
        # without a TPU runtime
        from kubernetes_tpu.state.cache import BindLedger, SchedulerCache
        from kubernetes_tpu.engine.scheduler_engine import (
            EvalCache,
            SchedulingEngine,
        )
        from kubernetes_tpu.utils.metrics import SchedulerMetrics

        self.cache = SchedulerCache()
        self.engine = SchedulingEngine(self.cache)
        self.metrics = SchedulerMetrics()
        self.binder = binder
        self._known_pods: Dict[str, Pod] = {}
        # per-request amortization + vocab-growth isolation (EvalCache
        # docstring; the reference amortizes the same work through its
        # scheduler cache + equivalence LRU)
        self.eval_cache = EvalCache()
        # staleness ledger for the warm lane (class docstring); guarded by
        # _lock — ThreadingHTTPServer serves each request on its own thread
        self._lock = lockcheck.make_rlock("TPUExtenderBackend._lock")
        self._state_dirty = True          # full refresh needed
        self._bind_hint: set = set()      # targeted refresh of these nodes
        self._infos = None                # cached node_infos() view
        self._aff_pod_count = 0           # cached pods carrying pod affinity
        # pods assumed by bind BEFORE any sync shipped their spec: /bind
        # carries only identifiers, so their accounting is spec-less until
        # the bulk cache sync delivers the real object (and replaces it)
        self._assumed_bare: Dict[str, Pod] = {}
        self._last_cleanup = 0.0
        self.eval_cache.cluster_aff_free = True
        # ---- multi-frontend service state (ISSUE 9) ----
        # Omega-style bounded staleness: within this window, bind-hinted
        # snapshot refreshes are DEFERRED, so verdicts serve from the memo
        # while commits advance — the bind fence re-validates every commit
        # against live cache truth, so staleness costs conflicts (reported),
        # never correctness. 0.0 = always fresh (the PR 1-8 behavior).
        self.stale_window_s = stale_window_s
        self._last_refresh = 0.0
        # commit_gen: bumped per committed mutation (bind assume/rollback,
        # bulk sync). _snap_gen: the commit_gen the snapshot reflects —
        # what verdicts report as "SnapshotGen"; a /bind whose verdict gen
        # equals the CURRENT commit_gen provably re-validated nothing away
        # and may skip the fence.
        self.commit_gen = 0
        self._snap_gen = 0
        self.ledger = BindLedger()
        # service counters: own lock, so /metrics scrapes and coalescer
        # increments never contend with (or tear against) the eval lock —
        # the ISSUE 9 torn-read audit
        self._counters_lock = lockcheck.make_lock("TPUExtenderBackend._counters_lock")
        self._counters: Dict[str, int] = {}
        self._rng = random.Random(0xB19D)
        self.coalescer = EvalCoalescer(self, window_s=coalesce_window_s,
                                       max_batch=coalesce_max_batch,
                                       max_depth=coalesce_max_depth)
        # unified telemetry registry (ISSUE 13): the ONE namespace every
        # introspection transport serves — HTTP /debug/vars, the binary
        # STATS verb, VerdictService.debug_snapshot and /metrics all read
        # THIS (transport parity is a dict equality, test-pinned). Each
        # source snapshots under its own lock, in sequence, never nested
        # — the r12 torn-read discipline carried over.
        from kubernetes_tpu.observability.registry import TelemetryRegistry
        self.telemetry = TelemetryRegistry()
        self.telemetry.register_metrics("extender", self.metrics)
        self.telemetry.register_counters("extender", self._counters_snapshot,
                                         prom_prefix="tpu_extender")
        self.telemetry.register_gauges("extender", self._gen_gauges)

    def _count(self, name: str, n: int = 1) -> None:
        with self._counters_lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def _counters_snapshot(self) -> Dict[str, int]:
        with self._counters_lock:
            return dict(self._counters)

    def _gen_gauges(self) -> Dict[str, int]:
        with self._lock:
            return {"tpu_extender_commit_gen": self.commit_gen,
                    "tpu_extender_snapshot_gen": self._snap_gen}

    def debug_vars(self) -> Dict:
        """The registry snapshot /debug/vars (and every other transport)
        serves."""
        return self.telemetry.snapshot()

    def debug_trace(self, last: int = 0):
        """The flight recorder's event tail for /debug/trace?last=N.
        ``last <= 0`` returns NOTHING — identical semantics on every
        transport (binary STATS, embedded debug_snapshot), so parity
        holds for every literal ``last`` value; a full-ring dump is an
        explicit ``last >= recorder.capacity`` (the capacity travels in
        /debug/vars as ``recorder.capacity``)."""
        from kubernetes_tpu.observability.recorder import RECORDER
        return RECORDER.snapshot(last) if last > 0 else []

    def debug_pods(self):
        """The pod tracer's /debug/pods payload (ISSUE 15) — per-window
        critical-path aggregate + slowest-K exemplar timelines,
        identical on every transport."""
        from kubernetes_tpu.observability.podtrace import TRACER
        return TRACER.snapshot()

    def debug_slo(self):
        """The SLO engine's /debug/slo payload (ISSUE 15), identical on
        every transport. The fast tier's 10 ms objective (ISSUE 17)
        rides under the "fast" key so both tiers land in one scrape."""
        from kubernetes_tpu.observability.slo import SLO, SLO_FAST
        return {**SLO.snapshot(), "fast": SLO_FAST.snapshot()}

    # -- cache sync ---------------------------------------------------------

    # assumed-pod TTL sweep cadence: the sidecar has no informer confirm
    # loop — the bulk cache sync IS the confirmation — so a bind whose pod
    # never reappears in a sync (deleted at the apiserver, write lost)
    # must expire via the cache's own TTL or its phantom pod_count/capacity
    # leaks for the process lifetime
    CLEANUP_INTERVAL_S = 5.0

    def _maybe_cleanup_assumed_locked(self) -> None:
        """Time-gated cleanup_assumed (cache.go:355 analog) — called with
        the lock held from the sync/refresh paths."""
        lockcheck.assert_held(self._lock, "_maybe_cleanup_assumed_locked")
        import time as _time
        now = _time.monotonic()
        if now - self._last_cleanup < self.CLEANUP_INTERVAL_S:
            return
        self._last_cleanup = now
        expired = self.cache.cleanup_assumed()
        if expired:
            for k in expired:
                self._assumed_bare.pop(k, None)
            self._state_dirty = True  # released capacity: full re-walk
            # cache truth moved like any other mutation: a verdict issued
            # before the expiry must NOT satisfy the fence-skip gen check
            # against the post-expiry state
            self.commit_gen += 1

    def sync_nodes(self, nodes: List[Node]) -> None:
        with self._lock:
            self.eval_cache.on_sync()
            self._state_dirty = True
            self.commit_gen += 1
            self._bind_hint.clear()
            self._maybe_cleanup_assumed_locked()
            seen = set()
            for n in nodes:
                self.cache.update_node(n)
                seen.add(n.name)
            removed = False
            for name in list(self.cache.node_infos().keys()):
                if name not in seen:
                    self.cache.remove_node(name)
                    removed = True
            if removed:
                # the sidecar's sync is a wholesale reconcile that already
                # escalates to a full refresh — compact the ISSUE 8
                # tombstones right away instead of accruing dead rows
                self.cache.purge_tombstones()

    def sync_pods(self, pods: List[Pod]) -> None:
        from kubernetes_tpu.ops.affinity import _has_affinity
        with self._lock:
            self.eval_cache.on_sync()
            self._state_dirty = True
            self.commit_gen += 1
            self._bind_hint.clear()
            self._maybe_cleanup_assumed_locked()
            seen = set()
            for p in pods:
                if not p.node_name:
                    continue
                seen.add(p.key())
                prev = self._known_pods.get(p.key())
                if prev is None:
                    bare = self._assumed_bare.pop(p.key(), None)
                    if bare is not None:
                        # bind assumed this pod WITHOUT its spec (wire
                        # carries identifiers only): swap the spec-less
                        # accounting for the real object — the confirm
                        # path alone would keep the zero-resource rows
                        self.cache.remove_pod(bare)
                    self.cache.add_pod(p)
                else:
                    self.cache.update_pod(prev, p)
                self._known_pods[p.key()] = p
            # full-state semantics, like sync_nodes: pods absent from the
            # snapshot were deleted — release their capacity
            for key in list(self._known_pods):
                if key not in seen:
                    self.cache.remove_pod(self._known_pods.pop(key))
            self._aff_pod_count = sum(
                1 for p in self._known_pods.values() if _has_affinity(p))
            self.eval_cache.cluster_aff_free = self._aff_pod_count == 0

    # -- extender verbs -----------------------------------------------------

    def _refresh_warm_locked(self):
        """Bring the persistent snapshot up to date with the cache, paying
        only for what actually moved (class docstring). Returns the live
        infos view.

        Bounded staleness (ISSUE 9, PAPERS.md §Omega): when a stale_window
        is configured, BIND-hinted refreshes are deferred inside it —
        verdicts keep serving from the current snapshot version (memo
        hits, zero device work) while commits advance, and the bind fence
        re-validates every commit against live cache truth. Sync-driven
        dirtiness always refreshes immediately: membership/spec changes
        are not a staleness the fence is allowed to absorb."""
        lockcheck.assert_held(self._lock, "_refresh_warm_locked")
        import time as _time

        from kubernetes_tpu.utils.trace import COUNTERS, timed_span
        snap = self.engine.snapshot
        self._maybe_cleanup_assumed_locked()  # time-gated; a bind-only deployment
        # (no syncs ever) must still expire unconfirmed assumptions
        if self._state_dirty or self._infos is None:
            with timed_span("extender.refresh_full"):
                self._infos = self.cache.node_infos()
                snap.refresh(self._infos)
            self._state_dirty = False
            self._bind_hint.clear()
            self._snap_gen = self.commit_gen
            self._last_refresh = _time.monotonic()
        elif self._bind_hint:
            if self.stale_window_s > 0 and (
                    _time.monotonic() - self._last_refresh
                    < self.stale_window_s):
                COUNTERS.inc("extender.stale_served")
                return self._infos
            with timed_span("extender.refresh_hint"):
                hint = tuple(self._bind_hint)
                self._bind_hint.clear()
                snap.refresh(self._infos, changed_hint=hint)
            self._snap_gen = self.commit_gen
            self._last_refresh = _time.monotonic()
        return self._infos

    def _port_words_for(self, pod: Pod) -> int:
        from kubernetes_tpu.ops.predicates import bucket
        snap = self.engine.snapshot
        words = snap.port_words_used()
        for c in pod.containers:
            for p in c.ports:
                if p.host_port > 0:
                    words = max(words, p.host_port // 32 + 1)
        return bucket(max(words, 1), lo=1)

    def _eval_locked(self, pod: Pod, nodes: Optional[List[Node]]):
        lockcheck.assert_held(self._lock, "_eval_locked")
        from kubernetes_tpu.engine.scheduler_engine import evaluate_pod
        from kubernetes_tpu.state.snapshot import ClusterSnapshot

        if nodes is not None:
            # non-cache-capable: full node state ships in every request, so
            # evaluate against a FRESH snapshot — reusing the persistent one
            # would diff generation counters of unrelated NodeInfo objects
            # and silently serve stale rows
            from kubernetes_tpu.state.node_info import node_info_map
            infos = node_info_map(nodes, [p for p in self._known_pods.values()])
            snap = ClusterSnapshot()
            snap.refresh(infos)
            m, s = evaluate_pod(
                pod, infos, snap, self.engine.priorities,
                workloads=self.engine.workloads_provider(),
                hard_weight=self.engine.hard_pod_affinity_weight,
                volume_ctx=self.engine.volume_ctx, eval_cache=None)
            return snap, m, s
        snap = self.engine.snapshot
        infos = self._refresh_warm_locked()
        # deferred: evaluate_pod invokes this only after vocab flushes, so
        # a label-matrix rebuild can never race a stale device upload
        provider = (lambda: self.engine._nodes_on_device(
            port_words=self._port_words_for(pod)))
        m, s = evaluate_pod(
            pod, infos, snap, self.engine.priorities,
            workloads=self.engine.workloads_provider(),
            hard_weight=self.engine.hard_pod_affinity_weight,
            volume_ctx=self.engine.volume_ctx,
            eval_cache=self.eval_cache, device_nodes_provider=provider)
        return snap, m, s

    FAIL_REASON = "node(s) didn't satisfy TPU predicate kernel"

    @staticmethod
    def _lock_held(wait, pod_key: str) -> None:
        """End an `extender.lock_wait` span once the verb holds `_lock`
        (`with timed_span("extender.lock_wait") as wait, self._lock:`),
        naming the verb's pod while a profiler capture records it."""
        if wait.recording:
            wait.annotate(pod=pod_key)
        wait.stop()

    # ---- coalescer seams (ISSUE 9): the leader evaluates whole batches
    # under ONE lock acquisition; verdict objects capture names/index/gen
    # from the same critical section so responses build outside it -------

    def _eval_many(self, pods):
        """Leader-side batch evaluation: one fused [C, N] dispatch for the
        batch's unique classes (engine.evaluate_pods_batch). Returns one
        _Verdict per pod, in order."""
        from kubernetes_tpu.engine.scheduler_engine import evaluate_pods_batch
        with timed_span("extender.lock_wait") as wait, self._lock:
            self._lock_held(wait, pods[0].key())
            infos = self._refresh_warm_locked()
            snap = self.engine.snapshot
            port_words = max(self._port_words_for(p) for p in pods)
            provider = (lambda: self.engine._nodes_on_device(
                port_words=port_words))
            outs = evaluate_pods_batch(
                pods, infos, snap, self.engine.priorities,
                workloads=self.engine.workloads_provider(),
                hard_weight=self.engine.hard_pod_affinity_weight,
                volume_ctx=self.engine.volume_ctx,
                eval_cache=self.eval_cache, device_nodes_provider=provider)
            names = snap.node_names
            idx = snap.node_index
            gen = self._snap_gen
        return [_Verdict(m, s, names, idx, gen) for (m, s) in outs]

    def _eval_one(self, pod):
        """Degraded per-request fallback (coalescer fault path)."""
        with timed_span("extender.lock_wait") as wait, self._lock:
            self._lock_held(wait, pod.key())
            snap, m, s = self._eval_locked(pod, None)
            return _Verdict(m, s, snap.node_names, snap.node_index,
                            self._snap_gen)

    def _split_passed(self, m, names, idx, node_names):
        """Shared /filter response split (verdict mask -> passed/failed)."""
        if node_names is None:
            # whole-cluster candidate set: vectorized split instead of
            # a per-name dict-lookup loop over N nodes
            import numpy as np
            mask = m[:len(names)]
            if mask.all():
                return list(names), {}
            passed = [names[i] for i in np.nonzero(mask)[0]]
            failed = {names[i]: self.FAIL_REASON
                      for i in np.nonzero(~mask)[0]}
            return passed, failed
        passed, failed = [], {}
        for nm in node_names:
            i = idx.get(nm, -1)
            if i >= 0 and m[i]:
                passed.append(nm)
            else:
                failed[nm] = self.FAIL_REASON
        return passed, failed

    def filter_verdict(self, pod, node_names=None, deadline_s=None):
        """/filter through the coalescing window: (passed, failed, gen)."""
        v = self.coalescer.submit(pod, deadline_s)
        passed, failed = self._split_passed(v.m, v.names, v.idx, node_names)
        return passed, failed, v.gen

    @staticmethod
    def _top_scores(v: "_Verdict", top_k: int):
        """Vectorized top-k (host, score) over a verdict's FITTING nodes —
        argpartition, not a 5k-tuple Python sort (at fleet request rates
        the marshalling would cost more than the evaluation)."""
        import numpy as np
        n = len(v.names)
        if not (top_k and n):
            return []
        # widen BEFORE masking: the verdict's scores are int32 on the
        # production config, and np.where(int32, int64-min) wraps the
        # sentinel to 0 — a non-fitting node would ride TopScores with
        # score 0 whenever fewer than k nodes fit, steering the frontend
        # into a guaranteed fence conflict
        s = np.asarray(v.s[:n]).astype(np.int64, copy=True)
        s[~np.asarray(v.m[:n])] = np.iinfo(np.int64).min
        k = min(int(top_k), n)
        part = np.argpartition(s, n - k)[n - k:]
        order = part[np.argsort(-s[part], kind="stable")]
        sl = s[order].tolist()
        return [(v.names[i], sl[j])
                for j, i in enumerate(order.tolist())
                if sl[j] != np.iinfo(np.int64).min]

    def fused_verdict(self, pod, node_names=None, deadline_s=None,
                      top_k: int = 0):
        """ONE coalescer submit answering both verbs (the wire mirror of
        the PR 1 fused-verb memo): (passed, failed, top_scores, gen).
        A fleet scheduleOne becomes two round trips (filter+, bind)
        instead of three, and one window ticket instead of two.
        top_scores honors the caller's candidate restriction: a fused
        verdict must never steer a frontend to a node its own scheduler
        already excluded."""
        v = self.coalescer.submit(pod, deadline_s)
        passed, failed = self._split_passed(v.m, v.names, v.idx, node_names)
        if node_names is None:
            top = self._top_scores(v, top_k)
        else:
            # restricted candidate set: rank only the PASSED subset
            sl = [(nm, int(v.s[v.idx[nm]])) for nm in passed]
            sl.sort(key=lambda e: -e[1])
            top = sl[:max(int(top_k), 0)]
        return passed, failed, top, v.gen

    def prioritize_verdict(self, pod, node_names=None, deadline_s=None,
                           top_k: int = 0):
        """/prioritize through the coalescing window: (scores, gen).
        ``top_k`` > 0 returns only the k top-scored hosts, selected
        VECTORIZED (argpartition over the score row) — at fleet request
        rates, materializing 5k (host, score) Python tuples per request
        just to pick a winner costs more than the evaluation did."""
        v = self.coalescer.submit(pod, deadline_s)
        if top_k and node_names is None:
            # whole-cluster TopK masks to FITTING nodes (the verbs are
            # fused on one verdict; a top score on a failed node would
            # send the frontend into a guaranteed fence conflict)
            return self._top_scores(v, top_k), v.gen
        sl = v.s.tolist()  # one bulk convert beats N np-scalar __int__s
        if node_names is None:
            return list(zip(v.names, sl[:len(v.names)])), v.gen
        idx = v.idx
        return [(nm, sl[idx[nm]]) for nm in node_names if nm in idx], v.gen

    def filter(self, pod, nodes, node_names):
        if nodes is not None:
            # non-cache-capable args-mode: full state ships per request —
            # nothing to coalesce against, evaluate directly
            with timed_span("extender.lock_wait") as wait, self._lock:
                self._lock_held(wait, pod.key())
                snap, m, _ = self._eval_locked(pod, nodes)
                names = snap.node_names
                idx = snap.node_index
            cand = node_names if node_names is not None \
                else [n.name for n in nodes]
            return self._split_passed(m, names, idx, cand)
        passed, failed, _gen = self.filter_verdict(pod, node_names)
        return passed, failed

    def prioritize(self, pod, nodes, node_names):
        if nodes is not None:
            with timed_span("extender.lock_wait") as wait, self._lock:
                self._lock_held(wait, pod.key())
                snap, _, s = self._eval_locked(pod, nodes)
                names = snap.node_names
                idx = snap.node_index
            sl = s.tolist()
            cand = node_names if node_names is not None \
                else [n.name for n in nodes]
            return [(nm, sl[idx[nm]]) for nm in cand if nm in idx]
        scores, _gen = self.prioritize_verdict(pod, node_names)
        return scores

    def _bind_fence_locked(self, pod: Pod, node: str):
        """Single-commit mirror of the engine's harvest fence (ISSUE 9):
        re-validate capacity / pod count / host ports / liveness — and,
        when affinity is in play, the full topology verdict via a FRESH
        evaluation — for one (pod, node) commit against CURRENT cache
        truth. This is the Omega transaction re-validator at the wire:
        verdicts may be stale (stale_window_s), commits never are. Called
        with the lock held, BEFORE the assume. Returns the typed conflict
        as ``(reason_code, message)`` — reason_code indexes
        podtrace.REASON_NAMES, the SAME vocabulary the wave engine's
        fence_reason_* requeues use (ISSUE 16: the per-reason
        bind_conflict counters partition the total with names the
        existing requeue attribution already established) — or None to
        admit."""
        lockcheck.assert_held(self._lock, "_bind_fence_locked")
        from kubernetes_tpu.observability import podtrace
        from kubernetes_tpu.ops import oracle
        from kubernetes_tpu.ops.affinity import _has_affinity
        infos = self._infos if self._infos is not None \
            else self.cache.node_infos()
        info = infos.get(node)
        if info is None:
            return podtrace.REASON_LIVENESS, f"node {node} unknown"
        if info.node is None:
            return podtrace.REASON_LIVENESS, f"node {node} gone"
        if info.node.unschedulable:
            return podtrace.REASON_LIVENESS, f"node {node} cordoned"
        if not oracle.check_node_condition(info.node):
            return podtrace.REASON_LIVENESS, f"node {node} not ready"
        # NodeInfo.requested includes every assume committed so far —
        # exactly the occupancy the harvest fence's prefix math re-checks
        ok, fails = oracle.pod_fits_resources(pod, info)
        if not ok:
            return (podtrace.REASON_CAPACITY,
                    f"insufficient capacity on {node}: {','.join(fails)}")
        if not oracle.pod_fits_host_ports(pod, info):
            return (podtrace.REASON_CAPACITY,
                    f"host port conflict on {node}")
        if _has_affinity(pod) or not self.eval_cache.cluster_aff_free:
            # topology mirror: an affinity verdict can be invalidated by
            # ANY foreign commit — force the deferred hint refresh past
            # the staleness window and re-check the chosen node against
            # the fresh evaluation
            self._last_refresh = 0.0
            snap, m, _s = self._eval_locked(pod, None)
            i = snap.node_index.get(node, -1)
            if i < 0 or not m[i]:
                return (podtrace.REASON_AFFINITY,
                        f"topology re-validation failed on {node}")
        return None

    def _fence_conflict(self, code: int, reason: str,
                        idem_key: Optional[str]):
        """One typed fence refusal (lock held): fold the total, attribute
        the per-reason counter — the partition invariant
        sum(bind_conflict_reason_*) == bind_conflicts is test-pinned on
        every transport — stamp a ring instant for the perfetto fence
        lane (wave=-1 marks a WIRE conflict; b carries the reason code),
        and answer the retryable CONFLICT."""
        import time as _time

        from kubernetes_tpu.observability import podtrace
        from kubernetes_tpu.observability.recorder import RECORDER
        from kubernetes_tpu.observability import recorder as flightrec
        self._count("bind_conflicts")
        self._count("bind_conflict_reason_" + podtrace.REASON_NAMES[code])
        if RECORDER.enabled:
            RECORDER.record(flightrec.FENCE_REQUEUE, wave=-1,
                            t0=_time.monotonic(), a=1, b=code)
        err = f"CONFLICT: {reason}"
        if idem_key:
            self.ledger.finish(idem_key, "conflict", err)
        return err, "conflict", self._retry_jitter()

    def list_state(self):
        """``(nodes, bound_pods)`` — cell truth for a relisting scheduler
        process (ISSUE 16): every live node plus every pod the cache
        currently charges to a node (assumed AND confirmed — exactly the
        occupancy the bind fence validates commits against). This is the
        RELIST half of a per-process watch/relist snapshot refresh: a
        worker process syncs this into ITS OWN backend and schedules
        against bounded-stale local truth while commits race through the
        shared fence."""
        with self._lock:
            infos = self._infos if self._infos is not None \
                else self.cache.node_infos()
            nodes = [i.node for i in infos.values() if i.node is not None]
            pods = [p for i in infos.values() for p in list(i.pods)]
            return nodes, pods

    def bind(self, pod_name, pod_namespace, pod_uid, node):
        """Legacy single-scheduler wire shape: error string, "" = bound."""
        err, _kind, _retry = self.bind_verdict(pod_name, pod_namespace,
                                               pod_uid, node)
        return err

    def bind_verdict(self, pod_name, pod_namespace, pod_uid, node,
                     snapshot_gen: Optional[int] = None,
                     idem_key: Optional[str] = None,
                     deadline_s: Optional[float] = None,
                     pod_spec: Optional[Pod] = None):
        """The multi-frontend /bind commit (ISSUE 9). Returns
        (error, kind, retry_after_s) with kind in:

          ok       — committed (or a replayed success);
          conflict — the fence refused; RETRYABLE: re-run scheduleOne
                     against a fresh verdict after the jittered backoff;
          pending  — a twin with the same idempotency key is in flight;
                     retryable exactly like a conflict;
          shed     — the request outlived its own deadline; nothing
                     happened (a same-key retry starts fresh);
          error    — the downstream apiserver write failed; AMBIGUOUS
                     (may have landed) — retry with the SAME key and the
                     ledger replays it to exactly-once.

        NOTE on affinity: the /bind wire carries identifiers only
        (ExtenderBindingArgs), so without a shipped "Pod" spec a freshly
        bound pod's affinity stays unknown until the bulk cache sync —
        cluster_aff_free changes only at sync boundaries, so no evaluation
        path can see the unknown affinity (fast lane == oracle)."""
        import dataclasses
        import time as _time
        t0 = _time.monotonic()
        key = f"{pod_namespace}/{pod_name}"
        replaying = False
        replay_err = ""
        if idem_key:
            verdict, lnode, lerr = self.ledger.begin(idem_key, node)
            if verdict == "done":
                # completed attempt: answer from the record — no second
                # assume, no second apiserver write (exactly-once)
                self._count("bind_replays")
                kind = "conflict" if lerr.startswith("CONFLICT") else \
                    ("ok" if not lerr else "error")
                return lerr, kind, self._retry_jitter()
            if verdict == "pending":
                self._count("bind_replays")
                return ("CONFLICT: bind attempt in flight", "pending",
                        self._retry_jitter())
            if verdict == "replay":
                # ambiguous prior attempt: converge on ITS node choice
                # (BindLedger docstring), never a fresh one
                self._count("bind_replays")
                node = lnode
                replaying = True
                replay_err = lerr
        try:
            return self._bind_attempt(key, pod_name, pod_namespace,
                                      pod_uid, node, snapshot_gen,
                                      idem_key, deadline_s, pod_spec, t0,
                                      replaying, replay_err)
        except BaseException:
            # an unexpected escape (device error in the fence's re-eval,
            # cache invariant trip) must not pin a PENDING ledger entry —
            # that would answer every same-key retry "in flight" forever
            if idem_key:
                if replaying:
                    self.ledger.finish(idem_key, "uncertain", replay_err)
                else:
                    self.ledger.abandon(idem_key)
            raise

    def _bind_attempt(self, key, pod_name, pod_namespace, pod_uid, node,
                      snapshot_gen, idem_key, deadline_s, pod_spec, t0,
                      replaying, replay_err):
        """The fence + assume + downstream-write body of bind_verdict,
        after the ledger prologue resolved what to attempt."""
        import dataclasses
        import time as _time
        assumed_now = False
        with timed_span("extender.lock_wait") as wait, self._lock:
            self._lock_held(wait, key)
            if deadline_s is not None \
                    and _time.monotonic() - t0 > deadline_s:
                self._count("deadline_shed")
                if idem_key:
                    if replaying:  # restore the ambiguity record
                        self.ledger.finish(idem_key, "uncertain", replay_err)
                    else:
                        self.ledger.abandon(idem_key)
                return "DEADLINE_EXCEEDED", "shed", 0.0
            base = self._known_pods.get(key)
            if base is None and pod_spec is not None:
                base = pod_spec  # wire-shipped spec: exact fence math +
                # resource-true assume instead of the zero-resource bare
            if base is None:
                base = Pod(name=pod_name, namespace=pod_namespace,
                           uid=pod_uid)
            # DOUBLE-CLAIM (ISSUE 16): a pod already charged to a
            # DIFFERENT node was committed by another scheduler racing
            # this cell — refuse typed BEFORE the capacity fence (and
            # regardless of the generation skip below: a current-gen
            # verdict attests the snapshot, not pod ownership). Same-node
            # re-binds fall through untouched: that is the client-retry-
            # of-a-landed-bind shape the assume's KeyError tolerance and
            # the store's idempotent refusal already heal.
            from kubernetes_tpu.observability import podtrace
            claimed = self.cache.claimed_node(key)
            if claimed is not None and claimed != node:
                return self._fence_conflict(
                    podtrace.REASON_DOUBLE_CLAIM,
                    f"double-claim: pod {key} already claimed on "
                    f"{claimed}", idem_key)
            # FENCE (optimistic concurrency): skip only when the verdict's
            # generation is provably current — nothing was committed since
            # the snapshot it read, so its own /filter pass IS the fence
            if snapshot_gen is None or snapshot_gen != self.commit_gen:
                self._refresh_warm_locked()  # liveness truth for _infos
                fenced = self._bind_fence_locked(base, node)
                if fenced is not None:
                    return self._fence_conflict(fenced[0], fenced[1],
                                                idem_key)
            else:
                self._count("bind_fence_skipped")
            pod = dataclasses.replace(base, node_name=node)
            try:
                self.cache.assume_pod(pod)
                self.cache.finish_binding(pod)
                assumed_now = True
                if key not in self._known_pods:
                    self._assumed_bare[key] = pod
                # the warm lane's staleness ledger: exactly one node's
                # dynamic row moved
                self._bind_hint.add(node)
                self.commit_gen += 1
            except KeyError:
                pass  # already known (e.g. a client retry of a bind that
                # succeeded) — do NOT treat the existing assumption as ours
        # the apiserver write runs OUTSIDE the lock: a slow apiserver must
        # not stall every concurrent /filter//prioritize for the duration
        # of an external HTTP call. Concurrent evaluations meanwhile see
        # the optimistic assume — exactly the reference's semantics
        # (scheduler.go:224-250: assume first, bind async, forget on
        # failure), compensated below.
        if self.binder is not None:
            try:
                self.binder(pod_name, pod_namespace, pod_uid, node)
            except Exception as e:
                if assumed_now:
                    # undo ONLY what this call assumed: a duplicate /bind
                    # whose write fails must not forget a legitimately
                    # bound pod (that would leak its capacity until the
                    # next sync)
                    with timed_span("extender.lock_wait") as wait, \
                            self._lock:
                        self._lock_held(wait, key)
                        self.cache.forget_pod(pod)
                        self._assumed_bare.pop(key, None)
                        self._bind_hint.add(node)
                        self.commit_gen += 1
                self._count("bind_errors")
                if idem_key:
                    # AMBIGUOUS: the write may have landed (bind-API
                    # timeout shape) — record it so a same-key retry
                    # replays to the same node instead of double-booking
                    self.ledger.finish(idem_key, "uncertain", str(e))
                return str(e), "error", 0.0
        if idem_key:
            self.ledger.finish(idem_key, "ok", "")
        return "", "ok", 0.0

    def _retry_jitter(self) -> float:
        """Server-suggested conflict backoff: jittered so a fleet that
        conflicted together doesn't retry in lockstep."""
        with self._counters_lock:
            return 0.002 + self._rng.random() * 0.01

    def metrics_text(self) -> str:
        # the single Prometheus render of the unified registry (ISSUE 13):
        # same families as the pre-r15 hand-rolled fold (scheduler
        # histograms, tpu_extender_*_total counters, gen gauges) plus the
        # span and flight-recorder families. Lock discipline unchanged:
        # each source snapshots under ITS lock, in sequence, never nested.
        return self.telemetry.render_prometheus()
