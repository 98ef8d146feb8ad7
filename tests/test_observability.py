"""Flight recorder + unified telemetry (ISSUE 13).

Pins the contracts the observability tentpole rests on:

- the recorder ring is bounded (overwrites oldest, counts drops), typed,
  ordered, and an EXACT no-op when disabled — a drain with the recorder
  off emits zero events;
- a pipelined drain records dispatch/harvest/bind-flush per wave with
  matching wave ids, and the Perfetto exporter renders them on the host
  lane with the pipeline's interleaving VISIBLE, beside the fence lane;
- the unified registry folds spans + SchedulerMetrics + service counters
  + gauges into one labeled namespace with a single Prometheus render
  (legacy metric names intact);
- TRANSPORT PARITY: HTTP /debug/vars, the binary STATS verb and the
  embedded debug_snapshot serve IDENTICAL registry contents, and
  mid-storm scrapes never tear (the r12 dedicated-lock audit pattern);
- Histogram growth is bounded by the weighted reservoir while
  percentile() stays exact below the bound and rank-accurate on a known
  distribution above it;
- a budget-breaching streaming step dumps its Trace step breakdown
  (log_if_long at the budget threshold), fake-clock pinned.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from kubernetes_tpu.api.types import make_pod
from kubernetes_tpu.engine.scheduler import Scheduler
from kubernetes_tpu.models.hollow import PROFILES, hollow_nodes, load_cluster
from kubernetes_tpu.observability import perfetto
from kubernetes_tpu.observability import recorder as rec
from kubernetes_tpu.observability.recorder import RECORDER, FlightRecorder
from kubernetes_tpu.observability.registry import TelemetryRegistry
from kubernetes_tpu.server.apiserver_lite import ApiServerLite
from kubernetes_tpu.utils.metrics import Histogram


@pytest.fixture
def flight():
    """The process-wide ring, armed for one test and ALWAYS disarmed
    after — global state must never leak across tests."""
    RECORDER.clear()
    RECORDER.enable()
    try:
        yield RECORDER
    finally:
        RECORDER.disable()
        RECORDER.clear()


def mk_sched(n_nodes=64, n_pods=0):
    api = ApiServerLite()
    load_cluster(api, hollow_nodes(n_nodes),
                 PROFILES["density"](n_pods) if n_pods else [])
    s = Scheduler(api, record_events=False)
    s.start()
    return api, s


# ---------------------------------------------------------------- the ring


def test_ring_bounds_order_and_drops():
    r = FlightRecorder(capacity=8)
    r.enable()
    for i in range(20):
        r.record(rec.DISPATCH, wave=i, t0=float(i), a=i)
    ev = r.snapshot()
    assert len(ev) == 8
    assert [e["wave"] for e in ev] == list(range(12, 20))  # oldest->newest
    assert r.stats()["events"] == 20
    assert r.stats()["dropped"] == 12
    tail = r.snapshot(last=3)
    assert [e["wave"] for e in tail] == [17, 18, 19]
    r.clear()
    assert r.snapshot() == [] and r.stats()["events"] == 0


def test_disabled_recorder_is_exact_noop():
    """Emit sites guard on .enabled — a full pipelined drain with the
    recorder off must leave the ring untouched."""
    assert not RECORDER.enabled
    before = RECORDER.stats()["events"]
    api, s = mk_sched(n_pods=300)
    s.run_until_drained(max_batch=128)
    assert RECORDER.stats()["events"] == before


def test_drain_records_typed_waves_with_matching_ids(flight):
    api, s = mk_sched(n_pods=500)
    totals = s.run_until_drained(max_batch=128)
    assert totals["bound"] == 500
    ev = flight.snapshot()
    by_kind = {}
    for e in ev:
        by_kind.setdefault(e["kind"], []).append(e)
    # one dispatch + one harvest + one bind-flush per wave, ids joined
    disp = {e["wave"] for e in by_kind["dispatch"]}
    harv = {e["wave"] for e in by_kind["harvest"]}
    flush = {e["wave"] for e in by_kind["bind_flush"]}
    assert disp and disp == harv == flush
    assert sum(e["a"] for e in by_kind["dispatch"]) == 500   # pods admitted
    assert sum(e["a"] for e in by_kind["bind_flush"]) == 500  # pods bound
    for e in ev:
        assert e["t"] > 0 and e["dur"] >= 0


# ------------------------------------------------------------ the exporter


def test_perfetto_export_lanes_and_overlap(flight, tmp_path):
    """The exported timeline carries the host/fence/preempt/fastlane
    lanes and no device lane (the device's own timeline is the profiler
    trace). Each wave's dispatch, harvest and bind-flush sit on the host
    lane in stage order, and the pipelined overlap is VISIBLE as their
    interleaving: some wave dispatches before the previous wave's
    harvest."""
    api, s = mk_sched(n_pods=800)
    s.run_until_drained(max_batch=128)
    ev = flight.snapshot()
    out = tmp_path / "trace.json"
    trace = perfetto.export_chrome_trace(ev, str(out))
    # the file is loadable chrome://tracing JSON (object form)
    loaded = json.loads(out.read_text())
    assert loaded["traceEvents"]
    lanes = {m["args"]["name"] for m in loaded["traceEvents"]
             if m.get("ph") == "M" and m["name"] == "thread_name"}
    assert lanes == {"host", "fence", "preempt", "fastlane"}
    spans = [m for m in trace["traceEvents"] if m.get("ph") == "X"]
    assert spans and all(m["tid"] == perfetto.TID_HOST for m in spans)
    start = {}
    for m in spans:
        stage, _, wave = m["name"].rpartition(" ")
        start.setdefault(wave, {})[stage] = m["ts"]
    waves = sorted(start, key=lambda w: int(w[1:]))
    assert len(waves) >= 3
    for w in waves:
        st = start[w]
        assert st["dispatch"] < st["harvest"] < st["bind-flush"], (w, st)
    # overlap: wave k+1 was dispatched before wave k was harvested
    assert any(start[b]["dispatch"] < start[a]["harvest"]
               for a, b in zip(waves, waves[1:])), start


def test_perfetto_fence_lane_markers(flight, tmp_path):
    """Fence-requeue / degraded / churn events render as instants on the
    fence lane."""
    flight.record(rec.FENCE_REQUEUE, wave=3, a=2, b=1)
    flight.record(rec.DEGRADED, a=1, b=3)
    flight.record(rec.CHURN_OP, a=rec.CHURN_OP_CODES["kill"], b=1)
    trace = perfetto.build_chrome_trace(flight.snapshot())
    instants = [m for m in trace["traceEvents"] if m.get("ph") == "i"]
    names = {m["name"] for m in instants}
    assert {"fence-requeue w3", "degraded-enter", "churn:kill"} <= names
    assert all(m["tid"] == perfetto.TID_FENCE for m in instants)


# ------------------------------------------------------------ the registry


def test_registry_folds_all_sources_one_namespace():
    from kubernetes_tpu.utils.metrics import SchedulerMetrics
    from kubernetes_tpu.utils.trace import COUNTERS

    reg = TelemetryRegistry()
    m = SchedulerMetrics()
    m.e2e_latency.observe(0.01)
    m.scheduled.inc(7)
    counters = {"binds": 3}
    reg.register_metrics("sched", m)
    reg.register_counters("svc", lambda: dict(counters),
                          prom_prefix="tpu_svc")
    reg.register_gauges("g", lambda: {"tpu_quantum": 512})
    COUNTERS.inc("obs.test_span")
    snap = reg.snapshot()
    assert snap["counter.svc.binds"] == 3
    assert snap["gauge.tpu_quantum"] == 512
    assert snap["counter.sched.scheduler_pods_scheduled_total"] == 7
    assert snap[
        "hist.sched.scheduler_e2e_scheduling_latency_seconds.count"] == 1
    assert snap["span.obs.test_span.count"] >= 1
    assert "recorder.events" in snap and "recorder.enabled" in snap
    text = reg.render_prometheus()
    assert "tpu_svc_binds_total 3" in text
    assert "# TYPE tpu_quantum gauge\ntpu_quantum 512" in text
    assert 'tpu_span_count_total{span="obs.test_span"}' in text
    assert "scheduler_pods_scheduled_total 7" in text
    assert "tpu_flight_recorder_events" in text
    # re-registering under the same key replaces, never accumulates
    reg.register_gauges("g", lambda: {"tpu_quantum": 1024})
    assert reg.snapshot()["gauge.tpu_quantum"] == 1024


def test_stream_gauges_registered_on_scheduler_registry():
    api, s = mk_sched(n_nodes=16)
    loop = s.stream(budget_s=0.25, min_quantum=256)
    snap = s.telemetry.snapshot()
    assert snap["gauge.stream_quantum"] == loop.quantum
    assert snap["gauge.stream_degraded"] == 0
    assert snap["gauge.stream_budget_ms"] == 250.0
    assert "gauge.stream_backlog" in snap
    # close() drops the dead loop's gauges (stale-introspection guard) —
    # unless a replacement loop already took the key over
    loop.close()
    assert "gauge.stream_quantum" not in s.telemetry.snapshot()
    loop2 = s.stream(budget_s=0.25)
    loop3 = s.stream(budget_s=0.5)
    loop2.close()  # superseded registration stays loop3's
    assert s.telemetry.snapshot()["gauge.stream_budget_ms"] == 500.0
    loop3.close()


# ------------------------------------------------------- transport parity


def _parity_rig(n_nodes=48):
    from kubernetes_tpu.server.asyncwire import AsyncBinaryServer
    from kubernetes_tpu.server.embedded import VerdictService
    from kubernetes_tpu.server.extender import (
        ExtenderHTTPServer,
        TPUExtenderBackend,
    )

    b = TPUExtenderBackend(coalesce_window_s=0.0005)
    b.sync_nodes(hollow_nodes(n_nodes))
    b.filter(make_pod("warm", cpu=100, memory=256 << 20), None, None)
    svc = VerdictService(b)
    http_srv = ExtenderHTTPServer(b)
    http_srv.start()
    bin_srv = AsyncBinaryServer(svc)
    bin_srv.start()
    return b, svc, http_srv, bin_srv


def _http_get(port, path):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def test_transport_parity_identical_snapshots_mid_storm(flight):
    """The same registry snapshot through all three transports: identical
    counter names AND values once quiesced, torn-read-free while a
    filter/bind storm is concurrently mutating every source (the r12
    dedicated-lock audit, extended to the introspection path)."""
    from kubernetes_tpu.client.binarywire import BinaryWireClient

    b, svc, http_srv, bin_srv = _parity_rig()
    errors: list = []
    stop = threading.Event()

    def storm(i):
        try:
            for j in range(25):
                b.filter_verdict(make_pod(f"storm-{i}-{j}", cpu=100,
                                          memory=256 << 20))
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    def scraper():
        # mid-storm reads must never raise or tear: every fetch parses,
        # and the key SET is identical across transports at every pull
        c = BinaryWireClient("127.0.0.1", bin_srv.port).connect()
        try:
            while not stop.is_set():
                hv = _http_get(http_srv.port, "/debug/vars")
                bv = c.stats()["vars"]
                ev = svc.debug_snapshot()["vars"]
                for snap in (hv, bv, ev):
                    assert "gauge.tpu_extender_commit_gen" in snap
                    assert "recorder.events" in snap
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        finally:
            c.close()

    threads = [threading.Thread(target=storm, args=(i,)) for i in range(6)]
    sc = threading.Thread(target=scraper)
    sc.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    sc.join(timeout=60)
    assert not errors, errors
    # quiesced: the three transports serve IDENTICAL contents
    c = BinaryWireClient("127.0.0.1", bin_srv.port).connect()
    try:
        http_vars = _http_get(http_srv.port, "/debug/vars")
        bin_snap = c.stats(last=10)
        emb_snap = svc.debug_snapshot(last=10)
        assert http_vars == bin_snap["vars"] == emb_snap["vars"]
        assert bin_snap["trace"] == emb_snap["trace"]
        # the storm really moved the sources this snapshot folds
        assert http_vars["counter.extender.coalesce_requests"] >= 150
        http_trace = _http_get(http_srv.port, "/debug/trace?last=10")
        assert http_trace == bin_snap["trace"]
    finally:
        c.close()
        bin_srv.stop()
        http_srv.stop()


def test_debug_trace_last_bounds_the_tail(flight):
    b, svc, http_srv, bin_srv = _parity_rig(n_nodes=8)
    try:
        for i in range(12):
            flight.record(rec.DISPATCH, wave=i, a=1)
        tail = _http_get(http_srv.port, "/debug/trace?last=4")
        assert [e["wave"] for e in tail] == [8, 9, 10, 11]
        # absent param -> bounded default tail (256 covers these 12)
        full = _http_get(http_srv.port, "/debug/trace")
        assert len(full) == 12
        # literal last=0 means NO trace on EVERY transport (parity)
        assert _http_get(http_srv.port, "/debug/trace?last=0") == []
        assert svc.debug_snapshot(last=0)["trace"] == []
    finally:
        bin_srv.stop()
        http_srv.stop()


# ------------------------------------------------- bounded histogram store


def test_histogram_store_is_bounded_under_always_on_load():
    """The r15 leak fix: unbounded _values/_chunks growth under the
    always-on loop is capped by the weighted reservoir."""
    h = Histogram("x", reservoir_max=4096)
    rng = np.random.default_rng(7)
    for _ in range(100):
        h.observe_batch(list(rng.exponential(0.05, 5000)))
    assert h.count == 500_000
    assert h.stored_points <= 4096
    # weighted observe_many entries count toward the bound too
    h2 = Histogram("y", reservoir_max=512)
    for i in range(5000):
        h2.observe_many(float(i % 97) / 97.0, 3)
    assert h2.stored_points <= 512
    assert h2.count == 15000


def test_histogram_percentile_accuracy_on_known_distribution():
    """Rank accuracy through compaction, pinned on a known distribution:
    the compacted percentile must land within a small rank tolerance of
    the exact value."""
    h = Histogram("x", reservoir_max=8192)
    rng = np.random.default_rng(11)
    all_vals = []
    for _ in range(60):
        vals = list(rng.exponential(0.05, 4000))
        all_vals.extend(vals)
        h.observe_batch(vals)
    arr = np.sort(np.asarray(all_vals))
    for p in (50, 90, 99):
        exact = float(arr[min(int(p / 100 * len(arr)), len(arr) - 1)])
        got = h.percentile(p)
        # tolerance: +-0.5% of rank around the exact quantile
        lo = float(arr[max(int((p - 0.5) / 100 * len(arr)), 0)])
        hi = float(arr[min(int((p + 0.5) / 100 * len(arr)),
                           len(arr) - 1)])
        assert lo <= got <= hi, (p, got, exact, lo, hi)


def test_histogram_percentile_exact_below_the_bound():
    """Below the reservoir bound nothing compacts: rank semantics are
    identical to the pre-r15 exact walk, across BOTH stores."""
    h = Histogram("x")
    h.observe_batch([0.5, 0.1, 0.9, 0.3])  # chunk store
    h.observe_many(0.2, 3)                 # weighted store
    # expanded multiset: [.1 .2 .2 .2 .3 .5 .9], ranks 0..6
    assert h.percentile(0) == 0.1
    assert h.percentile(50) == pytest.approx(0.2)
    assert h.percentile(100) == 0.9
    assert h.stored_points == 5
    empty = Histogram("e")
    assert empty.percentile(99) == 0.0
    # totals() reads (count, sum) under the lock for the registry
    assert h.totals() == (7, pytest.approx(0.5 + 0.1 + 0.9 + 0.3 + 0.6))


# ------------------------------------------- budget-breach streaming trace


def test_stream_budget_breach_dumps_trace_fake_clock():
    """A pod-ful streaming step whose fake-clock span crosses the budget
    dumps the step breakdown; under-budget steps stay silent."""
    api, s = mk_sched(n_nodes=16)
    loop = s.stream(budget_s=0.25, min_quantum=256)
    dumps: list = []

    class Tick:
        def __init__(self, dt):
            self.t = 1000.0
            self.dt = dt

        def __call__(self):
            self.t += self.dt
            return self.t

    try:
        loop.trace_sink = dumps.append
        # under budget: 1ms between trace stamps -> no dump
        loop.trace_now = Tick(0.001)
        for p in PROFILES["density"](32):
            p.name = "quiet-" + p.name
            api.create("Pod", p)
        loop.step()
        loop.step()  # harvest the in-flight wave
        assert dumps == []
        # breach: every trace stamp advances 100ms -> the pod-ful step's
        # total crosses the 250ms budget and the breakdown dumps
        loop.trace_now = Tick(0.1)
        for p in PROFILES["density"](32):
            p.name = "slow-" + p.name
            api.create("Pod", p)
        loop.step()
        assert len(dumps) == 1
        text = dumps[0]
        assert "micro-wave step" in text
        assert "informer sync done" in text
        assert "micro-wave popped" in text
        assert "quantum=" in text
        # idle ticks never dump, whatever the clock says
        n = len(dumps)
        loop.step()  # harvests, pod-ful in effect (prev wave) — may dump
        loop.step()  # now truly idle
        idle_dumps = len(dumps)
        loop.step()
        assert len(dumps) == idle_dumps
    finally:
        loop.close()


def test_stream_trace_off_in_fixed_mode():
    """The drain (fixed-chunk mode) never constructs the per-step trace —
    budget tracing is a streaming-mode contract."""
    api, s = mk_sched(n_nodes=16, n_pods=64)
    dumps: list = []
    pipe = s.pipeline(chunk=32)
    pipe.trace_sink = dumps.append
    pipe.trace_now = lambda: 0.0  # would crash Trace math if ever used
    while True:
        st = pipe.step()
        if st["popped"] == 0 and pipe.idle:
            break
    pipe.close()
    assert dumps == []


# ----------------------------------------------------------- churn marker


def test_churn_ops_land_on_the_ring(flight):
    from kubernetes_tpu.testing.churn import (
        ChurnConfig,
        ChurnInjector,
        make_churn_schedule,
    )

    api = ApiServerLite()
    load_cluster(api, hollow_nodes(12), [])
    cfg = ChurnConfig(seed=3, node_churn_per_min=3.0, evict_per_min_abs=0)
    inj = ChurnInjector(api, make_churn_schedule(
        [n.name for n in api.list("Node")[0]], cfg, duration_s=2.0))
    inj.apply_until(2.0)
    assert sum(inj.applied.values()) > 0
    ops = [e for e in flight.snapshot() if e["kind"] == "churn_op"]
    assert len(ops) == sum(inj.applied.values())
    names = {rec.CHURN_OP_NAMES[e["a"]] for e in ops}
    assert names <= set(rec.CHURN_OP_CODES)


def test_cli_records_a_drain_to_a_chrome_trace(tmp_path, capsys):
    """`python -m kubernetes_tpu.observability`: --trace records a small
    pipelined drain and writes a loadable trace-event JSON, leaving the
    process-wide ring disarmed; with nothing to do it prints its usage
    and exits 2."""
    from kubernetes_tpu.observability.__main__ import main

    assert main([]) == 2
    assert "nothing to do" in capsys.readouterr().err
    out = tmp_path / "trace.json"
    assert main(["--trace", str(out), "--nodes", "16", "--pods", "64",
                 "--no-warm"]) == 0
    events = json.loads(out.read_text())["traceEvents"]
    assert any(e.get("ph") == "X" for e in events)
    assert not RECORDER.enabled
