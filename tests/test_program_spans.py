"""The program's spans on the profiler timeline.

Pins the span system's contracts:

- `timed_span` lands by name on the host plane of a `jax.profiler`
  capture, nested inside its parent, carrying its metadata, at the
  `time.monotonic` stamps it hands its caller (on the `bench.anchor`
  mapping the benchmark reads traces with);
- with no capture running a span changes COUNTERS and nothing else;
- a stream drain fires the engine's spans (`sched.sync`,
  `pipeline.encode`, `pipeline.harvest`, `bind.flush`), and the flight
  recorder's dispatch/harvest/bind-flush events carry the same stamps;
- an extender scheduleOne fires `extender.http.decode`,
  `extender.http.encode` and `extender.lock_wait`, and the lock wait
  grows while another thread holds the backend's lock;
- `benchmark/program_trace.py` sets the loop thread's spans against the
  device's idle time: exact on fixed intervals, and read off a CPU
  capture of a real stream drain (None for a trace without spans).
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

import pytest

from kubernetes_tpu.api import serde
from kubernetes_tpu.api.types import make_pod
from kubernetes_tpu.engine.scheduler import Scheduler
from kubernetes_tpu.models.hollow import PROFILES, hollow_nodes, load_cluster
from kubernetes_tpu.observability.recorder import RECORDER
from kubernetes_tpu.server.apiserver_lite import ApiServerLite
from kubernetes_tpu.utils.trace import COUNTERS, timed_span

BENCHMARK_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK_DIR not in sys.path:
    sys.path.append(BENCHMARK_DIR)

import program_trace  # noqa: E402
from trace_reduce import DeviceTrace, find_xplane  # noqa: E402

ENGINE_SPANS = ("sched.sync", "pipeline.dispatch", "pipeline.encode",
                "pipeline.harvest", "bind.flush")
EXTENDER_SPANS = ("extender.http.decode", "extender.http.encode",
                  "extender.lock_wait")


class capture:
    """A CPU profiler capture into `path`, with the benchmark's options."""

    def __init__(self, path):
        self.path = str(path)

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False


def host_events(path):
    """{line name: [(name, start_ns, end_ns, stats)]} of the host plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.setdefault(ln.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in ln.events)
    return out


def span_diff(before):
    out = {}
    for k, (c, t) in COUNTERS.snapshot().items():
        c0, t0 = before.get(k, (0, 0.0))
        if (c, t) != (c0, t0):
            out[k] = (c - c0, t - t0)
    return out


def mk_sched(n_nodes=32, n_pods=0):
    api = ApiServerLite()
    load_cluster(api, hollow_nodes(n_nodes),
                 PROFILES["density"](n_pods) if n_pods else [])
    s = Scheduler(api, record_events=False)
    s.start()
    return api, s


# ------------------------------------------------------------- the span API


def test_timed_span_lands_nested_on_profiler_host_plane(tmp_path):
    import jax
    with capture(tmp_path):
        with jax.profiler.TraceAnnotation("bench.anchor"):
            t_anchor = time.monotonic()
        with timed_span("spantest.outer") as outer:
            with timed_span("spantest.inner") as inner:
                assert inner.recording
                inner.annotate(wave=7)
                time.sleep(0.002)
    lines = host_events(tmp_path)
    found = {}
    for line, evs in lines.items():
        for name, s, e, st in evs:
            if name.startswith("spantest."):
                found[name] = (line, s, e, st)
    assert set(found) == {"spantest.outer", "spantest.inner"}
    o_line, o0, o1, _ = found["spantest.outer"]
    i_line, i0, i1, i_stats = found["spantest.inner"]
    assert o_line == i_line
    assert o0 <= i0 and i1 <= o1
    assert i_stats.get("wave") == 7
    tr = DeviceTrace(str(tmp_path), anchor_mono=t_anchor)
    for span, start in ((outer, o0), (inner, i0)):
        assert abs(start - tr.to_ns(span.t0)) < 1e6
    assert abs((o1 - o0) - outer.dur * 1e9) < 1e6


def test_timed_span_without_capture_changes_only_counters(tmp_path):
    before = COUNTERS.snapshot()
    events0 = RECORDER.stats()["events"]
    with timed_span("spantest.off") as sp:
        assert not sp.recording
    assert span_diff(before) == {"spantest.off": (1, sp.dur)}
    assert sp.dur >= 0 and RECORDER.stats()["events"] == events0
    # nothing was left pending for a later capture either
    with capture(tmp_path):
        pass
    assert not any(name == "spantest.off"
                   for evs in host_events(tmp_path).values()
                   for name, *_ in evs)


# ------------------------------------------------------------------ engine


def test_stream_drain_fires_engine_spans():
    api, s = mk_sched()
    loop = s.stream()
    before = COUNTERS.snapshot()
    for p in PROFILES["density"](300):
        api.create("Pod", p)
    loop.drain()
    loop.close()
    assert sum(1 for p in api.list("Pod")[0] if p.node_name) == 300
    diff = span_diff(before)
    for name in ENGINE_SPANS:
        assert diff.get(name, (0, 0.0))[0] > 0, (name, diff)
    # encode is a child of dispatch; device_block a child of harvest
    assert diff["pipeline.encode"][1] <= diff["pipeline.dispatch"][1]
    assert diff["pipeline.device_block"][1] <= diff["pipeline.harvest"][1]


def test_recorder_events_take_the_span_stamps():
    api, s = mk_sched(n_pods=500)
    RECORDER.clear()
    RECORDER.enable()
    before = COUNTERS.snapshot()
    try:
        assert s.run_until_drained(max_batch=128)["bound"] == 500
    finally:
        RECORDER.disable()
    events = RECORDER.snapshot()
    RECORDER.clear()
    diff = span_diff(before)
    for kind, span in (("dispatch", "pipeline.dispatch"),
                       ("harvest", "pipeline.harvest"),
                       ("bind_flush", "bind.flush")):
        evs = [e for e in events if e["kind"] == kind]
        assert len(evs) == diff[span][0], kind
        assert sum(e["dur"] for e in evs) == pytest.approx(diff[span][1],
                                                           rel=1e-9)


# ---------------------------------------------------------------- extender


def _extender_rig(n_nodes=8):
    from kubernetes_tpu.server.extender import (
        ExtenderHTTPServer,
        TPUExtenderBackend,
    )
    from kubernetes_tpu.testing.churn import extender_store_binder
    api = ApiServerLite()
    nodes = hollow_nodes(n_nodes)
    for n in nodes:
        api.create("Node", n)
    backend = TPUExtenderBackend(binder=extender_store_binder(api))
    backend.sync_nodes(nodes)
    srv = ExtenderHTTPServer(backend)
    srv.start()
    return api, backend, srv, [n.name for n in nodes]


def _post(conn, verb, obj):
    conn.request("POST", f"/{verb}", json.dumps(obj),
                 {"Content-Type": "application/json"})
    return json.loads(conn.getresponse().read())


def _schedule_one(api, conn, names, i):
    pod = make_pod(f"spantest-{i}", cpu=100, memory=256 << 20)
    api.create("Pod", pod)
    wire = serde.encode_pod(pod)
    passed = _post(conn, "filter", {"Pod": wire, "NodeNames": names})
    scores = _post(conn, "prioritize", {"Pod": wire,
                                        "NodeNames": passed["NodeNames"]})
    top = max(scores, key=lambda e: e["Score"])["Host"]
    out = _post(conn, "bind", {"PodName": pod.name,
                               "PodNamespace": pod.namespace,
                               "PodUID": pod.uid, "Node": top, "Pod": wire})
    assert not out.get("Error"), out


def test_extender_schedule_one_fires_http_and_lock_spans():
    api, backend, srv, names = _extender_rig()
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    try:
        _schedule_one(api, conn, names, 0)       # warm: compiles
        before = COUNTERS.snapshot()
        _schedule_one(api, conn, names, 1)
        diff = span_diff(before)
    finally:
        conn.close()
        srv.stop()
    for name in EXTENDER_SPANS:
        assert diff.get(name, (0, 0.0))[0] > 0, (name, diff)
    # one response per verb; the evaluation and the bind wait for the lock
    assert diff["extender.http.encode"][0] == 3
    assert diff["extender.lock_wait"][0] >= 2


def test_extender_lock_wait_grows_while_lock_is_held():
    api, backend, srv, names = _extender_rig()
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    held, hold_s = threading.Event(), 0.3

    def holder():
        with backend._lock:
            held.set()
            time.sleep(hold_s)

    try:
        _schedule_one(api, conn, names, 0)
        before = COUNTERS.snapshot()
        _schedule_one(api, conn, names, 1)
        free = span_diff(before)["extender.lock_wait"][1]
        th = threading.Thread(target=holder)
        th.start()
        held.wait(5)
        before = COUNTERS.snapshot()
        _schedule_one(api, conn, names, 2)
        waited = span_diff(before)["extender.lock_wait"][1]
        th.join()
    finally:
        conn.close()
        srv.stop()
    assert free < 0.1
    assert waited >= 0.5 * hold_s > free


# ------------------------------------------------------ the benchmark side


@pytest.mark.parametrize("busy,spans,windows,want", [
    # idle [2,4) and [6,10); spans cover [3,4) and [6,8): 3 of 6 uncovered
    ([(0, 2), (4, 6)], [(3, 5), (6, 8)], [(0, 10)], 50.0),
    # overlapping busy and spans merge; two windows
    ([(1, 3), (2, 4)], [(0, 1), (0.5, 1)], [(0, 5), (10, 12)], 75.0),
    # no span at all: every idle moment is unspanned
    ([(0, 1)], [], [(0, 4)], 100.0),
    # spans cover the whole idle
    ([(0, 1)], [(1, 4)], [(0, 4)], 0.0),
])
def test_unspanned_idle_pct_on_fixed_intervals(busy, spans, windows, want):
    assert program_trace.unspanned_idle_pct(busy, spans, windows) \
        == pytest.approx(want)


def test_unspanned_idle_pct_none_without_idle():
    assert program_trace.unspanned_idle_pct([(0, 10)], [(0, 1)],
                                            [(2, 8)]) is None


def _traced_stream(path, n_pods):
    """A tiny stream drain under a CPU capture, as the benchmark runs it: each
    step inside a `stream.step` annotation, the clock tied by
    `bench.anchor`. Returns (anchor_mono, interval)."""
    import jax
    api, s = mk_sched()
    loop = s.stream()
    for p in PROFILES["density"](64):      # compile outside the capture
        p.name = "warm-" + p.name
        api.create("Pod", p)
    loop.drain()
    for p in PROFILES["density"](n_pods):
        api.create("Pod", p)
    with capture(path):
        with jax.profiler.TraceAnnotation("bench.anchor"):
            t0 = time.monotonic()
        while not loop.settled():
            with jax.profiler.TraceAnnotation("stream.step"):
                loop.step()
        t1 = time.monotonic()
    loop.close()
    return t0, (t0, t1)


def test_idle_unspanned_reads_a_cpu_stream_capture(tmp_path):
    t0, iv = _traced_stream(tmp_path, 400)
    tr = DeviceTrace(str(tmp_path), anchor_mono=t0, cpu_ops=True)
    assert tr.ops, "the CPU ops stand in for the device"
    spans = program_trace.loop_spans(str(tmp_path))
    assert spans
    got = program_trace.idle_unspanned(tr, find_xplane(str(tmp_path)), [iv])
    assert got is not None and 0.0 <= got <= 100.0


def test_idle_unspanned_is_none_without_program_spans(tmp_path):
    import jax
    with capture(tmp_path):
        with jax.profiler.TraceAnnotation("bench.anchor"):
            t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("stream.step"):
            import jax.numpy as jnp
            jnp.arange(64).sum().block_until_ready()
        t1 = time.monotonic()
    tr = DeviceTrace(str(tmp_path), anchor_mono=t0, cpu_ops=True)
    assert program_trace.loop_spans(str(tmp_path)) == []
    assert program_trace.idle_unspanned(
        tr, find_xplane(str(tmp_path)), [(t0, t1)]) is None
