"""The stream loop schedules CPython's cyclic collector.

Pins the contracts of `engine/streaming.py`'s collector schedule:

- while a ScheduleLoop is live the thresholds are GC_THRESHOLDS, and
  close() puts back exactly the process's own thresholds and enabled
  state, also when a step raises, and with two loops only once the last
  of them closes, in either order;
- collection stays on: a saturated loop that never idles still collects
  a planted reference cycle once the raised gen-0 threshold is crossed;
- a lull runs exactly one idle collection, counted in `gc.idle_collect`
  and `gc.collections.gen*`, with its pause in `gc.pause`;
- each pause is a `gc.pause` annotation on a profiler capture's host
  plane, which the benchmark's `gc_ms_per_kpod.burst` reader clips to
  the bursts (None on a trace without the annotation).
"""

from __future__ import annotations

import gc
import importlib.util
import os
import sys
import time
import weakref

import pytest

from kubernetes_tpu.engine import streaming
from kubernetes_tpu.engine.scheduler import Scheduler
from kubernetes_tpu.models.hollow import PROFILES, hollow_nodes, load_cluster
from kubernetes_tpu.server.apiserver_lite import ApiServerLite
from kubernetes_tpu.utils.trace import COUNTERS

BENCHMARK_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCHMARK_DIR not in sys.path:
    sys.path.append(BENCHMARK_DIR)

OWN = (777, 11, 13)      # the process's own thresholds in these tests


@pytest.fixture
def own_collector():
    """Distinctive thresholds of the process, and no loop live from an
    earlier test (a loop never closed is released by its collection)."""
    gc.collect()
    assert streaming._COLLECTOR._users == 0
    before = gc.get_threshold()
    gc.set_threshold(*OWN)
    try:
        yield
    finally:
        gc.set_threshold(*before)


def mk_sched(n_nodes=32):
    api = ApiServerLite()
    load_cluster(api, hollow_nodes(n_nodes), [])
    s = Scheduler(api, record_events=False)
    s.start()
    return api, s


def feed(api, n, tag):
    for p in PROFILES["density"](n):
        p.name = f"{tag}-{p.name}"
        api.create("Pod", p)


def counts(before):
    snap = COUNTERS.snapshot()
    return {k: snap.get(k, (0, 0.0))[0] - before.get(k, (0, 0.0))[0]
            for k in ("gc.idle_collect", "gc.pause", "gc.collections.gen0",
                      "gc.collections.gen1", "gc.collections.gen2")}


@pytest.mark.parametrize("first_closed", ["stream", "pipeline"])
def test_close_restores_the_collector_after_the_last_loop(own_collector,
                                                          first_closed):
    enabled = gc.isenabled()
    _api, s1 = mk_sched()
    _api, s2 = mk_sched()
    loops = {"stream": s1.stream(), "pipeline": s2.pipeline(chunk=64)}
    assert gc.get_threshold() == streaming.GC_THRESHOLDS
    loops.pop(first_closed).close()
    assert gc.get_threshold() == streaming.GC_THRESHOLDS, \
        "one loop is still live"
    (last,) = loops.values()
    last.close()
    last.close()                 # a second close changes nothing
    assert gc.get_threshold() == OWN
    assert gc.isenabled() == enabled
    assert streaming._COLLECTOR._users == 0
    assert streaming._COLLECTOR._hook not in gc.callbacks


def test_close_restores_the_collector_when_a_step_raises(own_collector,
                                                         monkeypatch):
    enabled = gc.isenabled()
    api, s = mk_sched()
    feed(api, 40, "boom")

    def dispatch(*_a, **_kw):
        assert gc.get_threshold() == streaming.GC_THRESHOLDS
        raise RuntimeError("device lost")
    monkeypatch.setattr(s.engine, "dispatch_waves", dispatch)
    with pytest.raises(RuntimeError, match="device lost"):
        s.run_until_drained(max_batch=16)
    assert gc.get_threshold() == OWN
    assert gc.isenabled() == enabled
    # a loop stepped by hand, closed in a finally, the same
    loop = s.stream()
    with pytest.raises(RuntimeError, match="device lost"):
        try:
            loop.step()
        finally:
            loop.close()
    assert gc.get_threshold() == OWN
    # close() itself raising while it harvests the wave in flight
    api, s = mk_sched()
    loop = s.stream()
    feed(api, 40, "inflight")
    assert loop.step()["popped"] == 40 and not loop.idle

    def complete(_handle):
        raise RuntimeError("harvest failed")
    monkeypatch.setattr(s, "_complete_wave", complete)
    with pytest.raises(RuntimeError, match="harvest failed"):
        loop.close()
    assert gc.get_threshold() == OWN
    assert gc.isenabled() == enabled


class _Cycle:
    pass


def test_saturated_loop_still_collects_a_planted_cycle(own_collector):
    api, s = mk_sched()
    loop = s.stream(min_quantum=64, max_quantum=64)
    feed(api, 64, "warm")
    loop.drain()
    before = COUNTERS.snapshot()
    gc.collect()                 # every generation's count back to 0
    c = _Cycle()
    c.me = c
    planted = weakref.ref(c)
    del c
    held = []
    try:
        for i in range(6):
            feed(api, 64, f"sat{i}")
            assert loop.step()["popped"] > 0, "the loop never idles"
            if i == 1:
                # a few waves allocate far fewer than the raised threshold
                # net: at CPython's 700 the cycle would be gone by now
                assert planted() is not None
            if i == 2:
                # cross the raised gen-0 threshold: the next allocation's
                # collection finds the cycle
                held = [[] for _ in range(streaming.GC_THRESHOLDS[0] + 1)]
        assert planted() is None
    finally:
        del held
        loop.close()
    got = counts(before)
    assert got["gc.idle_collect"] == 0
    assert got["gc.collections.gen0"] + got["gc.collections.gen1"] >= 1


def test_idle_step_after_a_drained_wave_collects_once(own_collector):
    api, s = mk_sched()
    loop = s.stream(min_quantum=64, max_quantum=64)
    feed(api, 64, "warm")
    loop.drain()
    feed(api, 48, "wave")
    assert loop.step()["popped"] == 48
    before = COUNTERS.snapshot()
    try:
        loop.step()                      # harvests the wave: the lull starts
        assert loop.idle
        assert counts(before)["gc.idle_collect"] == 0
        time.sleep(streaming.GC_IDLE_S * 1.5)
        for _ in range(5):
            assert loop.step()["popped"] == 0
        time.sleep(streaming.GC_IDLE_S * 1.5)
        loop.step()                      # the same lull: no second pass
        got = counts(before)
        assert got["gc.idle_collect"] == 1
        assert got["gc.collections.gen1"] + got["gc.collections.gen2"] >= 1
        assert got["gc.pause"] >= 1
        assert COUNTERS.time("gc.pause") > 0
        # a popped wave ends the lull; the next one collects again
        feed(api, 8, "next")
        assert loop.step()["popped"] == 8
        loop.step()
        time.sleep(streaming.GC_IDLE_S * 1.5)
        loop.step()
        assert counts(before)["gc.idle_collect"] == 2
    finally:
        loop.close()


# ------------------------------------------------------ the benchmark side


def _gc_reader():
    sp = importlib.util.spec_from_file_location(
        "gc_ms_reader", os.path.join(BENCHMARK_DIR, "metrics",
                                     "gc_ms_per_kpod.burst.py"))
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


class _Rec:
    def __init__(self, intervals):
        self.intervals = intervals


class _Ctx:
    def __init__(self, trace, intervals):
        self.trace, self.rec = trace, _Rec(intervals)


@pytest.mark.parametrize("loop_live", [True, False])
def test_gc_pause_on_the_host_plane_and_its_reader(own_collector, tmp_path,
                                                   monkeypatch, loop_live):
    import jax

    import drivers
    from trace_reduce import DeviceTrace
    reader = _gc_reader()
    _api, s = mk_sched()
    loop = s.stream() if loop_live else None
    monkeypatch.setattr(drivers, "TRACE_DIR", str(tmp_path))
    drivers._start_trace()
    try:
        with jax.profiler.TraceAnnotation("bench.anchor"):
            t0 = time.monotonic()
        gc.collect()                     # inside the "burst"
        t1 = time.monotonic()
        time.sleep(0.01)
        gc.collect()                     # between bursts: left out
    finally:
        drivers._stop_trace()
        if loop is not None:
            loop.close()
    tr = DeviceTrace(str(tmp_path), anchor_mono=t0)
    got = reader.read(_Ctx(tr, [("burst0", t0, t1, 1000)]))
    if not loop_live:
        assert got is None
        return
    from trace_reduce import find_xplane
    pauses = reader.pauses_ns(find_xplane(str(tmp_path)))
    assert len(pauses) >= 2
    a, b = tr.to_ns(t0), tr.to_ns(t1)
    inside = [(s, e) for s, e in pauses if s >= a and e <= b]
    assert inside and len(inside) < len(pauses)
    want = sum(e - s for s, e in inside) / 1e6     # ms, per 1,000 pods
    assert got == pytest.approx(want, rel=1e-6)
