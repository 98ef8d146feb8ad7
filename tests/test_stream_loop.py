"""Always-on incremental engine (ISSUE 7): the streaming micro-wave loop.

Pins the three contracts that make the arrival stream the headline
instead of the drain:

- the frozen-arrival-trace A/B: streaming micro-waves produce BIT-
  IDENTICAL placements to the fixed-chunk pipelined drain over the same
  admission boundaries — admission control changes WHEN waves run, never
  what a wave means (same discipline as the PR-2 pipelined==sequential
  and PR-5 gang A/Bs);
- the delta-only invariant: while the loop is live, span counters prove
  zero re-tensorization (encoding reuse only), zero full snapshot walks
  (hinted refresh only), and every fence-accepted assume riding the
  raw-delta fold (snapshot.apply_assume_delta) — the Firmament property
  the drain-shaped engine did NOT have under arrivals;
- quantum adaptation: at a pop whose backlog the admission cap cannot
  hold, the cap jumps to the ladder size the busy period's own measured
  waves say the budget allows; it halves the moment latency crosses the
  budget, and never leaves [min_quantum, max_quantum].

Plus the tier-1-fast arrival smoke (ISSUE 7 satellite): a few-second
offered stream on a small cluster must SUSTAIN the offered rate with a
loose create->bound p99 bound, so a streaming regression surfaces
without running the benchmark.
"""

from __future__ import annotations

import time

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.engine.scheduler import Scheduler
from kubernetes_tpu.engine.streaming import ScheduleLoop
from kubernetes_tpu.models.hollow import PROFILES, hollow_nodes, load_cluster
from kubernetes_tpu.server.apiserver_lite import ApiServerLite
from kubernetes_tpu.utils.trace import COUNTERS

Gi = 1 << 30

# a ragged arrival trace: group sizes deliberately non-bucket-aligned so
# the pad-floor machinery (not luck) is what keeps shapes stable
TRACE = (37, 128, 5, 96, 64, 111)


def mk_sched(n_nodes=64):
    api = ApiServerLite()
    load_cluster(api, hollow_nodes(n_nodes), [])
    s = Scheduler(api, record_events=False)
    s.start()
    return api, s


def feed(api, group, tag):
    pods = PROFILES["density"](group)
    for p in pods:
        p.name = f"{tag}-{p.name}"
        api.create("Pod", p)


def placements(api):
    return {p.name: p.node_name for p in api.list("Pod")[0]}


def drain_idle(sched, loop):
    loop.drain()  # the loop's shared quiesce predicate (settled())


# ------------------------------------------------------- frozen-trace A/B


def test_frozen_trace_streaming_equals_pipelined_drain():
    """The ISSUE 7 A/B: the same frozen arrival trace consumed (a) by the
    streaming loop (budget admission, micro-wave quantum) and (b) by the
    fixed-chunk pipelined drain must place every pod on the SAME node.
    Both admit one trace group per step (group sizes stay under the
    quantum/chunk), so the wave boundaries — and therefore the RR draws,
    the blind windows, and the fence decisions — are identical by
    construction; the test pins that the admission-control layer adds
    nothing else."""
    quantum = 128  # >= max(TRACE): one step consumes one group exactly

    # (a) streaming: latency budget generous so adaptation never shrinks
    # the cap below a group mid-trace
    api_a, s_a = mk_sched()
    loop = s_a.stream(budget_s=30.0, min_quantum=quantum,
                      max_quantum=quantum)
    for gi, group in enumerate(TRACE):
        feed(api_a, group, f"g{gi}")
        loop.step()
    drain_idle(s_a, loop)
    loop.close()

    # (b) the pipelined drain, same trace, same chunk (=> same pad floor)
    api_b, s_b = mk_sched()
    pipe = s_b.pipeline(chunk=quantum)
    for gi, group in enumerate(TRACE):
        feed(api_b, group, f"g{gi}")
        pipe.step()
    drain_idle(s_b, pipe)
    pipe.close()

    pa, pb = placements(api_a), placements(api_b)
    assert pa == pb, {k: (pa[k], pb[k]) for k in pa if pa[k] != pb[k]}
    assert all(v for v in pa.values()), "trace must fully bind"
    assert len(pa) == sum(TRACE)


# ----------------------------------------------------- delta-only invariant


def test_stream_delta_only_invariants():
    """While the loop is live, between micro-waves ONLY the delta touches
    the device (ISSUE 7 tentpole): encoding reuse (zero ClassBatch/
    AffinityData rebuilds), hinted refresh (zero full generation scans,
    zero shape rebuilds), and raw-delta assume folds for every bound pod.
    This is the counter-proof that the warm path is the ONLY path —
    the regression an early arrival run exposed (stream going cold between
    rounds) trips these exact counters."""
    api, s = mk_sched()
    loop = s.stream(budget_s=30.0, min_quantum=128, max_quantum=128)
    feed(api, 128, "warm")  # warm: compiles + builds the encoding
    loop.step()
    drain_idle(s, loop)

    COUNTERS.reset()
    groups = (96, 128, 57)
    for gi, group in enumerate(groups):
        feed(api, group, f"live{gi}")
        loop.step()
    drain_idle(s, loop)
    loop.close()
    snap = COUNTERS.snapshot()

    def cnt(name):
        return snap.get(name, (0, 0.0))[0]

    bound = sum(groups)
    assert {p.name: p.node_name
            for p in api.list("Pod")[0] if p.name.startswith("live")} \
        and all(p.node_name for p in api.list("Pod")[0])
    # zero re-tensorization: the cached class encoding serves every wave
    assert cnt("engine.wave_encode_build") == 0, snap
    assert cnt("engine.wave_encode_reuse") >= len(groups)
    # zero full snapshot walks: the owner's dirty notes cover everything
    assert cnt("snapshot.refresh_scan") == 0, snap
    assert cnt("snapshot.refresh_rebuild") == 0, snap
    assert cnt("snapshot.refresh_hinted") >= len(groups)
    # every fence-accepted assume rode the raw-delta fold, none walked
    assert cnt("snapshot.assume_delta_rows") == bound, snap
    # one fused dispatch per micro-wave
    assert cnt("engine.wave_dispatch") == len(groups), snap


# ------------------------------------------------------ quantum adaptation


class _FakeHandle:
    def __init__(self, n, latency):
        self.pods = [None] * n
        self.pop_ts = time.monotonic() - latency


class _FakeEngine:
    wave_pad_floor = 0

    def dispatch_waves(self, pods, pop_ts, gangs=None):
        return _FakeHandle(len(pods), 0.0)


class _FakeQueue:
    """A ready backlog of `backlog` pods; records each pop's cap."""

    def __init__(self, backlog=0):
        self.backlog = backlog
        self.caps = []

    def ready_count(self):
        return self.backlog

    def pop_batch(self, max_n=0, wait=None):
        self.caps.append(max_n)
        n = min(max_n, self.backlog)
        self.backlog -= n
        return [None] * n


class _FakeSched:
    """Enough of a Scheduler for ScheduleLoop.step(): a wave's harvest
    lands `latency(pods)` seconds after its pop, so the loop observes
    each wave one step late, as it does a real one."""

    def __init__(self, backlog=0, latency=None):
        self.engine = _FakeEngine()
        self._pipeline = None
        self.pipeline_chunk = 4096
        self.queue = _FakeQueue(backlog)
        self.latency = latency

    def sync(self, wait=0.0):
        return 0

    def _idle_gc(self):
        pass

    def _sweep_parked_gangs(self, pods):
        pass

    def _release_gangs_for_wave(self, pods, stats):
        return pods, None

    def _complete_wave(self, handle):
        handle.pop_ts = time.monotonic() - self.latency(len(handle.pods))
        return {"bound": len(handle.pods)}


def _sized():
    return COUNTERS.snapshot().get("stream.quantum_sized", (0, 0))[0]


def _settled(fixed, per_pod, budget, lo, hi):
    """Where a saturated stream settles under the budget, for waves that
    cost `fixed + per_pod * pods`: the largest ladder size whose wave ends
    within the budget and whose next size down ends within half of it."""
    q = lo
    while 2 * q <= hi and fixed + per_pod * 2 * q <= budget \
            and fixed + per_pod * q < 0.5 * budget:
        q *= 2
    return q


# (fixed s, s per pod): settles at 4,096, at max_quantum, at min_quantum
COST_MODELS = [(0.03, 3e-5), (0.005, 1e-5), (0.1, 1e-4)]


@pytest.mark.parametrize("fixed,per_pod", COST_MODELS)
def test_fresh_loop_reaches_settled_quantum_by_fifth_pop(fixed, per_pod):
    """A fresh loop facing a 30,000-pod backlog pops twice at the quantum
    it starts from (no wave of the busy period has completed), sizes from
    its first wave, and pops the settled size by its 5th pop: once two
    wave sizes are measured, the fit parts fixed from per-pod cost."""
    s = _FakeSched(30_000, lambda n: fixed + per_pod * n)
    loop = ScheduleLoop(s, budget_s=0.25, min_quantum=256,
                        max_quantum=16384)
    want = _settled(fixed, per_pod, 0.25, 256, 16384)
    before = _sized()
    while s.queue.backlog:
        loop.step()
    caps = s.queue.caps
    assert caps[:2] == [256, 256]
    assert caps[4] == want, caps
    assert max(caps) == want, caps
    assert (_sized() > before) == (want > 256)


def test_sized_jump_never_admits_over_budget():
    """Whatever the waves cost, a jump lands only on a size whose
    predicted latency stays within the budget: the per-pod overstatement
    of one measured wave, and the exact fit, both hold true latency
    under it; where a fit's fixed cost comes out negative, the next size
    down under half the budget does not by itself admit a size."""
    budget = 0.25
    for fixed in (0.0, 0.01, 0.05, 0.2):
        for per_pod in (1e-6, 1e-5, 1e-4, 1e-3):
            s = _FakeSched(30_000, lambda n: fixed + per_pod * n)
            loop = ScheduleLoop(s, budget_s=budget, min_quantum=256,
                                max_quantum=16384)
            while s.queue.backlog:
                before = _sized()
                loop.step()
                if _sized() > before:
                    assert fixed + per_pod * loop.quantum <= budget, \
                        (fixed, per_pod, s.queue.caps)

    # fit: -0.1 s fixed, 0.2 ms per pod. 1,024 is predicted 0.105 s, so
    # 2,048 passes the half-budget test yet is predicted 0.310 s
    s = _FakeSched(30_000)
    loop = ScheduleLoop(s, budget_s=budget, min_quantum=256,
                        max_quantum=16384)
    loop._observe_wave(_FakeHandle(512, 0.0024))
    loop._observe_wave(_FakeHandle(1024, 0.1048))
    loop._size_quantum()
    assert loop.quantum == 1024


def test_waves_before_idle_do_not_size_the_next_busy_period():
    """The loop forgets its waves when it goes idle: a busy period of
    1 ms waves, then an idle step, then a 30,000-pod backlog pops at the
    quantum as it stands until a wave of the NEW period has completed."""
    s = _FakeSched(600, lambda n: 0.001)
    loop = ScheduleLoop(s, budget_s=0.25, min_quantum=256,
                        max_quantum=16384)
    while s.queue.backlog or not loop.idle:
        loop.step()
    assert s.queue.caps[-1] == 256      # the idle step; no backlog fit
    before = _sized()
    s.queue.backlog = 30_000
    loop.step()
    loop.step()
    assert s.queue.caps[-2:] == [256, 256] and _sized() == before
    # the period's own first wave sizes it
    loop.step()
    assert s.queue.caps[-1] == 16384 and _sized() == before + 1


def test_backlog_within_quantum_leaves_it_unchanged():
    """A backlog at or below the quantum pops whole at the quantum as it
    stands, however cheap the measured waves: only a backlog the quantum
    cannot hold sizes it."""
    s = _FakeSched()
    loop = ScheduleLoop(s, budget_s=0.25, min_quantum=256,
                        max_quantum=16384)
    loop._observe_wave(_FakeHandle(256, 0.001))
    loop._observe_wave(_FakeHandle(200, 0.001))
    for backlog in (0, 100, 256):
        s.queue.backlog = backlog
        loop._size_quantum()
        assert loop.quantum == 256
    s.queue.backlog = 257
    loop._size_quantum()
    assert loop.quantum == 16384


def test_quantum_adaptation_bounds_and_hysteresis():
    """Unit contract of the admission model: no growth from waves alone,
    however fast (a partial wave earns nothing: the quantum grows only at
    a pop whose backlog it cannot hold); shrink immediately when the EWMA
    crosses the budget; clamp to [min_quantum, max_quantum]; fixed mode
    inert."""
    s = _FakeSched()
    loop = ScheduleLoop(s, budget_s=0.2, min_quantum=64, max_quantum=256)
    assert loop.quantum == 64
    assert s.engine.wave_pad_floor == 64  # the shape-ladder floor

    # fast waves, partial and full, while the backlog fits: no growth
    for n in (10, 64, 64):
        loop._observe_wave(_FakeHandle(n, 0.01))
    s.queue.backlog = 64
    loop._size_quantum()
    assert loop.quantum == 64
    # a backlog it cannot hold: one size the per-pod fit cannot part
    # from the other, so the dearest per-pod rate (the partial wave's,
    # 1 ms) sizes it: 128 is predicted 0.128 s, 256 0.256 s > budget
    s.queue.backlog = 1000
    loop._size_quantum()
    assert loop.quantum == 128
    # cap: a cheap wave cannot size a fresh loop past max_quantum
    loop = ScheduleLoop(s, budget_s=0.2, min_quantum=64, max_quantum=256)
    loop._observe_wave(_FakeHandle(64, 0.0001))
    loop._size_quantum()
    assert loop.quantum == 256
    # over-budget wave shrinks immediately...
    loop._observe_wave(_FakeHandle(256, 5.0))
    assert loop.quantum == 128
    # ...and the floor holds no matter how slow it gets; a backlog does
    # not size it back up past what its slow waves predict
    loop._observe_wave(_FakeHandle(128, 5.0))
    loop._observe_wave(_FakeHandle(64, 5.0))
    loop._observe_wave(_FakeHandle(64, 5.0))
    loop._size_quantum()
    assert loop.quantum == 64

    # fixed mode: neither a slow wave nor a backlog moves the chunk
    s = _FakeSched(30_000)
    loop = ScheduleLoop(s, chunk=1000)
    loop._observe_wave(_FakeHandle(1000, 9.9))
    loop._size_quantum()
    assert loop.quantum == 1000


def test_fixed_mode_pins_one_shape():
    """budget_s=None is the drain: quantum == chunk, pad floor == chunk —
    the ISSUE 2 contract the headline drain's compile stability rides."""
    s = _FakeSched()
    loop = ScheduleLoop(s, chunk=1000)
    assert loop.quantum == 1000
    assert s.engine.wave_pad_floor == 1000
    loop._observe_wave(_FakeHandle(1000, 9.9))  # adaptation inert
    assert loop.quantum == 1000


# ------------------------------------------------------- tier-1 fast smoke


def test_arrival_smoke_sustains_offered_rate():
    """The CI streaming smoke (ISSUE 7 satellite): a creator thread offers
    300 pods/s for 2 s into Scheduler.stream() on 64 nodes. The loop must
    bind every pod, consume the stream AT the offered rate inside the
    offer window, and give a real create->bound distribution (creator
    stamp to the wave_observer's bind instant) under a loose p99 bound.
    Shapes are tiny so the ladder warm is cheap on CI."""
    import threading

    import numpy as np

    rate, total, interval_s = 300.0, 600, 0.5
    # compile the micro-wave shape ladder on a throwaway cluster (jit
    # caches are process-wide) so no mid-offer compile lands in a latency
    warm_api, warm = mk_sched()
    for size in (64, 128, 256):
        feed(warm_api, size, f"ladder{size}")
        warm.run_until_drained(max_batch=size)
    api, s = mk_sched()
    loop = s.stream(budget_s=0.5, min_quantum=64, max_quantum=256,
                    chunk=64)
    feed(api, 64, "prime")       # boot this scheduler's resident state
    drain_idle(s, loop)

    pods = PROFILES["density"](total)
    index = {p.key(): i for i, p in enumerate(pods)}
    created_at = np.full(total, -1.0)
    bound_at = np.full(total, -1.0)
    seen = []
    t0 = time.monotonic()

    def observe(ts, keys):
        seen.extend(keys)
        for k in keys:
            bound_at[index[k]] = ts - t0

    s.wave_observer = observe
    created = [0]

    def creator():
        while created[0] < total:
            due = min(total, int(rate * (time.monotonic() - t0)))
            for i in range(created[0], due):
                api.create("Pod", pods[i])
                created_at[i] = time.monotonic() - t0
            created[0] = max(created[0], due)
            time.sleep(0.002)

    thread = threading.Thread(target=creator, daemon=True)
    thread.start()
    deadline = t0 + 60.0

    def done(stats, lp):
        assert time.monotonic() < deadline, "stream did not settle"
        return created[0] >= total and stats["popped"] == 0 \
            and lp.settled()

    try:
        loop.run(done)
    finally:
        loop.close()
        s.wave_observer = None
    thread.join(timeout=10)
    assert not thread.is_alive()

    assert (bound_at >= 0).all(), int((bound_at < 0).sum())
    assert len(seen) == total == len(set(seen)), len(seen)
    # sustained = median bind rate over the full buckets inside the offer
    # window, the first dropped as ramp: the post-offer drain cannot count
    offer_end = float(created_at.max())
    buckets = np.bincount((bound_at / interval_s).astype(int))
    steady = buckets[1:int(offer_end / interval_s)]
    assert steady.size, buckets
    sustained = float(np.sort(steady)[steady.size // 2]) / interval_s
    assert sustained >= 0.95 * rate, (sustained, buckets.tolist())
    lat_ms = (bound_at - created_at) * 1e3
    p50, p99 = np.percentile(lat_ms, 50), np.percentile(lat_ms, 99)
    # loose p99: double-digit ms warm on CI; anything near a second
    # means the stream went cold mid-offer
    assert p99 < 1500.0, (p50, p99)
    assert 0 < p50 < p99, "per-pod create->bound must be a distribution"


# ------------------------------------------------- per-bind wave_observer


def _instrument(api, s):
    """From here on, stamp each pod's first pop (after the queue hands it
    out) and collect every wave_observer report; returns (pops, reports,
    keys already bound in the store)."""
    pops, reports = {}, []
    for name in ("pop_batch", "pop_fast"):
        def popped(*a, _pop=getattr(s.queue, name), **kw):
            out = _pop(*a, **kw)
            t = time.monotonic()
            for p in out:
                pops.setdefault(p.key(), t)
            return out
        setattr(s.queue, name, popped)
    s.wave_observer = lambda ts, keys: reports.append((ts, list(keys)))
    return pops, reports, _bound_keys(api)


def _bound_keys(api):
    return {p.key() for p in api.list("Pod")[0] if p.node_name}


def _classic_round():
    api, s = mk_sched()
    feed(api, 40, "classic")
    seen = _instrument(api, s)
    while s.schedule_round()["popped"]:
        pass
    return api, seen


def _stream_harvest():
    api, s = mk_sched()
    loop = s.stream(budget_s=30.0, min_quantum=64, max_quantum=64)
    seen = _instrument(api, s)
    feed(api, 100, "stream")
    loop.drain()
    loop.close()
    return api, seen


def _wave_preemption():
    from kubernetes_tpu.utils import features

    # two nodes full of bound low-priority pods: the preemptor must
    # displace one to bind
    api = ApiServerLite()
    nodes = [make_node(f"n{i}", cpu=800, memory=16 * Gi, pods=4)
             for i in range(2)]
    lows = [make_pod(f"low-{k}", cpu=200, memory=256 << 20,
                     node_name=f"n{k // 4}") for k in range(8)]
    load_cluster(api, nodes, lows)
    features.DEFAULT_FEATURE_GATE.set("PodPriority", True)
    try:
        s = Scheduler(api, record_events=False)
        s.start()
        loop = s.stream(budget_s=30.0, min_quantum=16, max_quantum=16)
        seen = _instrument(api, s)
        hi = make_pod("hi", cpu=200, memory=256 << 20)
        hi.priority = 1000
        api.create("Pod", hi)
        preemptions = 0
        for _ in range(6):
            preemptions += loop.step().get("preemptions", 0)
            if preemptions:
                break
        loop.close()
    finally:
        features.DEFAULT_FEATURE_GATE.reset()
    assert preemptions == 1
    return api, seen


def _fast_lane():
    from kubernetes_tpu.engine.fastlane import FASTLANE_ANNOTATION

    api, s = mk_sched()
    loop = s.stream(budget_s=30.0, fastlane=True)
    feed(api, 64, "warm")
    loop.drain()
    COUNTERS.reset()
    seen = _instrument(api, s)
    for i in range(8):
        p = make_pod(f"fast-{i}", cpu=100, memory=128 << 20)
        p.annotations[FASTLANE_ANNOTATION] = "true"
        api.create("Pod", p)
    loop.drain()
    loop.close()
    assert COUNTERS.snapshot().get("fastlane.bound", (0, 0))[0] == 8
    return api, seen


@pytest.mark.parametrize("bind_site", [_classic_round, _stream_harvest,
                                       _wave_preemption, _fast_lane],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_wave_observer_reports_every_bind_once(bind_site):
    """Scheduler.wave_observer is how a loop owner times every bind (the
    benchmark's drivers join it against their creation stamps): each of
    the four bind sites (classic round, stream harvest, wave preemption,
    fast lane) reports every pod it binds exactly once, at an instant no
    earlier than the pod's pop."""
    api, (pops, reports, before) = bind_site()
    keys = [k for _ts, ks in reports for k in ks]
    assert keys
    assert len(keys) == len(set(keys)), "a bind was reported twice"
    assert set(keys) == _bound_keys(api) - before
    for ts, ks in reports:
        for k in ks:
            assert ts >= pops[k], (k, ts, pops[k])
