"""Sparrow fast lane (ISSUE 17): the sub-10 ms admission tier beside the
bulk waves.

What these tests pin:

- **off-by-default bit-identity**: the lane armed with ZERO
  latency-critical pods is invisible — the same frozen arrival trace
  places every pod on the SAME node as a lane-less run, with zero
  fast-lane dispatches and the same wave count (span counters, not
  vibes);
- **exactly-once under contention**: a fast bind and an in-flight wave
  racing one last-slot node resolve through the fence — store truth
  shows exactly one bind, the wave row requeues;
- **doomed-note fence**: a node-dying watch event noted but not yet
  applied (engine.note_node_doomed) refuses the fast bind BEFORE the
  liveness ladder — the ISSUE 8 fence extended to this path;
- **typed outcome partition**: bound + fell_back + bind_error +
  superseded == fast pods popped, with the fence-loss reasons counted
  by name;
- **delta-free evals**: a fast-only window builds zero encodings and
  walks zero full snapshots (the wave machinery never wakes);
- **device/host twin equivalence**: the jitted [1, k] kernel and its
  numpy twin agree on winner and fit count exactly (score within float
  rounding);
- **per-tier SLO**: fast binds burn the fast tier's own objective and
  surface as slo.fast.* through the telemetry registry and the
  Prometheus rendering every transport serves.
"""

from __future__ import annotations

import time

import numpy as np

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.engine.fastlane import (
    FASTLANE_ANNOTATION,
    FastLane,
    eligible,
    is_latency_critical,
)
from kubernetes_tpu.engine.scheduler import Scheduler
from kubernetes_tpu.models.hollow import PROFILES, hollow_nodes, load_cluster
from kubernetes_tpu.ops.fastlane import (
    FAST_NODE_KEYS,
    sample_eval,
    sample_eval_host,
)
from kubernetes_tpu.server.apiserver_lite import ApiServerLite
from kubernetes_tpu.utils.trace import COUNTERS

Mi = 1 << 20
Gi = 1 << 30

TRACE = (37, 128, 5, 96)


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


def fast_pod(name, cpu=100, mem=128 * Mi):
    p = make_pod(name, cpu=cpu, memory=mem)
    p.annotations[FASTLANE_ANNOTATION] = "true"
    return p


def placements(api):
    return {p.name: p.node_name for p in api.list("Pod")[0]}


def fast_counters():
    return {k: v[0] for k, v in COUNTERS.snapshot().items()
            if k.startswith("fastlane.")}


# ------------------------------------------------------------- eligibility


def test_tier_contract_annotation_and_priority_band():
    p = make_pod("plain", cpu=100, memory=64 * Mi)
    assert not is_latency_critical(p)
    p.annotations[FASTLANE_ANNOTATION] = "true"
    assert is_latency_critical(p) and eligible(p)
    q = make_pod("banded", cpu=100, memory=64 * Mi)
    q.priority = 2_000_000_000
    assert is_latency_critical(q) and eligible(q)


def test_eligibility_declines_everything_the_kernel_cannot_model():
    base = fast_pod("f")
    assert eligible(base)
    for mutate in (
        lambda p: setattr(p, "node_name", "pinned"),
        lambda p: setattr(p, "node_selector", {"zone": "a"}),
        lambda p: setattr(p, "tolerations", [object()]),
    ):
        p = fast_pod("f2")
        mutate(p)
        assert not eligible(p), mutate
    sel = make_pod("sel", cpu=100, memory=64 * Mi, ports=[8080])
    sel.annotations[FASTLANE_ANNOTATION] = "true"
    assert not eligible(sel)  # host ports: not in the [1,k] kernel
    ext = make_pod("ext", cpu=100, memory=64 * Mi,
                   extended={"example.com/foo": 1})
    ext.annotations[FASTLANE_ANNOTATION] = "true"
    assert not eligible(ext)  # extended resource: vocab-dependent row


# --------------------------------------------------- frozen-trace A/B (off)


def test_lane_armed_but_unused_is_bit_identical():
    """The satellite A/B: fast lane ENABLED with zero latency-critical
    pods must be invisible — same binds as a lane-less run on the same
    frozen trace, zero fast-lane dispatches, same wave count."""
    quantum = 128

    def run(fastlane):
        api, s = mk_sched()
        COUNTERS.reset()
        loop = s.stream(budget_s=30.0, min_quantum=quantum,
                        max_quantum=quantum, fastlane=fastlane)
        for gi, group in enumerate(TRACE):
            feed(api, group, f"g{gi}")
            loop.step()
        loop.drain()
        loop.close()
        snap = COUNTERS.snapshot()
        return placements(api), {
            "waves": snap.get("engine.wave_dispatch", (0, 0))[0],
            "fast": {k: v[0] for k, v in snap.items()
                     if k.startswith("fastlane.")}}

    pa, ca = run(True)
    pb, cb = run(False)
    assert pa == pb, {k: (pa[k], pb[k]) for k in pa if pa[k] != pb[k]}
    assert all(v for v in pa.values()), "trace must fully bind"
    # zero extra dispatches: the armed-but-unused lane never popped,
    # never evaluated, never touched a counter — and admitted the same
    # number of waves
    assert not any(ca["fast"].values()), ca["fast"]
    assert ca["waves"] == cb["waves"], (ca, cb)


# -------------------------------------------------------------- happy path


def test_fast_pods_bind_through_the_lane():
    api, s = mk_sched()
    loop = s.stream(budget_s=30.0, fastlane=True)
    feed(api, 64, "warm")
    loop.drain()
    COUNTERS.reset()
    for i in range(8):
        api.create("Pod", fast_pod(f"fast-{i}"))
    loop.drain()
    loop.close()
    c = fast_counters()
    assert c.get("fastlane.bound", 0) == 8, c
    placed = placements(api)
    assert all(placed[f"fast-{i}"] for i in range(8))
    # typed outcome partition: every popped fast pod lands in exactly
    # one outcome bucket
    outcomes = (c.get("fastlane.bound", 0)
                + c.get("fastlane.fell_back", 0)
                + c.get("fastlane.bind_error", 0)
                + c.get("fastlane.superseded", 0))
    assert outcomes == 8, c


def test_fast_only_window_is_delta_free():
    """Fast-lane evals never build an encoding and never walk the full
    snapshot: the counter-proof that the lane rides RESIDENT state (the
    acceptance bar's span-counter invariant)."""
    api, s = mk_sched()
    loop = s.stream(budget_s=30.0, fastlane=True)
    feed(api, 64, "warm")  # primes snapshot + encoding via the wave path
    loop.drain()
    COUNTERS.reset()
    for i in range(16):
        api.create("Pod", fast_pod(f"fast-{i}"))
    loop.drain()
    loop.close()
    snap = COUNTERS.snapshot()

    def cnt(name):
        return snap.get(name, (0, 0.0))[0]

    assert cnt("fastlane.bound") == 16, snap
    assert cnt("engine.wave_encode_build") == 0, snap
    assert cnt("engine.wave_dispatch") == 0, snap
    assert cnt("snapshot.refresh_scan") == 0, snap
    assert cnt("snapshot.refresh_rebuild") == 0, snap


# -------------------------------------------------------------- contention


def test_contended_node_store_truth_shows_exactly_one_bind():
    """A fast bind and an in-flight (blind) wave race the ONE node with
    one free slot: the fast pod lands first through its fence, the wave
    row must lose at the harvest fence — store truth shows exactly one
    pod on the node, no duplicate bind, no lost pod."""
    api = ApiServerLite()
    load_cluster(api, [make_node("solo", cpu=150, memory=1 * Gi,
                                 pods=110)], [])
    s = Scheduler(api, record_events=False)
    s.start()
    loop = s.stream(budget_s=30.0, fastlane=True)
    COUNTERS.reset()
    # the bulk pod rides a wave; dispatch it and leave it in flight
    # (blind window open)
    api.create("Pod", make_pod("bulk-0", cpu=100, memory=64 * Mi))
    s.sync()
    pods = s.queue.pop_batch()
    assert [p.name for p in pods] == ["bulk-0"]
    handle = s.engine.dispatch_waves(pods, time.monotonic())
    # while the wave is in flight, a latency-critical pod takes the slot
    api.create("Pod", fast_pod("fast-0"))
    s.sync()
    stats = {}
    assert loop._pump_fast(stats, busy=handle) == 1
    # now harvest: the fence re-validates the wave row against live
    # truth (the fast bind moved capacity) and must requeue it
    s._complete_wave(handle)
    placed = placements(api)
    assert placed["fast-0"] == "solo"
    assert not placed["bulk-0"], placed
    assert sum(1 for v in placed.values() if v == "solo") == 1
    c = fast_counters()
    assert c.get("fastlane.bound", 0) == 1, c
    loop.close()


def test_capacity_fence_loss_resamples_then_falls_back():
    """A stale snapshot score loses the capacity fence: the lane
    resamples with jitter (typed counter) and after bounded retries
    hands the pod to the wave path — never drops it."""
    api = ApiServerLite()
    load_cluster(api, [make_node("solo", cpu=150, memory=1 * Gi,
                                 pods=110)], [])
    s = Scheduler(api, record_events=False)
    s.start()
    loop = s.stream(budget_s=30.0, fastlane=True)
    COUNTERS.reset()
    api.create("Pod", fast_pod("fast-0"))
    s.sync()
    assert loop._pump_fast({}) == 1  # binds; snapshot NOT refreshed
    api.create("Pod", fast_pod("fast-1"))
    s.sync()
    assert loop._pump_fast({}) == 1  # stale eval fits, live fence says no
    c = fast_counters()
    assert c.get("fastlane.bound", 0) == 1, c
    assert c.get("fastlane.fence_capacity", 0) >= 1, c
    assert c.get("fastlane.resampled", 0) >= 1, c
    assert c.get("fastlane.fell_back", 0) == 1, c
    # the loser is safe on the bulk tier, not lost
    assert s.queue.ready_count() == 1
    loop.close()


def test_doomed_note_blocks_fast_bind_before_liveness():
    """Node-kill during a fast-lane bind (the satellite): the owner has
    SEEN the dying watch event (note_node_doomed) but not applied it —
    the fence must refuse the bind on the note alone, and the pod falls
    back to the wave path rather than landing on a dying node."""
    api = ApiServerLite()
    load_cluster(api, [make_node("dying", cpu=4000, memory=4 * Gi,
                                 pods=110)], [])
    s = Scheduler(api, record_events=False)
    s.start()
    loop = s.stream(budget_s=30.0, fastlane=True)
    COUNTERS.reset()
    s.engine.note_node_doomed("dying")
    api.create("Pod", fast_pod("fast-0"))
    s.sync()
    assert loop._pump_fast({}) == 1
    c = fast_counters()
    assert c.get("fastlane.fence_doomed", 0) >= 1, c
    assert c.get("fastlane.fell_back", 0) == 1, c
    assert c.get("fastlane.bound", 0) == 0, c
    placed = placements(api)
    assert not placed["fast-0"]
    # the doom clears (event applied, node lived): the wave path binds it
    s.engine.clear_node_doomed("dying")
    loop.drain()
    loop.close()
    assert placements(api)["fast-0"] == "dying"


# --------------------------------------------------------- eval twin parity


def test_device_and_host_eval_twins_agree():
    """The jitted [1, k] kernel and its numpy twin must agree on winner
    and fit count EXACTLY (same inputs), score within float rounding —
    the routing choice (device idle vs busy) is latency policy, never a
    semantics fork."""
    api, s = mk_sched(16)
    loop = s.stream(budget_s=30.0, fastlane=True)
    feed(api, 48, "warm")  # uneven load so scores differ across nodes
    loop.drain()
    loop.close()
    snap = s.engine.snapshot
    nodes = {k: np.asarray(getattr(snap, k)) for k in FAST_NODE_KEYS}
    req = snap.resource_row(milli_cpu=100, memory=128 * Mi, gpu=0,
                            scratch=0, overlay=0, extended={}, up=True,
                            width=snap.num_resources)
    rng = np.random.default_rng(7)
    for _trial in range(8):
        idx = rng.integers(0, len(snap.node_names), size=16).astype(
            np.int32)
        host = sample_eval_host(idx, req, False, False, nodes)
        dev = np.asarray(sample_eval(idx, req, False, False,
                                     nodes))  # graftlint: sync-ok
        assert int(host[0]) == int(dev[0]), (host, dev)
        assert int(host[1]) == int(dev[1]), (host, dev)
        assert abs(int(host[2]) - int(dev[2])) <= 2, (host, dev)


def test_device_path_used_when_device_idle_and_current():
    """When no wave is in flight and the resident device arrays are at
    the snapshot's version, the eval dispatches on device (counted) —
    and returns the same bind the host twin would have made."""
    api, s = mk_sched(8)
    loop = s.stream(budget_s=30.0, fastlane=True)
    feed(api, 16, "warm")
    loop.drain()
    fl = loop.fastlane
    # force the resident mirror current (a harvest bumps the snapshot
    # version past the device's; re-align as a fresh dispatch would —
    # _nodes_on_device stamps _device_version itself)
    s.engine._refresh()
    s.engine._nodes_on_device()
    COUNTERS.reset()
    api.create("Pod", fast_pod("fast-dev"))
    s.sync()
    pods = s.queue.pop_fast()
    assert len(pods) == 1
    fl.schedule(pods[0], time.monotonic(), device_ok=True)
    c = fast_counters()
    assert c.get("fastlane.dispatch_device", 0) == 1, c
    assert c.get("fastlane.bound", 0) == 1, c
    assert placements(api)["fast-dev"]
    loop.close()


# ------------------------------------------------------------ per-tier SLO


def test_fast_tier_slo_surfaces_through_registry_and_prometheus():
    from kubernetes_tpu.observability.registry import TelemetryRegistry
    from kubernetes_tpu.observability.slo import SLO_FAST
    SLO_FAST.clear()
    SLO_FAST.enable()
    try:
        api, s = mk_sched(8)
        loop = s.stream(budget_s=30.0, fastlane=True)
        feed(api, 16, "warm")
        loop.drain()
        COUNTERS.reset()
        for i in range(4):
            api.create("Pod", fast_pod(f"fast-{i}"))
        loop.drain()
        loop.close()
        assert fast_counters().get("fastlane.bound", 0) == 4
        reg = TelemetryRegistry()
        snap = reg.snapshot()
        fast_keys = [k for k in snap if k.startswith("slo.fast.")]
        assert fast_keys, sorted(snap)[:20]
        text = reg.render_prometheus()
        assert "tpu_slo_fast_" in text
        # the extender's /debug/slo payload (all three transports share
        # this one method) carries the fast tier beside the bulk one
        from kubernetes_tpu.server.extender import TPUExtenderBackend
        payload = TPUExtenderBackend().debug_slo()
        assert "fast" in payload and isinstance(payload["fast"], dict)
    finally:
        SLO_FAST.disable()
        SLO_FAST.clear()


# ------------------------------------------------------------ queue tiering


def test_fallback_pod_never_reroutes_into_the_fast_tier():
    """add_bulk bypasses the classifier: a fell-back latency-critical
    pod rides the wave path next (no starvation loop)."""
    api, s = mk_sched(4)
    loop = s.stream(budget_s=30.0, fastlane=True)
    p = fast_pod("loopy")
    s.queue.add_bulk([p])
    assert s.queue.fast_count() == 0
    assert s.queue.ready_count() == 1
    got = s.queue.pop_batch()
    assert [q.name for q in got] == ["loopy"]
    loop.close()


def test_bulk_aging_guard_untouched_by_fast_tier():
    """The r14 starvation guard lives on the BULK tier only: an aged
    bulk pod still pops ahead of fresh high-priority arrivals while the
    fast tier drains separately."""
    from kubernetes_tpu.utils import features
    api, s = mk_sched(4)
    loop = s.stream(budget_s=30.0, fastlane=True)
    q = s.queue
    old = make_pod("old-victim", cpu=100, memory=64 * Mi)
    young = make_pod("young-vip", cpu=100, memory=64 * Mi)
    young.priority = 1000
    fast = fast_pod("fast-0")
    features.DEFAULT_FEATURE_GATE.set("PodPriority", True)
    try:
        q.add(old)
        q.add(young)
        q.add(fast)
        # backdate the victim past the aging threshold (the r14 guard's
        # trigger); the vip stays fresh
        q._queued_at[old.key()] -= q.aging_threshold_s + 1.0
        assert q.fast_count() == 1
        popped = q.pop_batch()
    finally:
        features.DEFAULT_FEATURE_GATE.set("PodPriority", False)
    assert [p.name for p in popped] == ["old-victim", "young-vip"]
    assert [p.name for p in q.pop_fast()] == ["fast-0"]
    loop.close()
