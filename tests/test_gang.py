"""Gang (coscheduling) placement: quorum gating, atomicity, rollback.

BASELINE.json config 4 — new capability vs the reference's sequential
one-pod loop. The hard invariant is all-or-nothing: a gang that cannot
fully place leaves ZERO residue (no assumed pods, no partial binds), the
failure mode gang scheduling exists to prevent."""

from __future__ import annotations

import dataclasses

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.engine.gang import (
    GANG_MIN_AVAILABLE_ANNOTATION,
    GANG_NAME_ANNOTATION,
)
from kubernetes_tpu.engine.scheduler import Scheduler
from kubernetes_tpu.models.hollow import gang_pods
from kubernetes_tpu.server.apiserver_lite import ApiServerLite

Mi = 1 << 20
Gi = 1 << 30


def _gang_pod(name, gang, quorum, cpu=100):
    p = make_pod(name, cpu=cpu, memory=64 * Mi)
    p.annotations[GANG_NAME_ANNOTATION] = gang
    p.annotations[GANG_MIN_AVAILABLE_ANNOTATION] = str(quorum)
    return p


def _rig(n_nodes=4, cpu=1000):
    api = ApiServerLite()
    for i in range(n_nodes):
        api.create("Node", make_node(f"n{i}", cpu=cpu, memory=8 * Gi))
    sched = Scheduler(api, record_events=False)
    sched.start()
    return api, sched


def test_gang_schedules_atomically_when_it_fits():
    api, sched = _rig()
    for i in range(6):
        api.create("Pod", _gang_pod(f"g-{i}", "job-a", 6))
    totals = sched.run_until_drained()
    assert totals["bound"] == 6
    pods, _ = api.list("Pod")
    assert all(p.node_name for p in pods)


def test_gang_waits_for_quorum():
    api, sched = _rig()
    for i in range(3):
        api.create("Pod", _gang_pod(f"g-{i}", "job-a", 6))
    sched.run_until_drained()
    pods, _ = api.list("Pod")
    assert all(not p.node_name for p in pods), "below quorum: nothing binds"
    assert "job-a" in sched._gang_waiting
    # the remaining members arrive -> the whole gang goes
    for i in range(3, 6):
        api.create("Pod", _gang_pod(f"g-{i}", "job-a", 6))
    totals = sched.run_until_drained()
    assert totals["bound"] == 6
    assert "job-a" not in sched._gang_waiting


def test_infeasible_gang_leaves_zero_residue():
    """One member can never fit and quorum is the full gang -> no member
    binds AND no member stays assumed in the cache (capacity released)."""
    api, sched = _rig(n_nodes=4, cpu=1000)
    for i in range(4):
        api.create("Pod", _gang_pod(f"g-{i}", "job-x", 5))
    api.create("Pod", _gang_pod("g-huge", "job-x", 5, cpu=50_000))
    totals = sched.run_until_drained()
    assert totals["bound"] == 0
    assert totals["unschedulable"] >= 5
    pods, _ = api.list("Pod")
    assert all(not p.node_name for p in pods)
    # zero residue: every node's accounted capacity is untouched
    for info in sched.cache.node_infos().values():
        assert info.requested.milli_cpu == 0
        assert not info.pods


def test_partial_fit_gang_rolls_back():
    """The gang fits individually but not jointly (aggregate capacity
    passes the precheck; per-node packing fails) -> rollback, zero
    residue."""
    api, sched = _rig(n_nodes=2, cpu=1000)
    # 2 nodes x 1000m; gang of 3 pods x 700m: any 2 fit, 3 cannot
    # (aggregate 2100m > 2000m free is caught by the precheck, so use
    # 3 x 650m = 1950m < 2000m aggregate but only 1 fits per node)
    for i in range(3):
        api.create("Pod", _gang_pod(f"g-{i}", "job-p", 3, cpu=650))
    totals = sched.run_until_drained()
    assert totals["bound"] == 0
    pods, _ = api.list("Pod")
    assert all(not p.node_name for p in pods)
    for info in sched.cache.node_infos().values():
        assert info.requested.milli_cpu == 0


def test_quorum_commit_with_stragglers_retrying_solo():
    """Coscheduling PodGroup semantics: the gang commits when minAvailable
    members place; extras past quorum retry individually (the gang is past
    its atomicity point and marked degraded)."""
    api, sched = _rig(n_nodes=2, cpu=1000)
    # 3 members @650m, quorum 2: one fits per node -> 2 place, 1 straggles
    for i in range(3):
        api.create("Pod", _gang_pod(f"g-{i}", "job-q", 2, cpu=650))
    totals = sched.run_until_drained()
    assert totals["bound"] == 2
    pods, _ = api.list("Pod")
    assert sum(1 for p in pods if p.node_name) == 2
    assert "job-q" in sched._gang_degraded
    # capacity frees up -> the straggler schedules SOLO (no quorum gate)
    bound = [p for p in pods if p.node_name]
    api.delete("Pod", bound[0].namespace, bound[0].name)
    for _ in range(200):
        sched.schedule_round()
        if all(p.node_name for p in api.list("Pod")[0]):
            break
        sched._now()  # real clock: waits out the 1s backoff
        import time as _t
        _t.sleep(0.05)
    assert sum(1 for p in api.list("Pod")[0] if p.node_name) == 2


def test_gangs_mix_with_plain_pods():
    api, sched = _rig(n_nodes=4, cpu=4000)
    for i in range(4):
        api.create("Pod", _gang_pod(f"g-{i}", "job-m", 4))
    for i in range(8):
        api.create("Pod", make_pod(f"plain-{i}", cpu=100))
    totals = sched.run_until_drained()
    assert totals["bound"] == 12


def test_gang_bench_profile_places_feasible_gangs_only():
    """The gang storm profile: every feasible gang fully binds, every
    infeasible gang (the ~1/16 with an impossible member) fully stays
    pending — atomicity at storm scale."""
    api = ApiServerLite()
    for i in range(50):
        api.create("Node", make_node(f"node-{i:03d}", cpu=16_000,
                                     memory=64 * Gi))
    pods = gang_pods(32 * 8)  # 32 gangs of 8; gangs 15 and 31 infeasible
    for p in pods:
        api.create("Pod", p)
    sched = Scheduler(api, record_events=False)
    sched.start()
    totals = sched.run_until_drained()
    by_gang = {}
    for p in api.list("Pod")[0]:
        by_gang.setdefault(
            p.annotations[GANG_NAME_ANNOTATION], []).append(bool(p.node_name))
    assert len(by_gang) == 32
    for gname, bound_flags in by_gang.items():
        assert len(set(bound_flags)) == 1, f"{gname} partially bound"
    placed = sum(1 for flags in by_gang.values() if flags[0])
    assert placed == 30  # all but the two infeasible gangs
    assert totals["bound"] == 30 * 8


def test_gang_park_timeout_fires_on_empty_rounds():
    """A below-quorum gang with NO new pod arrivals must still hit the
    parked-too-long sweep (the sweep runs before the empty-round early
    return): FailedScheduling surfaces and members re-queue with backoff."""
    t = [1000.0]
    api = ApiServerLite()
    api.create("Node", make_node("n1", cpu=4000, memory=8 * Gi))
    sched = Scheduler(api, now=lambda: t[0])
    sched.start()
    api.create("Pod", _gang_pod("g-a", "g", 3))
    sched.schedule_round()           # parks below quorum
    assert sched._gang_waiting.get("g")
    t[0] += sched.GANG_WAIT_TIMEOUT_S + 1
    sched.schedule_round()           # EMPTY round: nothing in the queue
    assert not sched._gang_waiting.get("g")
    evs = [e for e in sched.events
           if e.reason == "FailedScheduling" and "below quorum" in e.message]
    assert evs


def test_gang_completing_in_timeout_round_schedules():
    """A gang whose final quorum member arrives in the same round the park
    timeout expires must schedule, not be swept into backoff."""
    t = [1000.0]
    api = ApiServerLite()
    for i in range(3):
        api.create("Node", make_node(f"n{i}", cpu=4000, memory=8 * Gi))
    sched = Scheduler(api, now=lambda: t[0])
    sched.start()
    api.create("Pod", _gang_pod("g-a", "g", 2))
    sched.schedule_round()           # parks 1/2
    assert sched._gang_waiting.get("g")
    t[0] += sched.GANG_WAIT_TIMEOUT_S + 1
    api.create("Pod", _gang_pod("g-b", "g", 2))
    sched.schedule_round()           # completion + timeout in one round
    bound = [p for p in api.list("Pod")[0] if p.node_name]
    assert len(bound) == 2, [p.name for p in bound]
    assert not any(e.reason == "FailedScheduling" and "below quorum"
                   in e.message for e in sched.events)


def test_gang_pipelined_vs_classic_flush_ab():
    """ISSUE 5: a gang storm drained with gangs riding the pipelined wave
    path (the classic flush-every-gang arm of this A/B is gone with the
    routing it measured). The gang CONTRACT holds: the feasible gangs
    fully bind, zero partial gangs, zero residue for the losers — and
    the gangs really dispatch through waves."""
    from kubernetes_tpu.utils.trace import COUNTERS

    api = ApiServerLite()
    for i in range(50):
        api.create("Node", make_node(f"node-{i:03d}", cpu=16_000,
                                     memory=64 * Gi))
    for p in gang_pods(32 * 8):  # gangs 15 and 31 infeasible
        api.create("Pod", p)
    sched = Scheduler(api, record_events=False)
    sched.start()
    COUNTERS.reset()
    totals = sched.run_until_drained(max_batch=64)
    snap = COUNTERS.snapshot()
    by_gang = {}
    for p in api.list("Pod")[0]:
        by_gang.setdefault(p.annotations[GANG_NAME_ANNOTATION],
                           []).append(bool(p.node_name))
    for gname, flags in by_gang.items():
        assert len(set(flags)) == 1, f"{gname} partially bound"
    assert {g for g, f in by_gang.items() if f[0]} == \
        {f"job-{g:04d}" for g in range(32) if g % 16 != 15}
    assert totals["bound"] == 30 * 8
    assert snap.get("engine.gang_wave_dispatch", (0, 0))[0] >= 30, snap
    # zero residue for the infeasible gangs: assumed capacity all released
    used = sum(i.requested.milli_cpu
               for i in sched.cache.node_infos().values())
    assert used == 30 * 8 * 100, used


def test_gang_quorum_unreachable_on_waves_takes_classic_fallback():
    """The one chunk-shape flush corner left: a gang whose quorum is
    unreachable from its wave-eligible members (every member here carries
    more host ports than a wave row encodes, so all ride as host-check
    rows) would roll back on every re-dispatch, so its chunk flushes to
    the classic round, which still places gangs all-or-nothing."""
    from kubernetes_tpu.api.types import ContainerPort
    from kubernetes_tpu.utils.trace import COUNTERS

    def ports_member(name, gang, quorum):
        p = _gang_pod(name, gang, quorum)
        p.containers[0].ports = [ContainerPort(host_port=9000 + i)
                                 for i in range(10)]
        return p

    api, sched = _rig(n_nodes=6, cpu=4000)
    for i in range(4):
        api.create("Pod", ports_member(f"fits-{i}", "fits", 4))
    for i in range(8):  # the same host ports: one member per node at most
        api.create("Pod", ports_member(f"wide-{i}", "wide", 8))
    COUNTERS.reset()
    sched.run_until_drained()
    snap = COUNTERS.snapshot()
    assert snap.get("engine.wave_flush_gang_host", (0, 0))[0] >= 1, snap
    assert snap.get("stream.chunk_flush", (0, 0))[0] >= 1, snap
    bound = {p.name for p in api.list("Pod")[0] if p.node_name}
    assert bound == {f"fits-{i}" for i in range(4)}, sorted(bound)


def test_gang_pipelined_overlap_ab_bit_identical():
    """ISSUE 5 acceptance: the gang-bearing pipelined drain with overlap
    forced off (sequential debug mode) is BIT-IDENTICAL — the gang fence,
    not timing, decides every commit and rollback."""
    def drain(overlap):
        api = ApiServerLite()
        for i in range(8):
            api.create("Node", make_node(f"n{i}", cpu=2000, memory=8 * Gi))
        for g in range(4):
            for m in range(4):
                api.create("Pod", _gang_pod(f"g{g}-{m}", f"job-{g}", 4,
                                            cpu=450))
        for i in range(6):
            api.create("Pod", make_pod(f"plain-{i}", cpu=300,
                                       memory=64 * Mi))
        sched = Scheduler(api, record_events=False)
        sched.start()
        sched.run_until_drained(max_batch=5, overlap=overlap)
        return {p.name: (p.node_name or None) for p in api.list("Pod")[0]}

    assert drain(True) == drain(False)


def test_gang_straggler_released_when_quorum_commits_in_flight():
    """A straggler that pops while its gang's quorum is still IN FLIGHT is
    gated before the commit lands, so it parks below quorum; the harvest
    must release it to schedule solo as soon as the gang commits — not
    strand it until the 60s parked-gang sweep (the classic round marks
    degraded synchronously and never hits this window)."""
    api = ApiServerLite()
    for i in range(4):
        api.create("Node", make_node(f"n{i}", cpu=4000, memory=8 * Gi))
    for i in range(2):           # the quorum pair pops as chunk 1
        api.create("Pod", _gang_pod(f"q-{i}", "job-s", 2))
    api.create("Pod", _gang_pod("q-late", "job-s", 2))  # chunk 2, in-flight
    sched = Scheduler(api, record_events=False)
    sched.start()
    totals = sched.run_until_drained(max_batch=2)
    assert totals["bound"] == 3, totals
    assert "job-s" in sched._gang_degraded
    assert not sched._gang_waiting.get("job-s")
    assert all(p.node_name for p in api.list("Pod")[0])


def test_gang_fence_rollback_is_atomic_with_zero_residue():
    """Forced fence rollback (ISSUE 5): gang B's wave is dispatched BLIND
    to gang A's still-unharvested commits on the only node; at harvest,
    B's members fail the capacity re-validation, so the WHOLE gang rolls
    back atomically — nothing of B is ever assumed, zero residue — and
    requeues with backoff. A binds untouched."""
    from kubernetes_tpu.utils.trace import COUNTERS

    api = ApiServerLite()
    api.create("Node", make_node("n0", cpu=2000, memory=8 * Gi))
    for i in range(2):
        api.create("Pod", _gang_pod(f"a-{i}", "job-a", 2, cpu=1000))
    for i in range(2):
        api.create("Pod", _gang_pod(f"b-{i}", "job-b", 2, cpu=1000))
    sched = Scheduler(api, record_events=True)
    sched.start()
    COUNTERS.reset()
    totals = sched.run_until_drained(max_batch=2)
    snap = COUNTERS.snapshot()
    assert totals["bound"] == 2, totals
    assert totals["gang_requeued"] >= 2, totals  # B rolled back as a unit
    assert snap.get("engine.gang_fence_rollbacks", (0, 0))[0] >= 1, snap
    pods = api.list("Pod")[0]
    by_gang = {}
    for p in pods:
        by_gang.setdefault(p.annotations[GANG_NAME_ANNOTATION],
                           []).append(bool(p.node_name))
    assert len(set(by_gang["job-a"])) == 1  # never partial
    assert len(set(by_gang["job-b"])) == 1
    bound_gangs = [g for g, f in by_gang.items() if f[0]]
    assert len(bound_gangs) == 1, by_gang  # exactly one gang won the node
    # zero residue: only the winner's capacity is accounted
    info = sched.cache.node_infos()["n0"]
    assert info.requested.milli_cpu == 2000, info.requested
    assert len(info.pods) == 2
    evs = [e for e in sched.events
           if e.reason == "FailedScheduling" and "wave fence" in e.message]
    assert evs, [e.message for e in sched.events]


def test_gang_fuzz_all_or_nothing_invariant():
    """Randomized gang mixes; the hard invariant per trial: every gang is
    either FULLY placed (>= quorum members bound) or left with ZERO
    residue (no member bound, no assumed capacity leaked) — the partial-
    placement failure mode gang scheduling exists to prevent."""
    import numpy as np

    rng = np.random.default_rng(1234)
    for trial in range(8):
        n_nodes = int(rng.integers(2, 6))
        node_cpu = 1000
        api = ApiServerLite()
        for i in range(n_nodes):
            api.create("Node", make_node(f"n{i}", cpu=node_cpu,
                                         memory=8 * Gi))
        sched = Scheduler(api)
        sched.start()
        gangs = {}
        for g in range(int(rng.integers(1, 4))):
            size = int(rng.integers(1, 5))
            quorum = int(rng.integers(1, size + 1))
            cpu = int(rng.integers(100, 700))
            gangs[f"g{g}"] = (size, quorum, cpu)
            for m in range(size):
                api.create("Pod", _gang_pod(f"g{g}-{m}", f"g{g}", quorum,
                                            cpu=cpu))
        # a few plain pods competing for the same capacity
        for p in range(int(rng.integers(0, 4))):
            api.create("Pod", make_pod(f"plain-{p}",
                                       cpu=int(rng.integers(50, 400)),
                                       memory=64 * Mi))
        sched.run_until_drained(max_rounds=50)
        pods = api.list("Pod")[0]
        for gname, (size, quorum, cpu) in gangs.items():
            bound = [p for p in pods
                     if p.name.startswith(gname + "-") and p.node_name]
            assert len(bound) == 0 or len(bound) >= quorum, \
                f"trial {trial}: gang {gname} partially placed " \
                f"({len(bound)}/{size}, quorum {quorum})"
        # no node over capacity (assumed-capacity leak check)
        per_node = {}
        for p in pods:
            if p.node_name:
                per_node[p.node_name] = per_node.get(p.node_name, 0) \
                    + p.resource_request().milli_cpu
        for node_name, used in per_node.items():
            assert used <= node_cpu, \
                f"trial {trial}: {node_name} over capacity ({used})"
