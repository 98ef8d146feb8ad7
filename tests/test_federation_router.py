"""Federation tier (ISSUE 20): front-door router + cells end to end.

Pins the four acceptance seams:

  - ROUTER DETERMINISM: a frozen [C, M] cell-aggregate tensor produces
    bit-identical cell choices run to run AND across the device/host
    scoring twins (routing is a pure function of the tensor — the
    argmax tie-break is first-occurrence, never hash order);
  - GANGS ROUTE WHOLE-CELL: every member of a gang lands in ONE cell's
    store and binds there (the quorum fence never spans a cell
    boundary), audited from store truth;
  - BROWNOUT SPILLOVER EXACTLY-ONCE: a cell going NotReady drains its
    pending pods through the spillover path to survivors; the event-log
    audit holds — each pod key has bind events in AT MOST one cell,
    ever, and per-cell duplicate-bind audits stay hard zero;
  - AGGREGATE ORACLE A/B: the incrementally-folded CELL_AGG column
    equals the aggregate rebuilt from a full store walk on every shared
    field (the RELIST hydration path and the delta path can never
    disagree about a cell's capacity picture).

Plus the satellite seams: brownout-schedule determinism and the
unroutable backlog.
"""

from __future__ import annotations

import numpy as np
import pytest

from kubernetes_tpu.api.types import make_pod
from kubernetes_tpu.engine.gang import (
    GANG_MIN_AVAILABLE_ANNOTATION,
    GANG_NAME_ANNOTATION,
)
from kubernetes_tpu.engine.scheduler import Scheduler
from kubernetes_tpu.federation.aggregate import (
    CellAggregate,
    aggregate_from_lists,
)
from kubernetes_tpu.federation.cell import CellService
from kubernetes_tpu.federation.router import FederationRouter, LocalCell
from kubernetes_tpu.models.hollow import hollow_nodes
from kubernetes_tpu.parallel.multiproc import audit_duplicate_binds
from kubernetes_tpu.server.apiserver_lite import ApiServerLite


def _pod(name, cpu=100, mem=64 << 20, **kw):
    return make_pod(name, cpu=cpu, memory=mem, **kw)


def _gang(name, members, cpu=50):
    out = []
    for m in range(members):
        p = _pod(f"{name}-{m}", cpu=cpu, mem=32 << 20)
        p.annotations[GANG_NAME_ANNOTATION] = name
        p.annotations[GANG_MIN_AVAILABLE_ANNOTATION] = str(members)
        out.append(p)
    return out


class _Cell:
    """One in-process cell: store + engine + CellService, pumped from
    the test's own thread (deterministic — no pump thread)."""

    def __init__(self, name, n_nodes=16, zones=4):
        self.name = name
        self.api = ApiServerLite()
        for i, n in enumerate(hollow_nodes(n_nodes)):
            n.labels["zone"] = f"{name}-z{i % zones}"
            self.api.create("Node", n)
        self.sched = Scheduler(self.api, record_events=False)
        self.svc = CellService(self.api, cell=name)
        self.sched.spill_handler = self.svc.spill
        self.sched.spill_after_attempts = 2
        self.sched.start()
        self.loop = self.sched.stream(budget_s=0.05, min_quantum=8,
                                      max_quantum=128)
        self.handle = LocalCell(name, self.svc)

    def pump(self, steps=8):
        for _ in range(steps):
            self.loop.step(wait=0.001)

    def bound_keys(self):
        pods, _rv = self.api.list("Pod")
        return {p.key(): p.node_name for p in pods if p.node_name}

    def close(self):
        self.loop.close()


@pytest.fixture
def two_cells():
    cells = [_Cell("alpha", n_nodes=16), _Cell("beta", n_nodes=16)]
    router = FederationRouter([c.handle for c in cells])
    router.hydrate()
    yield cells, router
    for c in cells:
        c.close()


def _drain(cells, router, rounds=60):
    for _ in range(rounds):
        for c in cells:
            c.pump(4)
        router.spill_pump()
        if sum(a.pending for a in router.aggs.values()) == 0 \
                and not router.backlog:
            return
    raise AssertionError(
        f"fleet did not drain: pending="
        f"{ {n: a.pending for n, a in router.aggs.items()} } "
        f"backlog={len(router.backlog)}")


# ------------------------------------------------------------ determinism


def _frozen_router(use_device):
    """Router over dummy handles with a HAND-FROZEN aggregate tensor —
    route() reads only the columns, so no cell machinery is needed."""

    class _Dummy:
        def __init__(self, name):
            self.name = name

        def close(self):
            pass

    router = FederationRouter([_Dummy(n) for n in ("c0", "c1", "c2")],
                              use_device=use_device)
    shapes = {
        "c0": dict(nodes_total=10, nodes_ready=10, cpu_alloc_m=40_000,
                   mem_alloc_mib=40_960, cpu_used_m=35_000,
                   mem_used_mib=4_096, pending=0,
                   domains={"z0": 5, "z1": 5}),
        "c1": dict(nodes_total=10, nodes_ready=10, cpu_alloc_m=40_000,
                   mem_alloc_mib=40_960, cpu_used_m=8_000,
                   mem_used_mib=4_096, pending=12,
                   domains={"z1": 10}),
        "c2": dict(nodes_total=10, nodes_ready=10, cpu_alloc_m=40_000,
                   mem_alloc_mib=40_960, cpu_used_m=8_000,
                   mem_used_mib=4_096, pending=0, domains={"z2": 10}),
    }
    for name, kw in shapes.items():
        agg = CellAggregate(cell=name, ready=True, **kw)
        router.aggs[name] = agg
    return router


def _mixed_batch():
    pods = [_pod(f"d{i}", cpu=100 + 50 * (i % 3)) for i in range(40)]
    pods += [_pod("z1-pin", cpu=100, node_selector={"zone": "z1"}),
             _pod("z2-pin", cpu=100, node_selector={"zone": "z2"})]
    pods += _gang("dg", 4)
    return pods


def test_frozen_tensor_routes_bit_identical_run_to_run():
    a1, l1 = _frozen_router(False).route(_mixed_batch())
    a2, l2 = _frozen_router(False).route(_mixed_batch())
    as_keys = lambda a: {c: [p.key() for p in ps]  # noqa: E731
                         for c, ps in a.items()}
    assert as_keys(a1) == as_keys(a2)
    assert [p.key() for p in l1] == [p.key() for p in l2]
    # the frozen shape exercises every verdict class: the loaded c0
    # loses ties, zone pins land on their only domain, someone routes
    assert a1, "nothing routed"
    z1_cell = [c for c, ps in a1.items()
               if any(p.name == "z1-pin" for p in ps)]
    assert z1_cell and z1_cell[0] in ("c0", "c1")
    z2_cell = [c for c, ps in a1.items()
               if any(p.name == "z2-pin" for p in ps)]
    assert z2_cell == ["c2"]


def test_device_and_host_twins_route_identically():
    """use_device=True pads C to the bucket ladder and scores through
    the jitted kernel; the numpy twin must produce the SAME choices —
    the routing policy is latency, never semantics."""
    ah, _lh = _frozen_router(False).route(_mixed_batch())
    ad, _ld = _frozen_router(True).route(_mixed_batch())
    assert {c: [p.key() for p in ps] for c, ps in ah.items()} \
        == {c: [p.key() for p in ps] for c, ps in ad.items()}


def test_route_scores_twins_bitwise_equal():
    from kubernetes_tpu.ops.federation import (
        route_scores,
        route_scores_host,
    )
    rng = np.random.RandomState(7)
    C, M = 33, 5
    args = (rng.randint(0, 2000, C).astype(np.int32),
            rng.randint(0, 2000, C).astype(np.int32),
            rng.randint(-500, 40_000, M).astype(np.int32),
            rng.randint(-500, 40_000, M).astype(np.int32),
            rng.randint(1, 80_000, M).astype(np.int32),
            rng.randint(1, 80_000, M).astype(np.int32),
            rng.uniform(0, 3, M).astype(np.float32),
            rng.rand(M) > 0.3,
            rng.rand(C, M) > 0.2)
    dev = np.asarray(route_scores(*args))
    host = route_scores_host(*args)
    assert np.array_equal(dev, host)


# ------------------------------------------------------------------ gangs


def test_gang_routes_whole_cell_and_binds_there(two_cells):
    cells, router = two_cells
    gang = _gang("tg", 5)
    filler = [_pod(f"f{i}") for i in range(10)]
    router.admit(filler + gang)
    assert router.counters_snapshot()["routed_gangs"] == 1
    _drain(cells, router)
    homes = set()
    for c in cells:
        bound = c.bound_keys()
        members = [k for k in bound if k.startswith("default/tg-")]
        if members:
            homes.add(c.name)
            assert len(members) == 5, \
                f"gang split inside {c.name}: {members}"
    assert len(homes) == 1, f"gang spanned cells: {homes}"


# --------------------------------------------------- brownout exactly-once


def _bind_event_cells(cells):
    """Event-log audit surface: pod key -> set of cells whose store log
    EVER carried a bind event (Pod MODIFIED naming a node) for it."""
    seen = {}
    for c in cells:
        with c.api._lock:
            log = list(c.api._log)
        for ev in log:
            if ev.kind != "Pod" or ev.type != "MODIFIED":
                continue
            node = getattr(ev.obj, "node_name", "")
            if node:
                seen.setdefault(ev.obj.key(), set()).add(c.name)
    return seen


def test_brownout_spillover_is_exactly_once(two_cells):
    cells, router = two_cells
    alpha, beta = cells
    pods = [_pod(f"b{i}") for i in range(30)]
    router.admit(pods)
    # only beta's engine runs before the fault: whatever landed on
    # alpha is still pending there when it browns out
    beta.pump(4)
    evacuated = router.brownout("alpha")
    assert not router.aggs["alpha"].ready
    _drain(cells, router)
    router.recover("alpha")
    assert router.aggs["alpha"].ready
    # store truth: every pod bound exactly once, somewhere
    all_bound = {}
    for c in cells:
        for k, node in c.bound_keys().items():
            assert k not in all_bound, \
                f"{k} bound in two cells: {all_bound[k]} and {c.name}"
            all_bound[k] = c.name
        assert audit_duplicate_binds(c.api) == 0
    assert len(all_bound) == 30
    # event-log audit: one bound cell per pod EVER — an evacuated pod
    # left alpha's store before beta could bind it, so no pod key has
    # bind events in both logs
    for key, homes in _bind_event_cells(cells).items():
        assert len(homes) == 1, f"{key} has bind events in {homes}"
    if evacuated:
        assert router.counters_snapshot()["evacuated_moved"] == evacuated


def test_admit_wire_fault_replays_same_idem_key(two_cells):
    """An ambiguous ADMIT fault (reply lost AFTER the cell committed)
    replays the SAME idempotency key; the cell's idem cache converges
    the retry to the recorded answer — no pod double-enters."""
    cells, router = two_cells
    alpha = cells[0]
    real_admit = alpha.handle.admit
    state = {"fired": False}

    def flaky_admit(idem_key, pods):
        out = real_admit(idem_key, pods)
        if not state["fired"]:
            state["fired"] = True
            raise ConnectionError("reply lost after commit")
        return out

    alpha.handle.admit = flaky_admit
    router.admit([_pod(f"r{i}") for i in range(8)])
    assert state["fired"]
    pods, _rv = alpha.api.list("Pod")
    beta_pods, _rv = cells[1].api.list("Pod")
    names = sorted(p.name for p in pods) + sorted(
        p.name for p in beta_pods)
    assert names == sorted(f"r{i}" for i in range(8))
    # the replay hit the idem cache, not the store
    assert alpha.svc.counters_snapshot()["admit_replays"] == 0


# ---------------------------------------------------------- oracle A/B


def test_folded_aggregate_equals_store_oracle(two_cells):
    cells, router = two_cells
    alpha = cells[0]
    router.admit([_pod(f"o{i}") for i in range(20)])
    alpha.pump(6)
    cells[1].pump(6)
    d, _spilled = alpha.handle.cell_agg()
    folded = CellAggregate.from_dict(d)
    nodes, _rv = alpha.api.list("Node")
    pods, _rv = alpha.api.list("Pod")
    oracle = aggregate_from_lists(nodes, pods, cell="alpha")
    for key in ("nodes_total", "nodes_ready", "cpu_alloc_m",
                "mem_alloc_mib", "cpu_used_m", "mem_used_mib",
                "pending", "bound_total", "domains"):
        assert getattr(folded, key) == getattr(oracle, key), \
            f"fold/oracle diverge on {key}"
    # and the RELIST hydration path agrees on the capacity picture
    router.hydrate()
    hyd = router.aggs["alpha"]
    for key in ("nodes_total", "nodes_ready", "cpu_alloc_m",
                "mem_alloc_mib", "cpu_used_m", "mem_used_mib",
                "domains"):
        assert getattr(hyd, key) == getattr(oracle, key), \
            f"hydrate/oracle diverge on {key}"


def test_compacted_log_rebuild_matches_oracle():
    """A watch log compacted past the fold cursor forces the store-walk
    rebuild — the rebuilt column must equal the oracle too."""
    api = ApiServerLite(max_log=64)
    for i, n in enumerate(hollow_nodes(8)):
        n.labels["zone"] = f"g-z{i % 2}"
        api.create("Node", n)
    svc = CellService(api, cell="gamma")
    d, _sp = svc.cell_aggregate()
    assert d["nodes_total"] == 8
    # blow past the 64-event log bound so the cursor is too old
    for i in range(200):
        api.create("Pod", _pod(f"c{i}"))
    d, _sp = svc.cell_aggregate()
    assert svc.counters_snapshot()["agg_rebuilds"] == 1
    nodes, _rv = api.list("Node")
    pods, _rv = api.list("Pod")
    oracle = aggregate_from_lists(nodes, pods, cell="gamma")
    assert d["pending"] == oracle.pending == 200
    assert d["cpu_used_m"] == oracle.cpu_used_m


# ------------------------------------------------------------- satellites


def test_brownout_schedule_deterministic_and_bounded():
    from kubernetes_tpu.testing.churn import make_brownout_schedule
    a = make_brownout_schedule(["c0", "c1", "c2"], 10.0, down_s=2.0,
                               count=3, seed=42)
    b = make_brownout_schedule(["c0", "c1", "c2"], 10.0, down_s=2.0,
                               count=3, seed=42)
    assert a == b
    assert a != make_brownout_schedule(["c0", "c1", "c2"], 10.0,
                                       down_s=2.0, count=3, seed=43)
    busy = {}
    for op in a:
        assert 1.0 <= op.t <= 9.0
        assert busy.get(op.cell, -1.0) < op.t, "same-cell overlap"
        busy[op.cell] = op.t + op.down_s


def test_unroutable_pods_backlog_then_admit_after_capacity():
    """A pod no ready cell fits goes to the router backlog (counted
    unroutable), and pump_backlog admits it once capacity appears."""

    class _Dummy:
        def __init__(self, name):
            self.name = name
            self.batches = []

        def admit(self, idem_key, pods):
            self.batches.append(list(pods))
            return len(pods), 0

        def close(self):
            pass

    cell = _Dummy("solo")
    router = FederationRouter([cell])
    agg = CellAggregate(cell="solo", ready=True, nodes_total=2,
                        nodes_ready=2, cpu_alloc_m=1000,
                        mem_alloc_mib=1024, cpu_used_m=900,
                        mem_used_mib=0)
    router.aggs["solo"] = agg
    router.admit([_pod("big", cpu=500, mem=64 << 20)])
    assert len(router.backlog) == 1
    assert router.counters_snapshot()["unroutable"] == 1
    assert cell.batches == []
    with router._lock:
        router.aggs["solo"].cpu_used_m = 100
    assert router.pump_backlog() == 1
    assert [p.name for b in cell.batches for p in b] == ["big"]
