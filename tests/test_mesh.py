"""Device-mesh sharding tests (parallel/mesh.py).

The multi-chip story: node-indexed arrays sharded over a 1-D "nodes" mesh,
pod arrays replicated, XLA inserting the collectives (SURVEY.md §5.7 — the
tensor analog of workqueue.Parallelize(16, nodes) at
generic_scheduler.go:204,352). These tests run both engines under an
8-virtual-CPU-device mesh (tests/conftest.py) and assert bit-identical
placements vs the unsharded single-device run — sharding must be a pure
layout choice, never a semantics change.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from kubernetes_tpu.engine import waves
from kubernetes_tpu.engine.batch import node_state, place_batch
from kubernetes_tpu.ops import predicates as preds
from kubernetes_tpu.ops import priorities as prio
from kubernetes_tpu.parallel.mesh import (
    NODE_AXIS,
    make_mesh,
    replicate,
    shard_nodes,
)
from kubernetes_tpu.state.classes import ClassBatch
from kubernetes_tpu.state.node_info import node_info_map
from kubernetes_tpu.state.snapshot import ClusterSnapshot, PodBatch
from tests.helpers import Gi, Mi, random_nodes, random_pod

N_DEV = 8

PRIO = (("LeastRequestedPriority", 1), ("BalancedResourceAllocation", 1),
        ("TaintTolerationPriority", 1))


def _cluster(seed, n_nodes=24, n_pods=48):
    rng = random.Random(seed)
    nodes = random_nodes(rng, n_nodes)
    names = [n.name for n in nodes]
    pods = [random_pod(rng, i, names) for i in range(n_pods)]
    infos = node_info_map(nodes, [])
    # node axis padded to a multiple of the mesh size so shards are even
    snap = ClusterSnapshot(node_pad=N_DEV)
    snap.refresh(infos)
    return snap, pods


def test_make_mesh_and_shard_layout():
    mesh = make_mesh(N_DEV)
    assert mesh.devices.shape == (N_DEV,)
    snap, _ = _cluster(0)
    nodes = preds.node_arrays(snap)
    sharded = shard_nodes(nodes, mesh)
    n = int(nodes["alloc"].shape[0])
    assert n % N_DEV == 0
    # node-sharded arrays: each device holds exactly N/8 rows
    shards = sharded["alloc"].addressable_shards
    assert len(shards) == N_DEV
    assert all(s.data.shape[0] == n // N_DEV for s in shards)
    # replicated arrays: every device holds the full array
    rep = replicate({"x": jnp.arange(16)}, mesh)["x"]
    assert all(s.data.shape[0] == 16 for s in rep.addressable_shards)


@pytest.mark.parametrize("seed", [0, 2])
def test_fits_kernel_parity_under_mesh(seed):
    """static predicate matrix must be bit-identical sharded vs not."""
    snap, pods = _cluster(seed)
    batch = PodBatch(pods, snap)
    parr = preds.pod_arrays(batch)
    narr = preds.node_arrays(snap)
    base = np.asarray(preds.fits(parr, narr))

    mesh = make_mesh(N_DEV)
    with mesh:
        got = preds.fits(replicate(parr, mesh), shard_nodes(narr, mesh))
        got.block_until_ready()
    np.testing.assert_array_equal(np.asarray(got), base)
    # output inherits the node sharding on its node axis (axis 1)
    assert len({s.device for s in got.addressable_shards}) == N_DEV


@pytest.mark.parametrize("seed", [0, 1, 4])
def test_strict_engine_parity_under_mesh(seed):
    """place_batch (the bit-exact sequential scan) under an 8-device mesh
    must reproduce the single-device placement sequence exactly."""
    snap, pods = _cluster(seed)
    batch = PodBatch(pods, snap)
    parr = preds.pod_arrays(batch)
    narr = preds.node_arrays(snap)
    sel0, fc0, st0, rr0 = place_batch(parr, narr, node_state(narr),
                                      jnp.uint32(0), PRIO)
    base_sel, base_fc = np.asarray(sel0), np.asarray(fc0)

    mesh = make_mesh(N_DEV)
    with mesh:
        nsh = shard_nodes(narr, mesh)
        psh = replicate(parr, mesh)
        sel, fc, st, rr = place_batch(psh, nsh, node_state(nsh),
                                      jnp.uint32(0), PRIO)
        sel.block_until_ready()
    np.testing.assert_array_equal(np.asarray(sel), base_sel)
    np.testing.assert_array_equal(np.asarray(fc), base_fc)
    assert int(rr) == int(rr0)
    np.testing.assert_array_equal(np.asarray(st.requested),
                                  np.asarray(st0.requested))


@pytest.mark.parametrize("seed", [0, 3])
def test_wave_engine_parity_under_mesh(seed):
    """place_waves (throughput mode) sharded vs unsharded: same placements,
    same final capacity state."""
    snap, pods = _cluster(seed, n_pods=64)
    # wave path consumes class-level arrays
    cbatch = ClassBatch(pods, snap)
    cls = preds.pod_arrays(cbatch.reps_batch)
    narr = preds.node_arrays(snap)
    pc = cbatch.pod_class
    sel0, fc0, st0, rr0 = waves.place_waves(cls, narr, node_state(narr),
                                            pc, 0, PRIO)

    mesh = make_mesh(N_DEV)
    with mesh:
        nsh = shard_nodes(narr, mesh)
        csh = replicate(cls, mesh)
        sel, fc, st, rr = waves.place_waves(csh, nsh, node_state(nsh),
                                            pc, 0, PRIO)
    np.testing.assert_array_equal(sel, sel0)
    np.testing.assert_array_equal(fc, fc0)
    assert rr == rr0
    np.testing.assert_array_equal(np.asarray(st.pod_count),
                                  np.asarray(st0.pod_count))


def test_dryrun_multichip_impl_runs_in_process():
    """The driver-facing dryrun body itself (CPU backend is already forced
    by conftest, so the impl can run in-process here). Small explicit shape
    — the driver run uses the large default (2k nodes / 10k pods), which is
    minutes of CPU scan and belongs there, not in the suite."""
    import __graft_entry__ as g
    g._dryrun_multichip_impl(N_DEV, n_nodes=512, n_pending=288)


# ---------------------------------------------------------------- affinity


def _affinity_cluster(seed, n_nodes=24, n_existing=12, n_pending=32):
    """Cluster where the affinity machinery is genuinely exercised: existing
    guard pods with required anti-affinity, pending pods mixing required/
    preferred (anti-)affinity, and service workloads for spreading (reuses
    the fuzz generators of tests/test_affinity_fuzz.py)."""
    from tests.test_affinity_fuzz import _build_cluster, _pending
    rng = random.Random(seed)
    nodes, existing, workloads = _build_cluster(rng, n_nodes=n_nodes,
                                                n_existing=n_existing)
    pending = _pending(rng, n_pending)
    return nodes, existing, workloads, pending


def _affinity_kernel_inputs(nodes, existing, workloads, pending):
    """The exact array-construction path of SchedulingEngine.schedule."""
    from kubernetes_tpu.ops.affinity import (
        AffinityData,
        collect_pod_pairs,
        intern_topology_pairs,
    )
    from kubernetes_tpu.ops.predicates import bucket, pod_arrays_padded

    infos = node_info_map(nodes, existing)
    snap = ClusterSnapshot(node_pad=N_DEV)
    snap.refresh(infos)
    all_pairs, aff_pairs = collect_pod_pairs(infos)
    intern_topology_pairs(snap, pending, aff_pairs)
    cbatch = ClassBatch(pending, snap)
    c_pad = bucket(cbatch.num_classes + 1)
    adata = AffinityData(cbatch.reps, snap, all_pairs, aff_pairs,
                         workloads, 1, c_pad=c_pad)
    cls_arr = pod_arrays_padded(cbatch.reps_batch, c_pad)
    pc = np.full(preds.bucket(len(pending)), cbatch.num_classes,
                 dtype=np.int32)
    pc[: len(pending)] = cbatch.pod_class
    narr = preds.node_arrays(snap)
    return cls_arr, pc, narr, adata


@pytest.mark.parametrize("seed", [0, 5])
def test_strict_engine_affinity_parity_under_mesh(seed):
    """The flagship kernel — the full strict scan WITH the inter-pod
    affinity + spread machinery on — must be bit-identical sharded vs
    unsharded (VERDICT r3 #2: the [C,S,L]x[N,L] einsums' node axis is
    exactly what the mesh splits)."""
    from kubernetes_tpu.engine.batch import gather_place_batch
    from kubernetes_tpu.parallel.mesh import shard_affinity

    nodes, existing, workloads, pending = _affinity_cluster(seed)
    cls_arr, pc, narr, adata = _affinity_kernel_inputs(
        nodes, existing, workloads, pending)
    assert adata.fits_needed, "generator must exercise required affinity"
    assert adata.spread_needed or adata.prio_needed
    aff = adata.device_arrays()
    mode = (adata.fits_needed, adata.prio_needed, adata.spread_needed)
    sel0, fc0, st0, rr0 = gather_place_batch(
        cls_arr, jnp.asarray(pc), narr, node_state(narr),
        jnp.uint32(0), prio.DEFAULT_PRIORITIES, aff=aff, aff_mode=mode)
    base_sel, base_fc = np.asarray(sel0), np.asarray(fc0)
    assert (base_sel[: len(pending)] >= 0).any()

    mesh = make_mesh(N_DEV)
    with mesh:
        nsh = shard_nodes(narr, mesh)
        csh = replicate(cls_arr, mesh)
        ash = shard_affinity(aff, mesh)
        sel, fc, st, rr = gather_place_batch(
            csh, replicate({"pc": jnp.asarray(pc)}, mesh)["pc"], nsh,
            node_state(nsh), jnp.uint32(0), prio.DEFAULT_PRIORITIES,
            aff=ash, aff_mode=mode)
        sel.block_until_ready()
    np.testing.assert_array_equal(np.asarray(sel), base_sel)
    np.testing.assert_array_equal(np.asarray(fc), base_fc)
    assert int(rr) == int(rr0)
    np.testing.assert_array_equal(np.asarray(st.requested),
                                  np.asarray(st0.requested))
    np.testing.assert_array_equal(np.asarray(st.pod_count),
                                  np.asarray(st0.pod_count))


@pytest.mark.parametrize("seed", [1])
def test_frozen_affinity_scores_parity_under_mesh(seed):
    """Wave mode's batch-frozen spread/interpod score matrix [C,N] must be
    bit-identical sharded vs unsharded."""
    from kubernetes_tpu.engine.batch import node_state as mk_state
    from kubernetes_tpu.parallel.mesh import shard_affinity

    nodes, existing, workloads, pending = _affinity_cluster(seed)
    cls_arr, pc, narr, adata = _affinity_kernel_inputs(
        nodes, existing, workloads, pending)
    aff = adata.device_arrays()
    base = np.asarray(waves.frozen_affinity_scores(
        cls_arr, narr, mk_state(narr), aff, (2, 1)))
    mesh = make_mesh(N_DEV)
    with mesh:
        got = waves.frozen_affinity_scores(
            replicate(cls_arr, mesh), shard_nodes(narr, mesh),
            mk_state(shard_nodes(narr, mesh)), shard_affinity(aff, mesh),
            (2, 1))
        got.block_until_ready()
    np.testing.assert_array_equal(np.asarray(got), base)


# ------------------------------------------------- ISSUE 12: residency


def test_two_stage_tie_select_matches_global():
    """The winner-reduce contract: _ShardCol's two-stage tie selection
    (local rank + all-gathered [D, C] prefix + ownership-masked psum)
    must equal _GlobalCol's whole-axis tiemat lookup for every (class,
    draw) — including empty tie sets and ties straddling shard
    boundaries."""
    from jax.sharding import PartitionSpec as PS

    from kubernetes_tpu.engine.waves import _GlobalCol, _ShardCol
    from kubernetes_tpu.parallel.mesh import NODE_AXIS

    rng = np.random.default_rng(7)
    C, N, P_ = 5, 64, 40
    ties = rng.random((C, N)) < 0.2
    ties[3] = False                      # empty tie set
    ties[4, N - 1] = True                # tie on the last shard edge
    ties_j = jnp.asarray(ties)
    pod_class = jnp.asarray(rng.integers(0, C, P_).astype(np.int32))
    m = ties.sum(axis=1).astype(np.int32)
    draw = rng.integers(0, 1000, P_).astype(np.int32)
    kz = jnp.asarray(draw % np.maximum(m[np.asarray(pod_class)], 1))

    base = _GlobalCol(N).tie_select(ties_j, pod_class, kz)

    mesh = make_mesh(N_DEV)
    col = _ShardCol(NODE_AXIS, N, N // N_DEV)
    got = jax.shard_map(
        lambda t, pc, k: col.tie_select(t, pc, k),
        mesh=mesh, in_specs=(PS(None, NODE_AXIS), PS(), PS()),
        out_specs=PS(), check_vma=False)(ties_j, pod_class, kz)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_spmd_waves_loop_matches_global():
    """waves_loop(spmd_mesh=...) — the whole wave program under shard_map
    with the two-stage reduce — must produce the identical packed result
    and final NodeState as the single-program run (the tier-1 pin of the
    scale_sweep's bit-identity acceptance)."""
    snap, pods = _cluster(3, n_nodes=24, n_pods=48)
    cbatch = ClassBatch(pods, snap)
    cls = preds.pod_arrays(cbatch.reps_batch)
    narr = preds.node_arrays(snap)
    pc = jnp.asarray(cbatch.pod_class)
    ctr = jnp.uint32(0)
    packed0, st0 = waves.waves_loop(cls, narr, node_state(narr), pc, ctr,
                                    PRIO, 32)
    mesh = make_mesh(N_DEV)
    packed1, st1 = waves.waves_loop(cls, narr, node_state(narr), pc, ctr,
                                    PRIO, 32, spmd_mesh=mesh)
    np.testing.assert_array_equal(np.asarray(packed1), np.asarray(packed0))
    np.testing.assert_array_equal(np.asarray(st1.requested),
                                  np.asarray(st0.requested))
    np.testing.assert_array_equal(np.asarray(st1.pod_count),
                                  np.asarray(st0.pod_count))


def _mesh_sched(n_nodes, mesh):
    from kubernetes_tpu.engine.scheduler import Scheduler
    from kubernetes_tpu.models.hollow import hollow_nodes, load_cluster
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite

    api = ApiServerLite()
    load_cluster(api, hollow_nodes(n_nodes), [])
    s = Scheduler(api, record_events=False, mesh=mesh)
    s.start()
    return api, s


def test_resident_engine_partition_specs_and_identity():
    """Tier-1 mesh smoke (ISSUE 12): a tiny drain on the resident-mesh
    engine pins (a) the partition layout — node-axis device buffers
    sharded over all 8 devices, pod-side/class-side replicated — and
    (b) placements bit-identical to the unsharded engine."""
    from kubernetes_tpu.models.hollow import PROFILES

    def run(mesh):
        api, s = _mesh_sched(64, mesh)
        for p in PROFILES["density"](200):
            api.create("Pod", p)
        s.run_until_drained(max_batch=64)
        return api, s

    api0, _ = run(None)
    mesh = make_mesh(N_DEV)
    api1, s1 = run(mesh)
    p0 = {p.name: p.node_name for p in api0.list("Pod")[0]}
    p1 = {p.name: p.node_name for p in api1.list("Pod")[0]}
    assert p0 == p1 and all(p0.values())
    dev = s1.engine._device_nodes
    # node-axis arrays: one shard per device, rows split evenly
    for k in ("alloc", "requested", "labels", "pod_count"):
        shards = dev[k].addressable_shards
        assert len(shards) == N_DEV, k
        n = dev[k].shape[0]
        assert all(s.data.shape[0] == n // N_DEV for s in shards), k
    # pod-side tables stay replicated (pd_kind has no node axis)
    assert all(s.data.shape == dev["pd_kind"].shape
               for s in dev["pd_kind"].addressable_shards)
    # the sharded sync armed row tracking on the snapshot
    assert s1.engine.snapshot.dirty_rows is not None


def test_stream_sharded_equals_unsharded_frozen_trace():
    """ISSUE 12 satellite: the sharded==unsharded bit-identity A/B
    extended from the drain shapes to the STREAMING micro-wave path — the
    same frozen arrival trace consumed by two streaming loops (one
    mesh-resident, one unsharded) binds every pod to the same node, and
    the mesh run keeps the delta-only invariants: zero encode rebuilds
    after warmup and dynamic-row deltas riding the per-shard row path."""
    from kubernetes_tpu.models.hollow import PROFILES
    from kubernetes_tpu.utils.trace import COUNTERS

    trace = (37, 96, 5, 64)
    quantum = 128

    def run(mesh):
        api, s = _mesh_sched(48, mesh)
        loop = s.stream(budget_s=30.0, min_quantum=quantum,
                        max_quantum=quantum)
        # warm: one group compiles shapes + builds the encoding
        for p in PROFILES["density"](quantum):
            p.name = "warm-" + p.name
            api.create("Pod", p)
        loop.step()
        loop.drain()
        snap0 = COUNTERS.snapshot()
        for gi, group in enumerate(trace):
            pods = PROFILES["density"](group)
            for p in pods:
                p.name = f"g{gi}-{p.name}"
                api.create("Pod", p)
            loop.step()
        loop.drain()
        loop.close()
        snap1 = COUNTERS.snapshot()

        def delta(name):
            return snap1.get(name, (0, 0))[0] - snap0.get(name, (0, 0))[0]
        return ({p.name: p.node_name for p in api.list("Pod")[0]},
                {k: delta(k) for k in ("engine.wave_encode_build",
                                       "engine.shard_delta_rows",
                                       "snapshot.assume_delta_rows")})

    pa, _ = run(None)
    pb, counters = run(make_mesh(N_DEV))
    assert pa == pb, {k: (pa[k], pb[k]) for k in pa if pa[k] != pb[k]}
    assert all(v for v in pa.values())
    # delta-only invariant, mesh edition: no re-tensorization mid-stream,
    # and the assume folds shipped through the per-shard row path
    assert counters["engine.wave_encode_build"] == 0
    assert counters["engine.shard_delta_rows"] > 0
    assert counters["snapshot.assume_delta_rows"] >= sum(trace)


def test_make_mesh_refuses_more_devices_than_exist():
    """A mesh smaller than asked for would silently run unsharded."""
    with pytest.raises(ValueError, match="only"):
        make_mesh(len(jax.devices()) + 1)
    assert make_mesh(2).devices.size == 2
