"""Compile-only checks for the chip, at the main path's real widths.

Each test lowers and compiles a program for one chip of a described
TPU v5e 2x2 topology; nothing runs, so this says nothing about results
or times (chip_smoke.py does that on the chip). It catches what interpret
mode cannot: tiles the Mosaic compiler refuses, fast-memory overruns, a
program too big for the device. The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
the test workers must all collect the same tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

N_NODES = 5000
N_PODS = 30_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no chip model
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _described(tree, sharding):
    """Shapes (not arrays) of a pytree, placed on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()
    print(fn.__name__, compiled.memory_analysis())
    return compiled


def test_capacity_kernel_compiles_at_drain_width(one_chip):
    from kubernetes_tpu.ops.pallas_kernels import capacity_fits_pallas
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                           sharding=one_chip)
    compiled = _compile(capacity_fits_pallas, s((N_PODS, 8)),
                        s((N_NODES, 8)), s((N_NODES, 8)))
    assert "tpu_custom_call" in compiled.as_text()


def test_incidence_kernel_compiles_at_affinity_width(one_chip):
    from kubernetes_tpu.ops.pallas_kernels import incidence_matmul_pallas
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                           sharding=one_chip)
    compiled = _compile(incidence_matmul_pallas, s((48, 2048)),
                        s((N_NODES, 2048)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def hollow_snapshot():
    from kubernetes_tpu.models.hollow import hollow_nodes
    from kubernetes_tpu.state.node_info import node_info_map
    from kubernetes_tpu.state.snapshot import ClusterSnapshot
    infos = node_info_map(hollow_nodes(N_NODES), [])
    snap = ClusterSnapshot()
    snap.refresh(infos)
    return infos, snap


def test_fused_extender_eval_compiles_with_kernel(hollow_snapshot, one_chip,
                                                  monkeypatch):
    """The extender's one-pod eval for an anti-affinity pod: on the chip
    the static incidence half is the Pallas kernel. jax.default_backend()
    is the CPU here, so the test steers the dispatcher to the TPU branch."""
    from kubernetes_tpu.engine import scheduler_engine as se
    from kubernetes_tpu.models.hollow import mixed_affinity_pods
    from kubernetes_tpu.ops import pallas_kernels
    from kubernetes_tpu.ops import priorities as prio
    from kubernetes_tpu.ops.affinity import (
        AffinityData,
        collect_pod_pairs,
        intern_topology_pairs,
    )
    from kubernetes_tpu.ops.predicates import node_arrays, pod_arrays_bucketed
    from kubernetes_tpu.state.classes import ClassBatch

    infos, snap = hollow_snapshot
    pod = mixed_affinity_pods(1)[0]  # the "one replica per host" class
    assert pod.affinity is not None
    all_pairs, aff_pairs = collect_pod_pairs(infos)
    intern_topology_pairs(snap, [pod], aff_pairs)
    batch = ClassBatch([pod], snap)
    adata = AffinityData(batch.reps, snap, all_pairs, aff_pairs, [], 1)
    assert adata.fits_needed
    plain = tuple((nm, w) for nm, w in prio.DEFAULT_PRIORITIES
                  if nm not in prio.AFFINITY_PRIORITIES)
    weights = (sum(w for nm, w in prio.DEFAULT_PRIORITIES
                   if nm == "InterPodAffinityPriority"),
               sum(w for nm, w in prio.DEFAULT_PRIORITIES
                   if nm == "SelectorSpreadPriority"))
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    compiled = _compile(
        se._fused_eval,
        _described(pod_arrays_bucketed(batch.reps_batch), one_chip),
        _described(node_arrays(snap), one_chip),
        _described(adata.device_arrays(), one_chip),
        priorities=plain, weights=weights, aff_mode=(True, False, False))
    assert "tpu_custom_call" in compiled.as_text()


def test_waves_loop_compiles_at_drain_width(hollow_snapshot, one_chip):
    """One pipelined-drain chunk of the 30k/5k density drain (15,000 pods,
    bucketed to 16,384) through the whole wave program. The class axis is
    sub-tile, so the capacity check stays on the fused jnp path and no
    kernel is expected."""
    from kubernetes_tpu.engine import waves
    from kubernetes_tpu.engine.batch import node_state
    from kubernetes_tpu.models.hollow import density_pods
    from kubernetes_tpu.ops import priorities as prio
    from kubernetes_tpu.ops.predicates import (
        bucket,
        node_arrays,
        pod_arrays_padded,
    )
    from kubernetes_tpu.state.classes import ClassBatch

    _infos, snap = hollow_snapshot
    chunk = N_PODS // 2
    batch = ClassBatch(density_pods(chunk), snap)
    c_pad = bucket(batch.num_classes + 1)
    narr = node_arrays(snap)
    plain = tuple((nm, w) for nm, w in prio.DEFAULT_PRIORITIES
                  if nm not in prio.AFFINITY_PRIORITIES)
    pc = np.full(bucket(chunk), batch.num_classes, dtype=np.int32)
    pc[:chunk] = batch.pod_class
    nodes_d = _described(narr, one_chip)
    compiled = waves.waves_loop.lower(
        _described(pod_arrays_padded(batch.reps_batch, c_pad), one_chip),
        nodes_d, _described(node_state(narr), one_chip),
        _described(pc, one_chip),
        jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip),
        plain, 64).compile()
    print("waves_loop", compiled.memory_analysis())
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 10 ** 9


def test_tail_rounds_kernel_compiles_per_shard_on_a_mesh(
        hollow_snapshot, topo, monkeypatch):
    """The seeded tail is a GSPMD program over the engine's resident node
    mesh. With 128 class rows its capacity check takes the Pallas kernel,
    which XLA refuses to partition by itself ("Mosaic kernels cannot be
    automatically partitioned"): it must run per node shard."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from kubernetes_tpu.api.types import make_pod
    from kubernetes_tpu.engine import waves
    from kubernetes_tpu.engine.batch import node_state
    from kubernetes_tpu.ops import pallas_kernels
    from kubernetes_tpu.ops import priorities as prio
    from kubernetes_tpu.ops.predicates import (
        bucket,
        node_arrays,
        pod_arrays_padded,
    )
    from kubernetes_tpu.parallel.mesh import NODE_AXIS, node_spec
    from kubernetes_tpu.state.classes import ClassBatch

    _infos, snap = hollow_snapshot
    mesh = Mesh(np.array(topo.devices), (NODE_AXIS,))
    rep = NamedSharding(mesh, PS())
    pods = [make_pod(f"tail-{i}", cpu=100 + i, memory=256 << 20)
            for i in range(127)]
    batch = ClassBatch(pods, snap)
    c_pad = bucket(batch.num_classes + 1)
    assert c_pad >= pallas_kernels.P_BLK
    narr = node_arrays(snap)
    nodes_d = {k: jax.ShapeDtypeStruct(
        np.shape(v), np.asarray(v).dtype,
        sharding=NamedSharding(mesh, node_spec(k, np.ndim(v))))
        for k, v in narr.items()}
    state_d = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, PS(NODE_AXIS,
                                            *([None] * (x.ndim - 1))))),
        node_state(narr))
    plain = tuple((nm, w) for nm, w in prio.DEFAULT_PRIORITIES
                  if nm not in prio.AFFINITY_PRIORITIES)
    pc = np.zeros(bucket(len(pods)), dtype=np.int32)
    pc[:len(pods)] = batch.pod_class
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    compiled = waves.tail_rounds_loop.lower(
        _described(pod_arrays_padded(batch.reps_batch, c_pad), rep),
        nodes_d, state_d, _described(pc, rep),
        jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep), plain,
        spmd_mesh=mesh).compile()
    print("tail_rounds_loop", compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
