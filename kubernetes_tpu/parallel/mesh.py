"""Device-mesh sharding for the scheduler kernels.

The scale axis of the reference is cluster size x pending-queue depth
(SURVEY.md §5.7); here that becomes tensor sharding over a 1-D "nodes" mesh:
every node-indexed array (labels, taints, alloc, requested, port bitmaps...)
is sharded along axis 0 across devices, pod-side arrays are replicated, and
XLA inserts the collectives (max/argmin reductions over the node axis ride
the ICI ring) — the pjit recipe: pick a mesh, annotate shardings, let the
compiler do the communication. This replaces the reference's
workqueue.Parallelize(16, nodes) fan-out (generic_scheduler.go:204,352) with
true SPMD over chips.

The sequential placement scan works unchanged under these shardings: the
per-step dyn-fit/score math is elementwise over N (local to each shard), the
argmax/min reductions become cross-device collectives, and the capacity
commit is a scatter into the owning shard.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Arrays = Dict[str, jax.Array]

NODE_AXIS = "nodes"

# node-side arrays sharded along the node axis; everything else replicated
_NODE_SHARDED_KEYS = frozenset({
    "alloc", "requested", "nonzero", "pod_count", "allowed_pods",
    "schedulable", "mem_pressure", "disk_pressure", "labels", "taints_sched",
    "taints_pref", "port_bitmap", "valid", "avoid", "image_sizes",
    "has_zone", "vol_present", "vol_rw", "pd_present", "pd_counts",
})


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D node-axis mesh over the first `n_devices` devices (all when
    None). Asking for more devices than exist is an error, never a
    smaller mesh."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"make_mesh({n_devices}): only {len(devs)} "
                             f"{devs[0].platform} devices")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (NODE_AXIS,))


def shard_nodes(nodes: Arrays, mesh: Mesh) -> Arrays:
    """Place node-side arrays sharded along axis 0 of the mesh."""
    out = {}
    for k, v in nodes.items():
        spec = P(NODE_AXIS) if k in _NODE_SHARDED_KEYS else P()
        out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out


def replicate(pods: Arrays, mesh: Mesh) -> Arrays:
    sh = NamedSharding(mesh, P())
    return {k: jax.device_put(v, sh) for k, v in pods.items()}


# AffinityData device arrays (ops/affinity.py device_arrays): most are
# class/slot/label-indexed (replicated — the label axis L is the contraction
# axis of the topology einsums, so splitting it would force inner-product
# collectives per scan step; N is the embarrassingly-parallel axis), but
# some carry a node axis and shard with the nodes:
#   sp_static [C, N] axis 1, Z [N, ZN] axis 0, node_has_zone [N] axis 0,
# plus the r08/r09 wave-path bundles (engine/scheduler_engine
# _aff_node_views / _aff_tail_arrays): key_node [C, A, N] axis 2,
# static_forbid [C, N] axis 1, and the tail's projected node incidence
# labels_aff [N, Lp] axis 0 (Lp is the SMALL projected domain axis — it
# stays replicated as a contraction axis, exactly like L)
_AFF_NODE_AXIS = {"sp_static": 1, "Z": 0, "node_has_zone": 0,
                  "key_node": 2, "static_forbid": 1, "labels_aff": 0}


def shard_affinity(aff: Arrays, mesh: Mesh) -> Arrays:
    """Place affinity class arrays: node-axis arrays sharded along the mesh,
    everything else replicated. The affinity scan carry (commdom [C,L],
    committed [C,N], comm_cnt [C]) is created inside the jitted program;
    XLA lays it out to match these operand shardings."""
    out = {}
    for k, v in aff.items():
        out[k] = jax.device_put(v, NamedSharding(mesh, aff_spec(k)))
    return out


# ---------------------------------------------------------------- residency
# ISSUE 12: the node axis as a RESIDENT scaling dimension. The recipes
# above place arrays once per call — fine for a dryrun, wrong for an
# always-on engine whose snapshot/topology/static-pre tensors must stay
# sharded across every wave. The helpers below are the residency layer:
# spec tables shared by every consumer (engine uploads, shard_map
# in_specs, the dryrun), and a per-shard ROW update that rebuilds a
# sharded dynamic array touching ONLY the shards whose rows moved — the
# delta path's host->device traffic is then O(touched_shards x N/D)
# rows (whole shards re-ship, so a fold localized to few shards moves a
# fraction of N while a fold spread over every shard degrades to a full
# re-upload — engine.shard_upload_bytes states what actually moved), and
# no cross-device traffic is induced at all (untouched shards keep their
# existing device buffers by reference).


def node_spec(key: str, ndim: int = 2) -> P:
    """PartitionSpec for a snapshot/node-state array by key: node-axis
    arrays shard axis 0, everything else (pd_kind [3,V], pd_max [3],
    scalar-ish vocab tables) replicates."""
    if key in _NODE_SHARDED_KEYS:
        return P(NODE_AXIS, *([None] * (ndim - 1)))
    return P()


def aff_spec(key: str) -> P:
    """PartitionSpec for an AffinityData / wave-bundle device array."""
    ax = _AFF_NODE_AXIS.get(key)
    return P() if ax is None else P(*([None] * ax + [NODE_AXIS]))


def committed_spec() -> P:
    """The wave loop's [C, N] topology-occupancy carry: node axis 1."""
    return P(None, NODE_AXIS)


class ResidentMesh:
    """One engine's device mesh plus its cached NamedShardings.

    NamedSharding construction is cheap but not free, and the engine asks
    for the same handful of specs every wave; caching also gives spec
    IDENTITY, which the partition-spec pin test reads."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.n_devices = int(mesh.devices.size)
        self._cache: Dict[tuple, NamedSharding] = {}
        # device order along the node axis — shard d owns global rows
        # [d*Nl, (d+1)*Nl); make_array_from_single_device_arrays consumes
        # buffers in this order
        self.devices = list(mesh.devices.reshape(-1))

    def sharding(self, spec: P) -> NamedSharding:
        key = tuple(spec)
        hit = self._cache.get(key)
        if hit is None:
            hit = self._cache[key] = NamedSharding(self.mesh, spec)
        return hit

    def node_sharding(self, key: str, ndim: int = 2) -> NamedSharding:
        return self.sharding(node_spec(key, ndim))

    def aff_sharding(self, key: str) -> NamedSharding:
        return self.sharding(aff_spec(key))

    def committed_sharding(self) -> NamedSharding:
        return self.sharding(committed_spec())

    # ----------------------------------------------------- row delta path

    def update_rows(self, dev: jax.Array, host: np.ndarray,
                    rows: Sequence[int]) -> jax.Array:
        """Rebuild an axis-0-sharded device array from `host`, re-uploading
        ONLY the shards owning `rows`; every other shard keeps its existing
        device buffer (no transfer, no cross-device traffic). The unit of
        upload is a whole SHARD (N/D rows): traffic is
        O(touched_shards x N/D), so row-localized folds ship a fraction
        of N and a fold touching every shard degrades to a full
        re-upload — `touched_nbytes` states the actual byte cost. The
        caller guarantees `host` equals the device content outside the
        touched rows (the engine's dirty-row contract). Returns the new
        array and never mutates `dev` — in-flight waves keep their
        operand.

        Each touched shard's slice is COPIED host-side before device_put:
        even a zero-copy single-device placement then aliases only the
        throwaway slice, never the live snapshot array (the GL001
        copy-required contract, per shard)."""
        n = host.shape[0]
        nl = n // self.n_devices
        touched = {min(int(r) // nl, self.n_devices - 1) for r in rows}
        shards = {s.device: s.data for s in dev.addressable_shards}
        bufs = []
        for d, device in enumerate(self.devices):
            if d in touched:
                bufs.append(jax.device_put(
                    np.array(host[d * nl:(d + 1) * nl]), device))
            else:
                bufs.append(shards[device])
        sharding = self.sharding(P(NODE_AXIS, *([None] * (host.ndim - 1))))
        return jax.make_array_from_single_device_arrays(
            host.shape, sharding, bufs)

    def touched_nbytes(self, host: np.ndarray,
                       rows: Sequence[int]) -> int:
        """Host->device bytes update_rows actually ships for `rows`:
        whole shards, not rows — len(touched_shards) x N/D x row bytes."""
        n = host.shape[0]
        nl = n // self.n_devices
        touched = {min(int(r) // nl, self.n_devices - 1) for r in rows}
        return len(touched) * nl * (host.nbytes // max(n, 1))
