"""Vectorized fit predicates: the pod x node filter as one fused kernel.

Replaces the reference's findNodesThatFit hot loop
(plugin/pkg/scheduler/core/generic_scheduler.go:163-232: 16-way
workqueue.Parallelize over nodes, each worker running the predicate chain
object-by-object) with dense [P, N] masks computed in one XLA program.

Predicate parity map (reference: plugin/pkg/scheduler/algorithm/predicates/predicates.go):
  PodFitsResources        :556  -> resources_fit (incl. zero-request early-exit
                                   :576 and the overlay->scratch fallback :590-604)
  PodFitsHost             :698  -> host_fit
  PodFitsHostPorts        :859  -> ports_fit (bitmap gather over 65536 ports)
  PodMatchNodeSelector    :686  -> selector_fit (OR-of-AND terms as int8 matmuls)
  PodToleratesNodeTaints  :1241 -> taints_fit (intolerated x taint matmul)
  CheckNodeCondition      :1306 -> node_ok (precomputed host-side verdict)
  CheckNodeMemoryPressure :1274 -> mem_pressure_fit (best-effort pods only)
  CheckNodeDiskPressure   :1296 -> disk_pressure_fit
  GeneralPredicates       :900  -> resources & host & ports & selector

All functions are shape-polymorphic jittable JAX; inputs are the arrays
produced by kubernetes_tpu.state.snapshot (node side) and PodBatch (pod side),
passed as two dicts (pytrees). Integer semantics are preserved exactly.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from kubernetes_tpu.state.snapshot import (
    NUM_BASE_RESOURCES,
    R_GPU,
    R_MEM,
    R_CPU,
    R_OVERLAY,
    R_SCRATCH,
)

Arrays = Dict[str, jnp.ndarray]


_NODE_ARRAY_KEYS = ("alloc", "requested", "nonzero", "pod_count",
                    "allowed_pods", "schedulable", "mem_pressure",
                    "disk_pressure", "labels", "taints_sched",
                    "taints_pref", "port_bitmap", "valid", "avoid",
                    "image_sizes", "vol_present", "vol_rw", "pd_present",
                    "pd_counts", "pd_kind", "pd_max", "has_zone")


def node_arrays(snap) -> Arrays:
    """Assemble the node-side pytree from a ClusterSnapshot.

    Zero-copy VIEW seam: callers consume the dispatch synchronously
    (the extender cold path, tests) before any snapshot mutation can run,
    so aliasing the live snapshot arrays is safe AND free. Anything that
    holds device work across host bookkeeping must go through the
    engine's copying seam instead (_nodes_on_device — GL001's
    copy-required contract). GRAFT_SANITIZE=1 upgrades these to verified
    copies, so sanitized runs don't depend on the synchronous-consumption
    argument at all."""
    from kubernetes_tpu.analysis.sanitize import upload_view
    return {k: upload_view(getattr(snap, k)) for k in _NODE_ARRAY_KEYS}


def bucket(n: int, lo: int = 16) -> int:
    """Power-of-2 shape bucket: jit kernels specialize per shape, so batch
    axes are padded to buckets to bound recompiles at log2(max) variants."""
    p = lo
    while p < n:
        p *= 2
    return p


def pod_arrays_padded(batch, rows: int) -> Arrays:
    """pod_arrays with the batch axis padded to `rows`. Padding rows are
    marked `impossible` so they fit nothing, commit nothing, and never tick
    the RR counter — inert in both the strict scan and the wave kernel.
    Padding happens in NUMPY: eager jnp ops each compile a tiny XLA program
    (a compile per shape); np.pad + one device_put per
    array costs no compiles."""
    import numpy as _np
    arrs = _pod_arrays_np(batch)
    c = len(batch)
    if rows < c:
        raise ValueError(f"rows {rows} < batch size {c}")
    out = {}
    for k, a in arrs.items():
        if rows > c:
            pad = _np.zeros((rows - c,) + a.shape[1:], dtype=a.dtype)
            if k == "impossible":
                pad[:] = True
            a = _np.concatenate([a, pad], axis=0)
        out[k] = jnp.asarray(a)
    return out


def pod_arrays(batch) -> Arrays:
    """Assemble the pod-side pytree from a PodBatch (one device_put each)."""
    return {k: jnp.asarray(v) for k, v in _pod_arrays_np(batch).items()}


# selector/preference slot axes sized by actual usage (PodBatch): key ->
# (axis -> dim kind). Zero padding is inert on every one of them — padded
# terms carry sel_term_valid/pref_valid False (the OR skips them) and padded
# any-groups carry *_any_used False (the conjunct auto-passes).
_SLOT_AXES = {
    "sel_req_all": {1: "T"}, "sel_req_any": {1: "T", 2: "A"},
    "sel_forbid": {1: "T"}, "sel_term_valid": {1: "T"},
    "sel_any_used": {1: "T", 2: "A"}, "sel_unsat": {1: "T"},
    "pref_req_all": {1: "TP"}, "pref_req_any": {1: "TP", 2: "A"},
    "pref_forbid": {1: "TP"}, "pref_any_used": {1: "TP", 2: "A"},
    "pref_valid": {1: "TP"}, "pref_unsat": {1: "TP"},
    "pref_empty": {1: "TP"}, "pref_weight": {1: "TP"},
    "pvaff_req_any": {1: "A"}, "pvaff_any_used": {1: "A"},
}


def pod_arrays_bucketed(batch, rows: int = 0) -> Arrays:
    """pod_arrays with the selector-term / any-group / preferred-term axes
    padded up to power-of-2 buckets. PodBatch sizes those axes to the batch's
    actual usage, so [1,N] single-pod evaluations (the extender fast lane)
    would otherwise compile one kernel variant per distinct term count;
    bucketing bounds the variants at log2(slot caps) like every other batch
    axis (bucket()).

    ``rows`` > 0 additionally pads the CLASS axis to that many rows (the
    coalesced multi-class extender eval, ISSUE 9): padding rows are
    `impossible` — they fit nothing and score nothing — exactly the
    pod_arrays_padded contract, so a batch of B distinct classes compiles
    one kernel per bucket(B), not one per B."""
    import numpy as _np
    arrs = _pod_arrays_np(batch)
    c = len(batch)
    if rows and rows < c:
        raise ValueError(f"rows {rows} < batch size {c}")
    dims = {"T": bucket(arrs["sel_req_all"].shape[1], lo=1),
            "A": bucket(arrs["sel_req_any"].shape[2], lo=1),
            "TP": bucket(arrs["pref_req_all"].shape[1], lo=1)}
    out = {}
    for k, a in arrs.items():
        axes = _SLOT_AXES.get(k)
        if axes:
            widths = [(0, 0)] * a.ndim
            grow = False
            for ax, kind in axes.items():
                pad = dims[kind] - a.shape[ax]
                if pad > 0:
                    widths[ax] = (0, pad)
                    grow = True
            if grow:
                a = _np.pad(a, widths)
        if rows and rows > c:
            pad = _np.zeros((rows - c,) + a.shape[1:], dtype=a.dtype)
            if k == "impossible":
                pad[:] = True
            a = _np.concatenate([a, pad], axis=0)
        out[k] = jnp.asarray(a)
    return out


def _pod_arrays_np(batch):
    """The pod-side arrays as host numpy, keyed like pod_arrays."""
    return {
        "req": batch.req,
        "nonzero": batch.nonzero,
        "zero_req": batch.zero_req,
        "impossible": batch.impossible,
        "best_effort": batch.best_effort,
        "ports": batch.ports,
        "intolerated": batch.intolerated,
        "intolerated_pref": batch.intolerated_pref,
        "host_required": batch.host_required,
        "has_host": batch.has_host,
        "sel_req_all": batch.sel_req_all,
        "sel_req_any": batch.sel_req_any,
        "sel_forbid": batch.sel_forbid,
        "sel_term_valid": batch.sel_term_valid,
        "sel_any_used": batch.sel_any_used,
        "sel_unsat": batch.sel_unsat,
        "has_selector": batch.has_selector,
        "pref_req_all": batch.pref_req_all,
        "pref_req_any": batch.pref_req_any,
        "pref_forbid": batch.pref_forbid,
        "pref_any_used": batch.pref_any_used,
        "pref_valid": batch.pref_valid,
        "pref_unsat": batch.pref_unsat,
        "pref_empty": batch.pref_empty,
        "pref_weight": batch.pref_weight,
        "avoid_idx": batch.avoid_idx,
        "img_count": batch.img_count,
        "vol_hard": batch.vol_hard,
        "vol_ro": batch.vol_ro,
        "pd_req": batch.pd_req,
        "pd_req_count": batch.pd_req_count,
        "vz_req": batch.vz_req,
        "vz_err": batch.vz_err,
        "pvaff_req_all": batch.pvaff_req_all,
        "pvaff_req_any": batch.pvaff_req_any,
        "pvaff_forbid": batch.pvaff_forbid,
        "pvaff_any_used": batch.pvaff_any_used,
        "pvaff_unsat": batch.pvaff_unsat,
        "pvaff_has": batch.pvaff_has,
    }


# ---------------------------------------------------------------------------
# capacity-dependent predicates (re-evaluated inside the placement scan)
# ---------------------------------------------------------------------------


def resources_fit(pod_req: jnp.ndarray, zero_req: jnp.ndarray,
                  alloc: jnp.ndarray, requested: jnp.ndarray) -> jnp.ndarray:
    """PodFitsResources (predicates.go:556-624) minus the pod-count check.

    pod_req [P,R], zero_req [P], alloc [N,R], requested [N,R] -> bool [P,N].
    Column layout: 0=cpu 1=mem 2=gpu 3=scratch 4=overlay 5..=extended.
    """
    total = pod_req[:, None, :] + requested[None, :, :]  # [P,N,R]
    ok = total <= alloc[None, :, :]
    # cpu/mem/gpu + extended: plain elementwise
    plain = jnp.concatenate(
        [ok[..., :R_SCRATCH], ok[..., NUM_BASE_RESOURCES:]], axis=-1
    ).all(axis=-1)
    # storage special-case (predicates.go:590-604): when the node reports no
    # overlay capacity, overlay requests fall back onto scratch space.
    alloc_s = alloc[None, :, R_SCRATCH]
    alloc_o = alloc[None, :, R_OVERLAY]
    pod_s = pod_req[:, None, R_SCRATCH]
    pod_o = pod_req[:, None, R_OVERLAY]
    node_s = requested[None, :, R_SCRATCH]
    node_o = requested[None, :, R_OVERLAY]
    no_overlay = alloc_o == 0
    scratch_ok = jnp.where(
        no_overlay,
        pod_s + pod_o + node_s + node_o <= alloc_s,
        pod_s + node_s <= alloc_s,
    )
    overlay_ok = no_overlay | (pod_o + node_o <= alloc_o)
    fit = plain & scratch_ok & overlay_ok
    # all-zero request skips resource checks entirely (predicates.go:576-578)
    return fit | zero_req[:, None]


def pod_count_fit(pod_count: jnp.ndarray, allowed_pods: jnp.ndarray) -> jnp.ndarray:
    """len(pods)+1 <= allowedPodNumber (predicates.go:563-566). [N] -> [N]."""
    return pod_count + 1 <= allowed_pods


def ports_fit(ports: jnp.ndarray, port_bitmap: jnp.ndarray) -> jnp.ndarray:
    """PodFitsHostPorts (predicates.go:859-878) via packed-bitmap gather.

    ports [P,8] int32 with -1 sentinel; port_bitmap [N,2048] uint32 -> [P,N].
    """
    want = ports >= 0
    safe = jnp.maximum(ports, 0)
    word = safe // 32  # [P,8]
    bit = (safe % 32).astype(jnp.uint32)
    # gather words: [N, P, 8]
    gathered = jnp.take(port_bitmap, word, axis=1)
    hit = ((gathered >> bit[None, :, :]) & jnp.uint32(1)).astype(bool)
    conflict = (hit & want[None, :, :]).any(axis=-1)  # [N,P]
    return ~conflict.T


def no_disk_conflict(vol_hard: jnp.ndarray, vol_ro: jnp.ndarray,
                     vol_present: jnp.ndarray, vol_rw: jnp.ndarray
                     ) -> jnp.ndarray:
    """NoDiskConflict (predicates.go:183-196) as two int8 matmuls over the
    conflict-key vocab: a HARD key (EBS, or any read-write mount) conflicts
    with any presence; an RO key conflicts only with a read-write mount.
    vol_hard/vol_ro [P,Vc]; vol_present/vol_rw [N,Vc] -> bool [P,N]."""
    hard_hit = jnp.einsum("pv,nv->pn", vol_hard, vol_present,
                          preferred_element_type=jnp.int32)
    ro_hit = jnp.einsum("pv,nv->pn", vol_ro, vol_rw,
                        preferred_element_type=jnp.int32)
    return (hard_hit == 0) & (ro_hit == 0)


def max_pd_fit(pd_req: jnp.ndarray, pd_req_count: jnp.ndarray,
               pd_kind: jnp.ndarray, pd_present: jnp.ndarray,
               pd_counts: jnp.ndarray, pd_max: jnp.ndarray) -> jnp.ndarray:
    """MaxPDVolumeCount for all three filters (predicates.go:285-323):
    numExisting + numNew <= max, where numNew = pod's distinct filtered ids
    not already on the node; a pod with no kind-f volumes passes filter f
    (the quick return at :297-300).

    pd_req [P,Vpd], pd_req_count [P,3], pd_kind [3,Vpd], pd_present [N,Vpd],
    pd_counts [N,3], pd_max [3] -> bool [P,N]."""
    fit = None
    for k in range(3):
        req_k = pd_req * pd_kind[k][None, :]  # [P,Vpd] int8
        overlap = jnp.einsum("pv,nv->pn", req_k, pd_present,
                             preferred_element_type=jnp.int32)
        new = pd_req_count[:, k][:, None] - overlap
        ok = ((pd_req_count[:, k][:, None] == 0)
              | (pd_counts[None, :, k] + new <= pd_max[k]))
        fit = ok if fit is None else fit & ok
    return fit


# ---------------------------------------------------------------------------
# capacity-independent predicates (computed once per batch, MXU matmuls)
# ---------------------------------------------------------------------------


def volume_zone_fit(vz_req: jnp.ndarray, vz_err: jnp.ndarray,
                    labels: jnp.ndarray, has_zone: jnp.ndarray) -> jnp.ndarray:
    """NoVolumeZoneConflict (predicates.go:404-474): nodes with no
    zone/region labels pass (fast-path BEFORE PVC resolution, so resolution
    errors — vz_err — fail only zone-labeled nodes); otherwise every
    (zone-key, value) pair demanded by the pod's bound PVs must be present.
    vz_req [P,L] over the label-pair vocab; labels [N,L]; has_zone [N]."""
    cnt = jnp.einsum("pl,nl->pn", vz_req, labels.astype(jnp.int8),
                     preferred_element_type=jnp.int32)
    need = vz_req.astype(jnp.int32).sum(axis=-1)[:, None]
    return (~has_zone[None, :]) | ((cnt == need) & ~vz_err[:, None])


def pv_affinity_fit(pods: Arrays, labels: jnp.ndarray) -> jnp.ndarray:
    """NoVolumeNodeConflict (predicates.go:1354-1411 + util.go:193): the
    pod's bound PVs' node-affinity requirements, ANDed into one conjunct,
    evaluated like one selector term. Pass-through for pods without PV
    affinity (pvaff_has False)."""
    lab = labels.astype(jnp.int8)
    all_cnt = jnp.einsum("pl,nl->pn", pods["pvaff_req_all"], lab,
                         preferred_element_type=jnp.int32)
    need = pods["pvaff_req_all"].astype(jnp.int32).sum(axis=-1)[:, None]
    forbid_cnt = jnp.einsum("pl,nl->pn", pods["pvaff_forbid"], lab,
                            preferred_element_type=jnp.int32)
    any_cnt = jnp.einsum("pal,nl->pan", pods["pvaff_req_any"], lab,
                         preferred_element_type=jnp.int32)
    any_ok = ((any_cnt > 0) | ~pods["pvaff_any_used"][:, :, None]).all(axis=1)
    ok = ((all_cnt == need) & (forbid_cnt == 0) & any_ok
          & ~pods["pvaff_unsat"][:, None])
    return ok | ~pods["pvaff_has"][:, None]


def selector_fit(pods: Arrays, labels: jnp.ndarray) -> jnp.ndarray:
    """PodMatchNodeSelector + required node affinity (predicates.go:625-696).

    Terms are OR'd; inside a term requirements are AND'd. Compilation into
    req_all / req_any / forbid sets happens host-side (snapshot.PodBatch);
    here it is three int8 matmuls against node labels [N,L] and compares.
    """
    req_all = pods["sel_req_all"]  # [P,T,L]
    req_any = pods["sel_req_any"]  # [P,T,A,L]
    forbid = pods["sel_forbid"]  # [P,T,L]
    lab = labels.astype(jnp.int8)
    all_cnt = jnp.einsum("ptl,nl->ptn", req_all, lab,
                         preferred_element_type=jnp.int32)
    need = req_all.astype(jnp.int32).sum(axis=-1)  # [P,T]
    all_ok = all_cnt == need[:, :, None]
    forbid_cnt = jnp.einsum("ptl,nl->ptn", forbid, lab,
                            preferred_element_type=jnp.int32)
    forbid_ok = forbid_cnt == 0
    any_cnt = jnp.einsum("ptal,nl->ptan", req_any, lab,
                         preferred_element_type=jnp.int32)
    any_ok = ((any_cnt > 0) | ~pods["sel_any_used"][:, :, :, None]).all(axis=2)
    term_ok = (all_ok & forbid_ok & any_ok
               & pods["sel_term_valid"][:, :, None]
               & ~pods["sel_unsat"][:, :, None])
    return term_ok.any(axis=1) | ~pods["has_selector"][:, None]


def taints_fit(intolerated: jnp.ndarray, taints_sched: jnp.ndarray) -> jnp.ndarray:
    """PodToleratesNodeTaints (predicates.go:1241): fail when the node has any
    NoSchedule/NoExecute taint the pod does not tolerate. int8 matmul."""
    cnt = jnp.einsum("pt,nt->pn", intolerated, taints_sched.astype(jnp.int8),
                     preferred_element_type=jnp.int32)
    return cnt == 0


def host_fit(has_host: jnp.ndarray, host_required: jnp.ndarray, n: int) -> jnp.ndarray:
    """PodFitsHost (predicates.go:698-712). [P] -> [P,N]."""
    idx = jnp.arange(n, dtype=jnp.int32)
    return (~has_host[:, None]) | (host_required[:, None] == idx[None, :])


def node_condition_fit(pods: Arrays, nodes: Arrays) -> jnp.ndarray:
    """CheckNodeCondition + pressure predicates (predicates.go:1274-1337).
    Node-side verdicts are precomputed host-side; composition here."""
    ok = nodes["schedulable"] & nodes["valid"]  # [N]
    mem_ok = (~pods["best_effort"][:, None]) | (~nodes["mem_pressure"][None, :])
    disk_ok = ~nodes["disk_pressure"][None, :]
    return ok[None, :] & mem_ok & disk_ok


def static_fits(pods: Arrays, nodes: Arrays) -> jnp.ndarray:
    """All spec-INdependent predicates -> [P,N]. Computed once per batch;
    safe to reuse across the placement scan because nothing here changes as
    pods commit (labels/taints/host are node-spec facts). Node CONDITIONS
    (Ready/pressure/cordon/membership) are deliberately NOT in here since
    ISSUE 8: they flip under churn while the engine's cached precompute
    (waves.precompute) holds a static_fit across kills/flaps/respawns —
    every consumer ANDs node_condition_fit against its FRESH node arrays
    instead."""
    n = nodes["alloc"].shape[0]
    out = (
        selector_fit(pods, nodes["labels"])
        & taints_fit(pods["intolerated"], nodes["taints_sched"])
        & host_fit(pods["has_host"], pods["host_required"], n)
        & volume_zone_fit(pods["vz_req"], pods["vz_err"], nodes["labels"],
                          nodes["has_zone"])
        & pv_affinity_fit(pods, nodes["labels"])
        & ~pods["impossible"][:, None]  # ext resource no node advertises /
        # unresolvable PVC (predicate error in the reference)
    )
    if "policy_fit" in pods:
        # Policy-configured NodeLabelPresence / ServiceAffinity masks,
        # precomputed host-side (ops/policy_algos.py)
        out = out & pods["policy_fit"]
    if "host_fit" in pods:
        # host-check static column (ISSUE 18): the exact label-pure
        # host predicate for classes whose selector/zone/PV shape
        # overflowed the fused encoding, precomputed host-side
        # (PodBatch.host_static_fit) so those classes ride the wave
        # instead of flushing. ANDing exact with the over-approximate
        # terms above keeps the composite exact.
        out = out & pods["host_fit"]
    return out


def fits(pods: Arrays, nodes: Arrays) -> jnp.ndarray:
    """The full predicate chain against a frozen snapshot -> bool [P,N].

    Equivalent of running podFitsOnNode (generic_scheduler.go:234) for every
    (pending pod, node) pair with GeneralPredicates + taints + conditions —
    i.e. the default provider's registered predicates that are modeled so far
    (volume predicates pending; see SURVEY.md §7 step 7).
    """
    from kubernetes_tpu.ops.pallas_kernels import resources_fit_fast
    return (
        static_fits(pods, nodes)
        & node_condition_fit(pods, nodes)
        & resources_fit_fast(pods["req"], pods["zero_req"], nodes["alloc"],
                             nodes["requested"])
        & pod_count_fit(nodes["pod_count"], nodes["allowed_pods"])[None, :]
        & ports_fit(pods["ports"], nodes["port_bitmap"])
        & no_disk_conflict(pods["vol_hard"], pods["vol_ro"],
                           nodes["vol_present"], nodes["vol_rw"])
        & max_pd_fit(pods["pd_req"], pods["pd_req_count"], nodes["pd_kind"],
                     nodes["pd_present"], nodes["pd_counts"], nodes["pd_max"])
    )


fits_jit = jax.jit(fits)
