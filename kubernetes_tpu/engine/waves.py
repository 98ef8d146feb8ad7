"""Wave-parallel batch placement: the whole queue in a handful of MXU passes.

The strict engine (engine/batch.py) reproduces the reference's one-pod-at-a-
time loop (scheduler.go:253 scheduleOne) exactly with a 30k-step lax.scan —
bit-faithful, but latency-bound (~90us/step of sequential VPU work). This
module is the throughput mode: batch placement is *new capability* relative
to the reference (SURVEY.md §2.3 — the only in-tree batching notion is the
strictly-sequential loop), so its semantics are defined here, TPU-first, per
the SURVEY §7 step-2 design ("top-k per pod + greedy conflict resolution,
capacity decremented as pods commit"):

Wave semantics (deterministic, documented, score-exact):
  1. All still-pending pods score every node against a FROZEN node state
     using the *identical* predicate/priority kernels as the strict engine
     (ops/predicates.py, ops/priorities.py — integer semantics preserved, so
     individual scores bit-match generic_scheduler.go:88-142).
  2. Each pod draws from the shared round-robin counter in FIFO order (a pod
     with >1 fitting nodes consumes one draw, mirroring selectHost's counter
     discipline at generic_scheduler.go:144-160) and targets the
     (draw mod m)-th node of its class's max-score tie set — so a wave of
     identical pods fans out across the whole tie set in ONE device program
     instead of m sequential steps.
  3. Per-node conflict resolution ON DEVICE: pods that picked the same node
     are ordered FIFO; the longest prefix run of spec-equal pods that still
     fits (exact integer capacity math, including the overlay->scratch
     fallback of predicates.go:590-604) commits; the rest re-enter the next
     wave against the updated state. Pods with host ports or volumes commit
     at most one per node per wave (their within-wave interactions are not
     modeled, so they serialize).
  4. A pod whose class fits NO node under the frozen state is unschedulable:
     capacity only shrinks as pods commit, so it could not have fit later in
     the strict order either (monotonicity makes this verdict exact).

  5. Score-aware acceptance: rank r on a node commits only while the node's
     score AFTER r commits (exact integer re-evaluation of the dynamic
     priorities at the evolved utilization) stays >= the frozen runner-up
     score — reproducing the strict engine's score trajectory at integer
     score granularity, so LeastRequested still spreads and MostRequested
     still bin-packs within a single wave.

Inputs are CLASS-level arrays (state/classes.py) — fits/scores are [C, N]
with C = distinct pod specs, recovered per pod by gather. A uniform 30k-pod
storm is C=1: one [1,N] score row + O(P) index math per wave.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kubernetes_tpu.api.types import MAX_PRIORITY
from kubernetes_tpu.engine.batch import NodeState, gather_place_batch
from kubernetes_tpu.ops import predicates as preds
from kubernetes_tpu.ops import priorities as prio
from kubernetes_tpu.state.snapshot import (
    NUM_BASE_RESOURCES,
    R_OVERLAY,
    R_SCRATCH,
)

Arrays = Dict[str, jnp.ndarray]

_BIG = np.int32(2 ** 31 - 1)


# --------------------------------------------------------------------------
# node-axis collectives (ISSUE 12): every cross-node-axis operation in the
# wave body — row reductions, the winner tie-selection, per-row gathers,
# commit scatters — goes through ONE of these vtables so the single-device
# trace stays byte-for-byte what it always was while the sharded trace
# (waves_loop's spmd_mesh path, run under shard_map) becomes an explicit
# TWO-STAGE reduce: local per-shard work over N/D rows, then a tiny
# cross-device combine over n_devices candidates. No step ever gathers a
# full-N tensor to one device; the only cross-device payloads are [D, C]
# tie counts (all_gather), [C]/[P] psum/pmax combines, and the O(P)
# ownership-masked candidate sums.
# --------------------------------------------------------------------------


class _GlobalCol:
    """Whole-node-axis implementation — the ops exactly as the unsharded
    wave body always wrote them (bit-identity anchor for the A/B)."""

    spmd = False

    def __init__(self, n_global: int):
        self.n_global = n_global   # GLOBAL node-id sentinel bound
        self.n_local = n_global    # scatter width (== global here)

    def row_sum(self, x):
        return x.sum(axis=1)

    def row_max(self, x, keepdims=False):
        return x.max(axis=1, keepdims=keepdims)

    def first_fit(self, fits):
        """Global index of each class's first fitting node."""
        return jnp.argmax(fits, axis=1).astype(jnp.int32)

    def tie_select(self, ties, pod_class, kz):
        """Node index of the kz-th tie (ascending node order) of each
        pod's class — the RR fan-out lookup."""
        n = ties.shape[1]
        idx_n = jnp.arange(n, dtype=jnp.int32)
        rank = jnp.cumsum(ties.astype(jnp.int32), axis=1) - 1
        cols = jnp.where(ties, rank, n)
        rows = jnp.broadcast_to(jnp.arange(ties.shape[0])[:, None],
                                ties.shape)
        tiemat = jnp.zeros(ties.shape, dtype=jnp.int32).at[rows, cols].set(
            jnp.broadcast_to(idx_n[None, :], ties.shape), mode="drop")
        return tiemat[pod_class, kz]

    def take_rows(self, arr, idx):
        """arr[idx] for node-axis-0 arrays, idx = global node ids >= 0."""
        return arr[idx]

    def take2(self, arr, rows, cols):
        """arr[rows, cols] for [C, N] arrays, cols = global node ids."""
        return arr[rows, cols]

    def to_local(self, ids):
        """Scatter ids: global node id, or -1 -> the drop sentinel."""
        return jnp.where(ids < 0, jnp.int32(self.n_global), ids)


class _ShardCol:
    """Per-shard implementation, legal only inside shard_map over the node
    axis: shard d owns global rows [d*Nl, (d+1)*Nl). Reductions are local
    + psum/pmax; the tie lookup resolves ownership from an all-gathered
    [D, C] tie-count table (the O(n_devices) candidate traffic the bench
    counter reports); gathers/scatters translate global ids to local rows
    and drop the rest — each commit row is written by exactly ONE shard."""

    spmd = True

    def __init__(self, axis: str, n_global: int, n_local: int):
        self.axis = axis
        self.n_global = n_global
        self.n_local = n_local

    def _off(self):
        return (lax.axis_index(self.axis) * self.n_local).astype(jnp.int32)

    def row_sum(self, x):
        return lax.psum(x.sum(axis=1), self.axis)

    def row_max(self, x, keepdims=False):
        m = lax.pmax(x.max(axis=1), self.axis)
        return m[:, None] if keepdims else m

    def first_fit(self, fits):
        local = jnp.where(
            fits.any(axis=1),
            self._off() + jnp.argmax(fits, axis=1).astype(jnp.int32),
            _BIG)
        return lax.pmin(local, self.axis)

    def tie_select(self, ties, pod_class, kz):
        nl = ties.shape[1]
        off = self._off()
        m_l = ties.sum(axis=1).astype(jnp.int32)            # [C] local
        m_all = lax.all_gather(m_l, self.axis)              # [D, C] tiny
        prefix = jnp.cumsum(m_all, axis=0) - m_all          # exclusive
        my_prefix = prefix[lax.axis_index(self.axis)]       # [C]
        rank = jnp.cumsum(ties.astype(jnp.int32), axis=1) - 1
        cols = jnp.where(ties, rank, nl)
        rows = jnp.broadcast_to(jnp.arange(ties.shape[0])[:, None],
                                ties.shape)
        idx_n = off + jnp.arange(nl, dtype=jnp.int32)       # GLOBAL ids
        tiemat_l = jnp.zeros(ties.shape, dtype=jnp.int32).at[
            rows, cols].set(jnp.broadcast_to(idx_n[None, :], ties.shape),
                            mode="drop")
        lr = kz - my_prefix[pod_class]                      # local rank
        owned = (lr >= 0) & (lr < m_l[pod_class])
        cand = jnp.where(owned,
                         tiemat_l[pod_class, jnp.clip(lr, 0, nl - 1)], 0)
        return lax.psum(cand, self.axis)                    # [P] combine

    def take_rows(self, arr, idx):
        nl = arr.shape[0]
        loc = idx - self._off()
        ok = (loc >= 0) & (loc < nl)
        vals = arr[jnp.clip(loc, 0, nl - 1)]
        mask = ok.reshape(ok.shape + (1,) * (arr.ndim - 1))
        return lax.psum(jnp.where(mask, vals, 0), self.axis)

    def take2(self, arr, rows, cols):
        nl = arr.shape[1]
        loc = cols - self._off()
        ok = (loc >= 0) & (loc < nl)
        vals = arr[rows, jnp.clip(loc, 0, nl - 1)]
        return lax.psum(jnp.where(ok, vals, 0), self.axis)

    def to_local(self, ids):
        loc = ids - self._off()
        return jnp.where((ids >= 0) & (loc >= 0) & (loc < self.n_local),
                         loc, jnp.int32(self.n_local))


def _dynamic_fits(cls: Arrays, nodes: Arrays, state: NodeState,
                  mesh=None) -> jnp.ndarray:
    """Capacity-dependent predicate chain vs the wave's frozen state, [C,N].
    Same math as ops/predicates.fits but reading the evolving NodeState.
    `mesh`: the node-axis mesh of a GSPMD caller (see resources_fit_fast)."""
    from kubernetes_tpu.ops.pallas_kernels import resources_fit_fast
    return (
        resources_fit_fast(cls["req"], cls["zero_req"], nodes["alloc"],
                           state.requested, mesh=mesh)
        & preds.pod_count_fit(state.pod_count, nodes["allowed_pods"])[None, :]
        & preds.ports_fit(cls["ports"], state.port_bitmap)
        & preds.no_disk_conflict(cls["vol_hard"], cls["vol_ro"],
                                 state.vol_present, state.vol_rw)
        & preds.max_pd_fit(cls["pd_req"], cls["pd_req_count"], nodes["pd_kind"],
                           state.pd_present, state.pd_counts, nodes["pd_max"])
    )


_DYNAMIC = ("LeastRequestedPriority", "MostRequestedPriority",
            "BalancedResourceAllocation")
_REDUCE = ("TaintTolerationPriority", "NodeAffinityPriority")


def precompute(cls: Arrays, nodes: Arrays,
               priorities: Tuple[Tuple[str, int], ...]) -> Arrays:
    """Everything state-INdependent, computed once per batch OUTSIDE the
    wave loop (XLA cannot hoist work out of a lax.while_loop body): the
    static predicate mask, the reduce-priority count matrices, and the
    weighted sum of static priorities.

    The result depends only on the CLASS encoding and the STATIC node
    arrays — not on the evolving NodeState — so a pipelined drain reuses
    one instance across every wave/tail dispatch of an encoding
    (engine/scheduler_engine._tail_wave_pre): the selector/taint/
    node-affinity label-axis matmuls in here are the single largest
    per-dispatch cost once the loops themselves are round-granular.
    `precompute_jit` is the standalone entry point for that caching;
    the loops keep computing it inline when no `pre` is passed.

    Optional frozen columns (ISSUE 18): a `host_fit` [C, N] bool column
    (label-pure host-check classes, exact against build-time label
    truth — ops/predicates.static_fits ANDs it in) and `policy_fit` /
    `policy_score` columns (Policy-configured algorithms, frozen per
    class — ops/policy_algos.static_class_arrays). Both ride every
    dispatch of the encoding; staleness is the FENCE's problem
    (scheduler_engine._fence re-validates against live truth), never
    this eval's — which is what lets host-check and Policy chunks ride
    the wave path instead of flushing the pipeline."""
    c = cls["req"].shape[0]
    n = nodes["alloc"].shape[0]
    static_score = jnp.zeros((c, n), dtype=jnp.int32)
    for name, weight in priorities:
        if name in _DYNAMIC or name in _REDUCE:
            continue
        if name in ("SelectorSpreadPriority", "InterPodAffinityPriority"):
            # wave mode scores these against the batch-frozen cluster state
            # (ops/affinity.py); the engine passes them via extra_score
            continue
        static_score = static_score \
            + prio.PRIORITY_REGISTRY[name](cls, nodes, None) * weight
    if "policy_score" in cls:
        # Policy-configured NodeLabel / ServiceAntiAffinity priorities
        # (weights pre-folded; ops/policy_algos.py)
        static_score = static_score + cls["policy_score"]
    tt_cnt = jnp.einsum("ct,nt->cn", cls["intolerated_pref"],
                        nodes["taints_pref"].astype(jnp.int8),
                        preferred_element_type=jnp.int32) \
        if any(nm == "TaintTolerationPriority" for nm, _ in priorities) \
        else jnp.zeros((c, n), dtype=jnp.int32)
    na_cnt = prio.node_affinity_counts(cls, nodes["labels"]) \
        if any(nm == "NodeAffinityPriority" for nm, _ in priorities) \
        else jnp.zeros((c, n), dtype=jnp.int32)
    return {"static_fit": preds.static_fits(cls, nodes),
            "static_score": static_score, "tt_cnt": tt_cnt, "na_cnt": na_cnt}


precompute_jit = jax.jit(precompute, static_argnames=("priorities",))


def _wave_scores(cls: Arrays, nodes: Arrays, state: NodeState,
                 pre: Arrays, fits: jnp.ndarray,
                 priorities: Tuple[Tuple[str, int], ...],
                 col=None) -> jnp.ndarray:
    """Weighted priority sum [C,N] against the frozen state; identical
    per-node integer formulas as the strict path (batch._step_scores).
    `col` carries the node-axis reductions (the reduce-priority maxima) so
    the sharded trace reduces two-stage (ISSUE 12)."""
    if col is None:
        col = _GlobalCol(nodes["alloc"].shape[0])
    total = pre["static_score"]
    alloc = nodes["alloc"]
    for name, weight in priorities:
        if name == "LeastRequestedPriority":
            s = prio.least_requested(cls["nonzero"], state.nonzero, alloc)
        elif name == "MostRequestedPriority":
            s = prio.most_requested(cls["nonzero"], state.nonzero, alloc)
        elif name == "BalancedResourceAllocation":
            s = prio.balanced_allocation(cls["nonzero"], state.nonzero, alloc)
        elif name == "TaintTolerationPriority":
            cnt = pre["tt_cnt"]
            masked = jnp.where(fits, cnt, 0)
            mx = col.row_max(masked, keepdims=True)
            s = jnp.where(mx == 0, MAX_PRIORITY,
                          (MAX_PRIORITY * (mx - cnt)) // jnp.maximum(mx, 1))
        elif name == "NodeAffinityPriority":
            cnt = pre["na_cnt"]
            masked = jnp.where(fits, cnt, 0)
            mx = col.row_max(masked, keepdims=True)
            s = jnp.where(mx > 0, (MAX_PRIORITY * cnt) // jnp.maximum(mx, 1), 0)
        else:  # static and host-only priorities are in pre["static_score"]
            continue
        total = total + s * weight
    return total


def _class_capacity(cls: Arrays, nodes: Arrays, state: NodeState) -> jnp.ndarray:
    """cap[C,N]: how many MORE pods of class c fit on node n, by exact
    integer division per resource column (mirrors resources_fit semantics,
    including the overlay->scratch fallback and the zero-request early-exit
    of predicates.go:576-604) plus the allowed-pod-number ceiling. Division
    keeps everything in int32 with no long-prefix cumsums."""
    alloc = nodes["alloc"]
    rem = alloc - state.requested  # [N,R]
    req = cls["req"]  # [C,R]

    def col_cap(rem_col, req_col):  # [N],[C] -> [C,N]
        r = jnp.maximum(req_col, 1)[:, None]
        cap = jnp.maximum(rem_col, 0)[None, :] // r
        return jnp.where(req_col[:, None] > 0, cap, _BIG)

    plain_cols = [0, 1, 2] + list(range(NUM_BASE_RESOURCES, alloc.shape[1]))
    cap = _BIG * jnp.ones((req.shape[0], alloc.shape[0]), dtype=jnp.int32)
    for col in plain_cols:
        cap = jnp.minimum(cap, col_cap(rem[:, col], req[:, col]))
    # storage special case (predicates.go:590-604)
    no_ov = alloc[:, R_OVERLAY] == 0  # [N]
    scr_rem = jnp.where(no_ov,
                        alloc[:, R_SCRATCH] - state.requested[:, R_SCRATCH]
                        - state.requested[:, R_OVERLAY],
                        rem[:, R_SCRATCH])
    scr_add = jnp.where(no_ov[None, :],
                        (req[:, R_SCRATCH] + req[:, R_OVERLAY])[:, None],
                        req[:, R_SCRATCH][:, None])  # [C,N]
    scr_cap = jnp.where(scr_add > 0,
                        jnp.maximum(scr_rem, 0)[None, :]
                        // jnp.maximum(scr_add, 1), _BIG)
    cap = jnp.minimum(cap, scr_cap)
    ov_cap = jnp.where(no_ov[None, :], _BIG,
                       col_cap(rem[:, R_OVERLAY], req[:, R_OVERLAY]))
    cap = jnp.minimum(cap, ov_cap)
    cap = jnp.where(cls["zero_req"][:, None], _BIG, cap)
    count_cap = jnp.maximum(nodes["allowed_pods"] - state.pod_count, 0)
    return jnp.minimum(cap, count_cap[None, :])


# per-wave per-node acceptance window; bounds rank*request products so all
# acceptance math stays exact in int32 (see _rank_scores overflow analysis)
K_WAVE = 4096


def _dyn_at(total_cpu: jnp.ndarray, total_mem: jnp.ndarray,
            cap_cpu: jnp.ndarray, cap_mem: jnp.ndarray,
            priorities: Tuple[Tuple[str, int], ...]) -> jnp.ndarray:
    """Utilization-dependent priority sum for per-row totals (any shape).
    Mirrors least_requested/most_requested/balanced_allocation exactly."""
    out = jnp.zeros_like(total_cpu)
    for name, weight in priorities:
        if name == "LeastRequestedPriority":
            s = (prio._unused_score(total_cpu, cap_cpu)
                 + prio._unused_score(total_mem, cap_mem)) // 2
        elif name == "MostRequestedPriority":
            s = (prio._used_score(total_cpu, cap_cpu)
                 + prio._used_score(total_mem, cap_mem)) // 2
        elif name == "BalancedResourceAllocation":
            s = prio._balanced_score(total_cpu, total_mem, cap_cpu, cap_mem)
        else:
            continue
        out = out + s * weight
    return out


def _wave_aff_mask(aff: Arrays, committed: jnp.ndarray) -> jnp.ndarray:
    """Per-wave required-anti-affinity mask [C, N] from the PER-NODE
    occupancy carry (ISSUE 3). Wave-eligible anti classes have singleton
    topology domains (AffinityData.wave_strict routes everything else to
    the seeded strict tail), so domain occupancy IS per-node occupancy —
    the mask never touches the label axis, whose width scales with the
    cluster when hostname keys are interned (a [C, L] form here cost
    ~100x at 5k nodes). A node n is forbidden for class c when it
    carries (a) a static forbid (existing pods' matching
    anti terms — precomputed [C, N] at encoding build), (b) a committed
    pod matching one of c's own required anti terms whose key n has, or
    (c) a committed pod of class d whose anti term matches c (the
    symmetry direction, predicates.go:1146) under a key n has.
    key_node[c, a, n] = node n has term (c, a)'s topology key — the
    singleton-domain analog of the keymask."""
    m_anti = aff["m_anti"].astype(jnp.int32)           # [C, A, C]
    kn = aff["key_node"].astype(jnp.int32)             # [C, A, N]
    # own anti: committed pods matching (c, a) resident on n, key present
    occ = jnp.einsum("cad,dn->can", m_anti, committed)
    own = (occ * kn).sum(axis=1)                       # [C, N]
    # symmetry: committed pods of class d at n whose term a matches c
    sym = jnp.einsum("dac,dan->cn", m_anti, kn * committed[:, None, :])
    forb = own + sym + aff["static_forbid"].astype(jnp.int32)
    return forb == 0


def _wave_once(cls: Arrays, nodes: Arrays, state: NodeState,
               pre: Arrays, pod_class: jnp.ndarray, active: jnp.ndarray,
               counter: jnp.ndarray,
               priorities: Tuple[Tuple[str, int], ...],
               aff: Arrays = None,
               committed: jnp.ndarray = None,
               col=None,
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                          NodeState, jnp.ndarray, jnp.ndarray]:
    """One wave (pure traceable body — jitted standalone as wave_step and
    iterated on device by waves_loop). `pre` carries the hoisted
    state-independent tensors (see precompute). With `aff` given, the
    required-anti mask is re-evaluated against the per-node occupancy
    carry each wave and commits update it (the on-device topology
    AssumePod — ISSUE 3). `col` is the node-axis collectives vtable
    (ISSUE 12): _GlobalCol preserves the single-device trace exactly;
    _ShardCol (inside waves_loop's shard_map) makes every node-axis
    reduction/gather/scatter a two-stage per-shard form. Returns
    (selected [P] (-1 = no fit), accepted [P] bool, fit_count [P] int32,
    new state, new counter, new committed). `selected` always carries
    GLOBAL node indices, whichever col runs."""
    P = pod_class.shape[0]
    if col is None:
        col = _GlobalCol(nodes["alloc"].shape[0])
    iota = jnp.arange(P, dtype=jnp.int32)

    # conditions fresh per dispatch (NOT from pre): the cached precompute
    # survives node kills/flaps/cordons/respawns since ISSUE 8, so the
    # liveness verdict must come from the nodes dict of THIS dispatch
    fits = pre["static_fit"] & preds.node_condition_fit(cls, nodes) \
        & _dynamic_fits(cls, nodes, state)  # [C,N]
    if aff is not None:
        fits = fits & _wave_aff_mask(aff, committed)
    fitcnt = col.row_sum(fits).astype(jnp.int32)  # [C]
    scores = _wave_scores(cls, nodes, state, pre, fits, priorities, col=col)
    masked = jnp.where(fits, scores, jnp.int32(-1))
    best = col.row_max(masked, keepdims=True)
    ties = (masked == best) & fits  # [C,N]
    m = col.row_sum(ties).astype(jnp.int32)  # [C] global tie count

    fc = fitcnt[pod_class]  # [P]
    # FIFO draw from the shared RR counter (selectHost counter discipline)
    multi = active & (fc > 1)
    draw = counter.astype(jnp.int32) + jnp.cumsum(multi.astype(jnp.int32)) \
        - multi.astype(jnp.int32)
    mz = jnp.maximum(m[pod_class], 1)
    kz = (draw % mz).astype(jnp.int32)
    # the winner reduce: kz-th tie of each pod's class, ascending node
    # order (local rank + cross-shard prefix under _ShardCol)
    sel_multi = col.tie_select(ties, pod_class, kz)
    sel_single = col.first_fit(fits)[pod_class]
    sel = jnp.where(~active | (fc == 0), jnp.int32(-1),
                    jnp.where(fc == 1, sel_single, sel_multi))
    new_counter = counter + multi.sum().astype(jnp.uint32)

    # ---- per-node FIFO conflict resolution --------------------------------
    placeable = sel >= 0
    key = jnp.where(placeable, sel, col.n_global) * P + iota  # unique,
    # segment-sorted
    order = jnp.argsort(key)
    s_sel = sel[order]
    s_class = pod_class[order]
    s_place = placeable[order]
    seg_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), s_sel[1:] != s_sel[:-1]])
    bs = jax.lax.cummax(jnp.where(seg_start, iota, 0))  # segment-start index
    rank_in_seg = iota - bs
    first_class = s_class[bs]
    same_run = jnp.cumsum((s_class != first_class).astype(jnp.int32))
    same_run = (same_run - same_run[bs]) == 0  # prefix run of first class
    cap = _class_capacity(cls, nodes, state)  # [C,N]
    safe_sel = jnp.maximum(s_sel, 0)
    cap_lim = jnp.minimum(col.take2(cap, s_class, safe_sel), K_WAVE)
    special_cls = ((cls["ports"][:, 0] >= 0)
                   | (cls["vol_hard"].sum(axis=1) + cls["vol_ro"].sum(axis=1)
                      + cls["pd_req"].sum(axis=1) > 0))
    if aff is not None:
        # self-anti classes commit at most one pod per node per wave: the
        # second pod of the same FIFO run would land in a domain its first
        # just made forbidden (singleton domains make per-node the exact
        # granularity; AffinityData.wave_gate). The specials' port/volume
        # scatters below are no-ops for these classes (no ports, no vols).
        special_cls = special_cls | aff["wave_gate"]
    special = special_cls[s_class]
    # score-aware window: node score after r commits of this class must stay
    # >= the frozen runner-up (max score over non-tie nodes). Overflow-safe:
    # r_eff*nz is bounded either by cap (r*req <= alloc per resources_fit)
    # or by K_WAVE * the nonzero defaults (~8.4e8 < 2^31).
    thr = col.row_max(jnp.where(ties, jnp.int32(-1), masked))  # [C]
    r_eff = jnp.minimum(rank_in_seg, cap_lim)
    nz_z = cls["nonzero"][s_class]  # [P,2]
    nz_node = col.take_rows(state.nonzero, safe_sel)
    alloc_rows = col.take_rows(nodes["alloc"], safe_sel)
    tot0 = nz_node + nz_z
    tot_r = nz_node + (r_eff[:, None] + 1) * nz_z
    dyn0 = _dyn_at(tot0[:, 0], tot0[:, 1], alloc_rows[:, 0], alloc_rows[:, 1],
                   priorities)
    dyn_r = _dyn_at(tot_r[:, 0], tot_r[:, 1], alloc_rows[:, 0],
                    alloc_rows[:, 1], priorities)
    score_r = col.take2(masked, s_class, safe_sel) - dyn0 + dyn_r
    acc_core = (s_place & same_run & (rank_in_seg < cap_lim)
                & (~special | (rank_in_seg == 0))
                & ((rank_in_seg == 0) | (score_r >= thr[s_class])))
    # prefix closure: rank r commits only if ranks 0..r-1 all did (the rank/
    # capacity math above assumes the accepted set is a contiguous prefix;
    # BalancedResourceAllocation is not monotone in r, so enforce explicitly)
    fail = (~acc_core).astype(jnp.int32)
    pre_fail = jnp.cumsum(fail) - fail  # failures strictly before each row
    acc_s = acc_core & ((pre_fail - pre_fail[bs]) == 0)
    accepted = jnp.zeros(P, dtype=bool).at[order].set(acc_s)

    # ---- commit (batched AssumePod) ---------------------------------------
    # scatter ids translate to LOCAL rows under _ShardCol (drop sentinel =
    # local width): each accepted row lands on exactly the shard owning its
    # node — the "one shard written per commit" half of the delta story
    nl = col.n_local
    seg_ids = col.to_local(jnp.where(acc_s, s_sel, -1))
    gain = acc_s.astype(jnp.int32)
    add_req = jax.ops.segment_sum(cls["req"][s_class] * gain[:, None],
                                  seg_ids, num_segments=nl + 1)[:nl]
    add_nz = jax.ops.segment_sum(cls["nonzero"][s_class] * gain[:, None],
                                 seg_ids, num_segments=nl + 1)[:nl]
    add_cnt = jax.ops.segment_sum(gain, seg_ids, num_segments=nl + 1)[:nl]
    requested = state.requested + add_req
    nonzero = state.nonzero + add_nz
    pod_count = state.pod_count + add_cnt
    # specials: at most one accepted per node -> direct batched scatters
    sp = acc_s & special
    sp_gain = sp.astype(jnp.int32)
    sp_sel = col.to_local(jnp.where(sp, s_sel, -1))
    ports = cls["ports"][s_class]  # [P,8]
    want = (ports >= 0) & sp[:, None]
    wsafe = jnp.maximum(ports, 0)
    words = jnp.where(want, wsafe // 32, state.port_bitmap.shape[1])
    bits = jnp.where(want, jnp.uint32(1) << (wsafe % 32).astype(jnp.uint32),
                     jnp.uint32(0))
    port_bitmap = state.port_bitmap.at[
        sp_sel[:, None], words].add(bits, mode="drop")
    vh = cls["vol_hard"][s_class]
    vr = cls["vol_ro"][s_class]
    pdq = cls["pd_req"][s_class]
    sp8 = sp[:, None].astype(jnp.int8)
    vol_present = state.vol_present.at[sp_sel].max((vh | vr) * sp8,
                                                   mode="drop")
    vol_rw = state.vol_rw.at[sp_sel].max(vh * sp8, mode="drop")
    pd_present = state.pd_present.at[sp_sel].max(pdq * sp8, mode="drop")
    # distinct new PD ids the pod brings to its node, per kind
    pd_new = []
    for k in range(3):
        req_k = pdq * nodes["pd_kind"][k][None, :]
        overlap = jnp.einsum("pv,pv->p", req_k.astype(jnp.int32),
                             col.take_rows(state.pd_present,
                                           safe_sel).astype(jnp.int32))
        pd_new.append(cls["pd_req_count"][s_class, k] - overlap)
    pd_counts = state.pd_counts.at[sp_sel].add(
        jnp.stack(pd_new, axis=1) * sp_gain[:, None], mode="drop")

    new_state = NodeState(requested, nonzero, pod_count, port_bitmap,
                          vol_present, vol_rw, pd_present, pd_counts)
    if aff is not None:
        # topology-occupancy commit: each accepted pod ticks its (class,
        # node) cell, making it visible to the NEXT wave's mask (and to
        # the seeded strict tail / harvest fence afterwards). Scatter-add
        # accumulates duplicate (class, node) pairs; rejected rows land on
        # the dropped column.
        committed = committed.at[
            s_class, col.to_local(jnp.where(acc_s, s_sel, -1))].add(
                gain, mode="drop")
    return sel, accepted, fc, new_state, new_counter, committed


@functools.partial(jax.jit, static_argnames=("priorities",))
def wave_step(cls, nodes, state, pod_class, active, counter, priorities):
    """Standalone single wave (tests/debugging); waves_loop is the fast path."""
    pre = precompute(cls, nodes, priorities)
    return _wave_once(cls, nodes, state, pre, pod_class, active, counter,
                      priorities)[:5]


def _waves_loop_inner(cls, nodes, state, pod_class, counter, pre,
                      committed0, active0, aff, priorities, max_waves, col):
    """The wave iteration proper — shared verbatim by the single-program
    path and the shard_map SPMD path (the `col` vtable is the only
    difference). Returns (packed, state, committed)."""
    P = pod_class.shape[0]

    def cond(carry):
        _, active, _, _, _, _, w = carry
        return (w < max_waves) & active.any()

    def body(carry):
        state, active, counter, fsel, ffc, committed, w = carry
        sel, accepted, fc, state2, counter2, committed2 = _wave_once(
            cls, nodes, state, pre, pod_class, active, counter, priorities,
            aff=aff, committed=committed, col=col)
        if aff is None:
            committed2 = committed
        placed = active & accepted
        fsel = jnp.where(placed, sel, fsel)
        ffc = jnp.where(active, fc, ffc)
        active2 = active & ~accepted & (sel >= 0)
        return (state2, active2, counter2, fsel, ffc, committed2, w + 1)

    init = (state, active0, counter,
            jnp.full(P, -1, dtype=jnp.int32), jnp.zeros(P, dtype=jnp.int32),
            committed0, jnp.int32(0))
    (state, active, counter, fsel, ffc, committed, w) = \
        lax.while_loop(cond, body, init)
    packed = jnp.concatenate([fsel, ffc, active.astype(jnp.int32),
                              counter.astype(jnp.int32)[None], w[None]])
    return packed, state, committed


@functools.partial(jax.jit, static_argnames=("weights",))
def frozen_affinity_scores(cls: Arrays, nodes: Arrays, state: NodeState,
                           aff: Arrays,
                           weights: Tuple[int, int]) -> jnp.ndarray:
    """SelectorSpread / InterPodAffinity scores [C, N] against the
    batch-frozen cluster state, for the wave engine's additive static score
    (weights = (w_interpod, w_spread)). Wave semantics score these once per
    BATCH, not per wave — within-batch drift of preferred-affinity/spread
    counts is a documented wave-mode approximation that also applies to
    required-(anti-)affinity classes riding the waves (ISSUE 3) — only the
    REQUIRED fit side is re-evaluated per wave; the preferred score stays
    batch-frozen. Pure int32 — no x64 required."""
    from kubernetes_tpu.ops import affinity as aff_ops

    w_ip, w_sp = weights
    fits = preds.static_fits(cls, nodes) \
        & preds.node_condition_fit(cls, nodes) \
        & _dynamic_fits(cls, nodes, state)
    extra = jnp.zeros(fits.shape, dtype=jnp.int32)
    if w_ip:
        # jnp einsum, not the Pallas incidence kernel: this matrix is also
        # computed with the node axis sharded over a mesh (test_mesh.py),
        # and a pallas_call is a custom call the SPMD partitioner cannot
        # split. The single-chip evaluate_pod path uses the kernel.
        # labels_aff (when present) is the projected domain incidence the
        # caller's aff arrays are sliced to (engine _aff_tail_arrays).
        lab = aff["labels_aff"] if "labels_aff" in aff else nodes["labels"]
        pre = aff_ops.precompute_static(aff, lab)
        extra = extra + w_ip * aff_ops.interpod_score(pre["prio_counts"],
                                                      fits)
    if w_sp:
        extra = extra + w_sp * aff_ops.spread_score(
            aff, aff["sp_has"], aff["sp_static"], fits)
    return extra


@functools.partial(jax.jit,
                   static_argnames=("priorities", "max_waves", "spmd_mesh"))
def waves_loop(cls: Arrays, nodes: Arrays, state: NodeState,
               pod_class: jnp.ndarray, counter: jnp.ndarray,
               priorities: Tuple[Tuple[str, int], ...],
               max_waves: int = 32,
               extra_score: jnp.ndarray = None,
               aff: Arrays = None,
               committed0: jnp.ndarray = None,
               active0: jnp.ndarray = None,
               pre: Arrays = None,
               spmd_mesh=None,
               ) -> Union[Tuple[jnp.ndarray, NodeState],
                          Tuple[jnp.ndarray, NodeState, jnp.ndarray]]:
    """The whole wave iteration as ONE device program (lax.while_loop over
    _wave_once) — a single dispatch + a single [3P+2] host fetch regardless
    of wave count; a host round trip per wave would add a device sync per
    wave to the kernel time.

    With `aff` (ISSUE 3): committed0 seeds the [C, N] per-node topology
    occupancy carry (the engine's cumulative fence-accepted commits, so
    earlier chunks' placements are visible) and the per-wave mask +
    occupancy commit run inside the loop; active0 masks out pods routed to
    the seeded strict tail (AffinityData.wave_strict) — they exit with
    selected = -1 and still_active = 0 and the harvest places them.

    With `spmd_mesh` (a jax.sharding.Mesh whose one axis is the node
    axis — ISSUE 12), the WHOLE loop runs under shard_map: every
    node-axis tensor stays resident on its shard, the winner selection is
    the explicit two-stage reduce of _ShardCol, and commits write exactly
    the shard owning each node. Placements are bit-identical to the
    single-program run (the vtable swaps op implementations, never
    semantics); pass None (default) everywhere a mesh is not resident.

    Returns (packed, final state[, committed]) with packed =
    [selected(P), fit_count(P), still_active(P), counter, waves_used];
    still_active pods exhausted max_waves (the host finishes them via the
    strict scan). The trailing occupancy is returned only when `aff` is
    given."""
    P = pod_class.shape[0]
    if pre is None:  # hoisted: while_loop bodies re-execute everything
        # every iteration and XLA cannot hoist for us; callers draining
        # many chunks pass the per-encoding cached instance instead
        pre = precompute(cls, nodes, priorities)
    if extra_score is not None:  # batch-frozen spread/interpod scores
        pre = dict(pre, static_score=pre["static_score"] + extra_score)
    if aff is not None:
        committed0 = committed0.astype(jnp.int32)
    else:  # inert carry keeps ONE loop structure for both trace variants
        committed0 = jnp.zeros((1, 1), dtype=jnp.int32)
    if active0 is None:
        active0 = jnp.ones(P, dtype=bool)
    n_global = nodes["alloc"].shape[0]
    if spmd_mesh is None:
        col = _GlobalCol(n_global)
        packed, state, committed = _waves_loop_inner(
            cls, nodes, state, pod_class, counter, pre, committed0,
            active0, aff, priorities, max_waves, col)
    else:
        packed, state, committed = _waves_loop_spmd(
            cls, nodes, state, pod_class, counter, pre, committed0,
            active0, aff, priorities, max_waves, spmd_mesh)
    if aff is None:
        return packed, state
    return packed, state, committed


def _waves_loop_spmd(cls, nodes, state, pod_class, counter, pre,
                     committed0, active0, aff, priorities, max_waves,
                     mesh):
    """waves_loop's shard_map wrapper: node-axis operands enter sharded
    (specs from parallel/mesh's shared tables), pod-side operands enter
    replicated, and _waves_loop_inner runs per shard with _ShardCol
    supplying the cross-device stages. check_vma is off: the replication
    checker cannot see through the ownership-masked psum combines, but
    every P()-spec output is replicated by construction (psum/pmax
    results and replicated-input math only)."""
    from jax.sharding import PartitionSpec as PS

    from kubernetes_tpu.parallel.mesh import (
        _NODE_SHARDED_KEYS,
        aff_spec,
    )

    axis = mesh.axis_names[0]
    n_global = nodes["alloc"].shape[0]
    n_dev = int(mesh.devices.size)
    col = _ShardCol(axis, n_global, n_global // n_dev)
    node_sp = PS(axis)
    rep = PS()

    def nspec(k):
        return node_sp if k in _NODE_SHARDED_KEYS else rep

    nodes_spec = {k: nspec(k) for k in nodes}
    state_spec = NodeState(*([node_sp] * len(state)))
    pre_spec = {k: PS(None, axis) for k in pre}
    cls_spec = {k: rep for k in cls}
    comm_spec = PS(None, axis) if aff is not None else rep
    args = [cls, nodes, state, pod_class, counter, committed0, active0]
    in_specs = [cls_spec, nodes_spec, state_spec, rep, rep, comm_spec, rep]
    # pre/aff ride as operands (shard_map forbids closed-over tracers)
    args.append(pre)
    in_specs.append(pre_spec)
    has_aff = aff is not None
    if has_aff:
        args.append(aff)
        in_specs.append({k: aff_spec(k) for k in aff})

    def inner(cls_, nodes_, state_, pc_, ctr_, comm_, act_, pre_,
              *maybe_aff):
        aff_ = maybe_aff[0] if maybe_aff else None
        return _waves_loop_inner(cls_, nodes_, state_, pc_, ctr_, pre_,
                                 comm_, act_, aff_, priorities, max_waves,
                                 col)

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=tuple(in_specs),
                         out_specs=(rep, state_spec, comm_spec),
                         check_vma=False)(*args)


@functools.partial(jax.jit,
                   static_argnames=("priorities", "aff_mode", "spmd_mesh"))
def tail_rounds_loop(cls: Arrays, nodes: Arrays, state: NodeState,
                     pod_class: jnp.ndarray, counter: jnp.ndarray,
                     priorities: Tuple[Tuple[str, int], ...],
                     aff: Arrays = None,
                     aff_mode: Tuple[bool, bool, bool] = (False, False, False),
                     aff_init=None,
                     pre: Arrays = None,
                     spmd_mesh=None,
                     ) -> Tuple[jnp.ndarray, NodeState]:
    """The seeded strict tail as CONFLICT ROUNDS — one device program
    whose sequential depth is the number of rounds (a handful), not the
    number of tail pods (hundreds), with required-(anti-)affinity
    semantics EXACT at every commit.

    The per-pod scan (engine/batch.place_batch, still reachable via
    GRAFT_TAIL_ROUNDS=0) serializes the whole tail to keep two things
    exact: the affinity occupancy each pod evaluates against, and the
    classic one-at-a-time tie-break order. Only the first is a
    CONSTRAINT; the second is the same tie-spreading freedom every
    wave-mode class already trades away (batch-defined RR fan-out
    instead of the classic serialized order). So each round:

      1. re-evaluates the REQUIRED mask for every class exactly against
         the cumulative occupancy carry (ops/affinity.step_fits_all over
         the projected domain columns — allow side, own anti, the
         symmetry direction, and the lone-bootstrap rule, bit-identical
         per class to the scan's per-step mask), plus exact capacity
         predicates and scores;
      2. places every still-active pod wave-style: FIFO prefix RR draws
         over the per-class tie sets, per-node FIFO conflict resolution
         with exact integer capacity and the score-aware window (the
         _wave_once discipline);
      3. gates the commits whose own effects the round-start mask cannot
         see: a class still BOOTSTRAPPING an allow-side group (no static
         or committed match yet) commits at most ONE pod per round — the
         group picks its domain serially, then fans out — and classes
         coupled through any required ANTI term (as source or target,
         m_aff is monotone-benign but m_anti is not) commit at most one
         pod per round ACROSS the whole coupled pool, so a commit can
         never invalidate a same-round placement made under the stale
         mask. Allow-satisfied, anti-free classes fan out freely: their
         masks can only widen as the round's commits land.
      4. retires placed pods; fit_count==0 pods stay active while ANY
         commit lands (an allow-side commit may widen their mask — the
         scan's order-dependent schedulability, reproduced round-
         granular) and retire as unschedulable the first round nothing
         commits, which is also the loop exit.

    Every round with a placeable pod commits at least one (the first
    active pod survives per-node rank-0 resolution and every quota), so
    the loop terminates in <= P+1 rounds; the typical mixed-affinity
    tail is one bootstrap round per co-location group plus one or two
    fan-out rounds. Placements stay deterministic — the pipelined ==
    sequential (overlap=False) A/B holds bit-exactly — but tie-breaks
    follow wave semantics, the same documented divergence as every
    other wave-path class. Spread scoring is not modeled here (the
    harvest tail never runs it).

    With the node axis sharded over `spmd_mesh` (the engine's resident
    mesh), the program is partitioned by GSPMD; the mesh is passed down
    only so the capacity kernel can run per shard.

    Returns (packed, final NodeState) with packed =
    [selected(P), fit_count(P), counter, rounds_used]."""
    from kubernetes_tpu.engine.batch import check_affinity_priorities
    from kubernetes_tpu.ops import affinity as aff_ops

    fits_on, prio_on, spread_on = aff_mode
    if spread_on:
        raise ValueError("tail_rounds_loop does not model spread scoring "
                         "(the harvest tail runs with spread off)")
    check_affinity_priorities(priorities, aff, None)
    any_aff = aff is not None and (fits_on or prio_on)
    P = pod_class.shape[0]
    N = nodes["alloc"].shape[0]
    C = cls["req"].shape[0]
    iota = jnp.arange(P, dtype=jnp.int32)
    idx_n = jnp.arange(N, dtype=jnp.int32)
    if pre is None:
        pre = precompute(cls, nodes, priorities)
    w_ip = sum(w for nm, w in priorities
               if nm == "InterPodAffinityPriority") if prio_on else 0
    if any_aff:
        labels = aff["labels_aff"] if "labels_aff" in aff \
            else nodes["labels"]
        pre_aff = aff_ops.precompute_static(aff, labels)
        l_dim = labels.shape[1]
        # anti-coupled pool: classes that appear in ANY required anti
        # relation, as matching target or term owner — their commits can
        # shrink a same-round mask, so the pool shares one commit quota
        m_anti_b = aff["m_anti"].astype(bool)
        anti_pool = m_anti_b.any(axis=(1, 2)) | m_anti_b.any(axis=(0, 1))
        boot_candidate = (aff["aff_active"] & ~aff["aff_has_static"])
    else:
        labels = jnp.zeros((N, 1), dtype=jnp.int8)
        pre_aff = None
        l_dim = 1
        anti_pool = jnp.zeros(C, dtype=bool)
        boot_candidate = None
    if aff_init is not None:
        commdom0, committed0, comm_cnt0 = aff_init
        commdom0 = commdom0.astype(jnp.int32)
        committed0 = committed0.astype(jnp.int32)
        comm_cnt0 = comm_cnt0.astype(jnp.int32)
    else:
        commdom0 = jnp.zeros((C, l_dim), dtype=jnp.int32)
        committed0 = jnp.zeros((C, N), dtype=jnp.int32)
        comm_cnt0 = jnp.zeros(C, dtype=jnp.int32)
    special_base = ((cls["ports"][:, 0] >= 0)
                    | (cls["vol_hard"].sum(axis=1) + cls["vol_ro"].sum(axis=1)
                       + cls["pd_req"].sum(axis=1) > 0))

    def cond(carry):
        active = carry[1]
        w = carry[-1]
        return active.any() & (w <= P)

    def body(carry):
        (state, active, counter, fsel, ffc, commdom, committed,
         comm_cnt, w) = carry
        # ---- exact round-start evaluation, class-level [C, N] -----------
        fits_c = pre["static_fit"] & preds.node_condition_fit(cls, nodes) \
            & _dynamic_fits(cls, nodes, state, spmd_mesh)
        if fits_on:
            fits_c = fits_c & aff_ops.step_fits_all(aff, pre_aff, commdom,
                                                    comm_cnt, labels)
        scores_c = _wave_scores(cls, nodes, state, pre, fits_c, priorities)
        if prio_on:
            cnt = aff_ops.step_prio_counts_all(aff, pre_aff, commdom,
                                               labels)
            scores_c = scores_c + w_ip * aff_ops.interpod_score(cnt, fits_c)
        # ---- wave-style selection (the _wave_once discipline) -----------
        # NOTE: steps 2/4 below mirror _wave_once's tie-selection, per-node
        # FIFO conflict resolution, score window, and commit scatters with
        # only the fits source and the round-quota gate differing. A fix
        # to the acceptance math there (K_WAVE analysis, prefix closure,
        # port/volume scatters) must be applied HERE too — the tail and
        # the wave loop are tested to agree on those semantics.
        fitcnt = fits_c.sum(axis=1).astype(jnp.int32)
        masked = jnp.where(fits_c, scores_c, jnp.int32(-1))
        best = masked.max(axis=1, keepdims=True)
        ties = (masked == best) & fits_c
        m = ties.sum(axis=1).astype(jnp.int32)
        rank = jnp.cumsum(ties.astype(jnp.int32), axis=1) - 1
        cols = jnp.where(ties, rank, N)
        rows = jnp.broadcast_to(jnp.arange(ties.shape[0])[:, None],
                                ties.shape)
        tiemat = jnp.zeros(ties.shape, dtype=jnp.int32).at[rows, cols].set(
            jnp.broadcast_to(idx_n[None, :], ties.shape), mode="drop")
        fc = fitcnt[pod_class]
        multi = active & (fc > 1)
        draw = counter.astype(jnp.int32) \
            + jnp.cumsum(multi.astype(jnp.int32)) - multi.astype(jnp.int32)
        mz = jnp.maximum(m[pod_class], 1)
        kz = (draw % mz).astype(jnp.int32)
        sel_multi = tiemat[pod_class, kz]
        sel_single = jnp.argmax(fits_c, axis=1).astype(jnp.int32)[pod_class]
        sel = jnp.where(~active | (fc == 0), jnp.int32(-1),
                        jnp.where(fc == 1, sel_single, sel_multi))
        new_counter = counter + multi.sum().astype(jnp.uint32)
        # ---- per-node FIFO conflict resolution --------------------------
        placeable = sel >= 0
        key = jnp.where(placeable, sel, N) * P + iota
        order = jnp.argsort(key)
        s_sel = sel[order]
        s_class = pod_class[order]
        s_place = placeable[order]
        seg_start = jnp.concatenate(
            [jnp.ones(1, dtype=bool), s_sel[1:] != s_sel[:-1]])
        bs = jax.lax.cummax(jnp.where(seg_start, iota, 0))
        rank_in_seg = iota - bs
        first_class = s_class[bs]
        same_run = jnp.cumsum((s_class != first_class).astype(jnp.int32))
        same_run = (same_run - same_run[bs]) == 0
        cap = _class_capacity(cls, nodes, state)
        safe_sel = jnp.maximum(s_sel, 0)
        cap_lim = jnp.minimum(cap[s_class, safe_sel], K_WAVE)
        special = special_base[s_class]
        thr = jnp.where(ties, jnp.int32(-1), masked).max(axis=1)
        r_eff = jnp.minimum(rank_in_seg, cap_lim)
        nz_z = cls["nonzero"][s_class]
        nz_node = state.nonzero[safe_sel]
        alloc_rows = nodes["alloc"][safe_sel]
        tot0 = nz_node + nz_z
        tot_r = nz_node + (r_eff[:, None] + 1) * nz_z
        dyn0 = _dyn_at(tot0[:, 0], tot0[:, 1], alloc_rows[:, 0],
                       alloc_rows[:, 1], priorities)
        dyn_r = _dyn_at(tot_r[:, 0], tot_r[:, 1], alloc_rows[:, 0],
                        alloc_rows[:, 1], priorities)
        score_r = masked[s_class, safe_sel] - dyn0 + dyn_r
        acc_core = (s_place & same_run & (rank_in_seg < cap_lim)
                    & (~special | (rank_in_seg == 0))
                    & ((rank_in_seg == 0) | (score_r >= thr[s_class])))
        fail = (~acc_core).astype(jnp.int32)
        pre_fail = jnp.cumsum(fail) - fail
        acc_s = acc_core & ((pre_fail - pre_fail[bs]) == 0)
        accepted = jnp.zeros(P, dtype=bool).at[order].set(acc_s)
        # ---- the round gates (step 3 of the docstring) ------------------
        if any_aff:
            # boot_pending[c]: some active allow term has neither a static
            # nor a committed match — this round's commit IS the group's
            # domain choice, so it must be singular
            dyn_total = jnp.einsum("csd,d->cs",
                                   aff["m_aff"].astype(jnp.int32), comm_cnt)
            boot_pending = (boot_candidate & (dyn_total == 0)).any(axis=1)
            # quota group per class: bootstrapping classes serialize
            # individually (group id = class index); the anti-coupled pool
            # shares ONE group (id = C); everyone else is unquota'd
            qgroup = jnp.where(anti_pool, jnp.int32(C),
                               jnp.where(boot_pending,
                                         jnp.arange(C, dtype=jnp.int32),
                                         jnp.int32(-1)))
            g = qgroup[pod_class]                             # [P]
            member = accepted & (g >= 0)
            oh = (member[:, None]
                  & (g[:, None] == jnp.arange(C + 1, dtype=jnp.int32)[None, :]))
            rank_in_group = jnp.cumsum(oh.astype(jnp.int32), axis=0) \
                - oh.astype(jnp.int32)
            keep = ~member | (rank_in_group[iota, jnp.maximum(g, 0)] == 0)
            accepted = accepted & keep
            acc_s = accepted[order]
        # ---- commit (batched AssumePod, dropped pods stay active) -------
        seg_ids = jnp.where(acc_s, s_sel, N)
        gain = acc_s.astype(jnp.int32)
        add_req = jax.ops.segment_sum(cls["req"][s_class] * gain[:, None],
                                      seg_ids, num_segments=N + 1)[:N]
        add_nz = jax.ops.segment_sum(cls["nonzero"][s_class] * gain[:, None],
                                     seg_ids, num_segments=N + 1)[:N]
        add_cnt = jax.ops.segment_sum(gain, seg_ids, num_segments=N + 1)[:N]
        requested = state.requested + add_req
        nonzero = state.nonzero + add_nz
        pod_count = state.pod_count + add_cnt
        sp = acc_s & special
        sp_gain = sp.astype(jnp.int32)
        sp_sel = jnp.where(sp, s_sel, N)
        ports = cls["ports"][s_class]
        want = (ports >= 0) & sp[:, None]
        wsafe = jnp.maximum(ports, 0)
        words = jnp.where(want, wsafe // 32, state.port_bitmap.shape[1])
        bits = jnp.where(want,
                         jnp.uint32(1) << (wsafe % 32).astype(jnp.uint32),
                         jnp.uint32(0))
        port_bitmap = state.port_bitmap.at[
            jnp.where(sp, s_sel, N)[:, None], words].add(bits, mode="drop")
        vh = cls["vol_hard"][s_class]
        vr = cls["vol_ro"][s_class]
        pdq = cls["pd_req"][s_class]
        sp8 = sp[:, None].astype(jnp.int8)
        vol_present = state.vol_present.at[sp_sel].max((vh | vr) * sp8,
                                                       mode="drop")
        vol_rw = state.vol_rw.at[sp_sel].max(vh * sp8, mode="drop")
        pd_present = state.pd_present.at[sp_sel].max(pdq * sp8, mode="drop")
        pd_new = []
        for k in range(3):
            req_k = pdq * nodes["pd_kind"][k][None, :]
            overlap = jnp.einsum("pv,pv->p", req_k.astype(jnp.int32),
                                 state.pd_present[safe_sel].astype(jnp.int32))
            pd_new.append(cls["pd_req_count"][s_class, k] - overlap)
        pd_counts = state.pd_counts.at[sp_sel].add(
            jnp.stack(pd_new, axis=1) * sp_gain[:, None], mode="drop")
        new_state = NodeState(requested, nonzero, pod_count, port_bitmap,
                              vol_present, vol_rw, pd_present, pd_counts)
        # occupancy carry: committed pods become visible to the NEXT
        # round's exact mask
        sel_safe_p = jnp.maximum(sel, 0)
        gain_p = accepted.astype(jnp.int32)
        commdom = commdom.at[pod_class].add(
            labels[sel_safe_p].astype(jnp.int32) * gain_p[:, None])
        committed = committed.at[
            pod_class, jnp.where(accepted, sel, N)].add(gain_p, mode="drop")
        comm_cnt = comm_cnt.at[pod_class].add(gain_p)
        # ---- retire: placed pods always; fit_count==0 pods only once a
        # round commits nothing (an allow-side commit may still widen
        # their mask) — which is also the loop's natural exit
        none_committed = ~accepted.any()
        retire_unsched = active & (fc == 0) & none_committed
        done = accepted | retire_unsched
        fsel = jnp.where(accepted, sel, fsel)
        ffc = jnp.where(done, fc, ffc)
        return (new_state, active & ~done, new_counter, fsel, ffc,
                commdom, committed, comm_cnt, w + 1)

    init = (state, jnp.ones(P, dtype=bool), counter,
            jnp.full(P, -1, dtype=jnp.int32), jnp.zeros(P, dtype=jnp.int32),
            commdom0, committed0, comm_cnt0, jnp.int32(0))
    (state, _active, counter, fsel, ffc, _cd, _cm, _cc, w) = \
        lax.while_loop(cond, body, init)
    packed = jnp.concatenate([fsel, ffc,
                              counter.astype(jnp.int32)[None], w[None]])
    return packed, state


def place_waves(cls: Arrays, nodes: Arrays, state: NodeState,
                pod_class: np.ndarray, counter: int,
                priorities: Tuple[Tuple[str, int], ...],
                max_waves: int = 64,
                extra_score: jnp.ndarray = None,
                aff: Arrays = None,
                aff_mode: Tuple[bool, bool, bool] = (False, False, False),
                ) -> Tuple[np.ndarray, np.ndarray, NodeState, int]:
    """Run waves until every pod is placed or proven unplaceable — one
    device program (waves_loop) + one host fetch. Returns (selected [P]
    int32 node index or -1, fit_count [P], final NodeState, final counter).
    Each non-empty conflict segment commits at least its first pod per wave,
    so the device loop terminates in <= P waves (typically 1-3)."""
    P = len(pod_class)
    packed, state = waves_loop(cls, nodes, state, jnp.asarray(pod_class),
                               jnp.uint32(counter), priorities, max_waves,
                               extra_score)
    packed_h = np.asarray(packed)  # graftlint: sync-ok — the ONLY
    # blessed device->host sync on the classic wave path: one [3P+2]
    # fetch for the whole drain round, everything before it is one
    # async device program
    final_sel = packed_h[:P].copy()
    final_fc = packed_h[P:2 * P].copy()
    act_h = packed_h[2 * P:3 * P].astype(bool)
    counter_h = int(np.uint32(packed_h[3 * P]))
    if act_h.any():
        # pathological interleaving exhausted max_waves: finish the
        # stragglers strictly. The straggler count is padded to a bucket
        # (inert rows) so this rare path doesn't mint a compile per count.
        idx = np.nonzero(act_h)[0]
        n_strag = len(idx)
        if bool(np.asarray(cls["impossible"][-1])):
            pad_class = cls["req"].shape[0] - 1  # inert padding class row
            pc = np.full(preds.bucket(n_strag), pad_class, dtype=np.int32)
        else:  # caller passed unpadded class arrays: no inert row to map to
            pc = np.empty(n_strag, dtype=np.int32)
        pc[:n_strag] = pod_class[idx]
        # thread the affinity class data through so priorities containing
        # SelectorSpread/InterPodAffinity don't trip place_batch's guard
        # when extra_score is None (fits-only affinity batches)
        sel, fcs, state, counter_d = gather_place_batch(
            cls, jnp.asarray(pc), nodes, state, jnp.uint32(counter_h),
            priorities, aff=aff, aff_mode=aff_mode, extra_score=extra_score)
        # rare straggler finish (max_waves exhausted): a second fetch is
        # the cost of correctness here, not a hot-path stall
        final_sel[idx] = np.asarray(sel)[:n_strag]  # graftlint: sync-ok
        final_fc[idx] = np.asarray(fcs)[:n_strag]  # graftlint: sync-ok
        counter_h = int(counter_d)  # graftlint: sync-ok (scalar, idle)
    return final_sel, final_fc, state, counter_h
