"""Pallas TPU kernels for the hot ops.

The PodFitsResources check (`ops/predicates.py resources_fit`,
reference predicates.go:556-624) is the one [P,N]-shaped op whose jnp
form materializes a [P, N, R] intermediate (`pod_req[:,None,:] +
requested[None,:,:]`): at 30k pods x 5k nodes x 8 resources that is
~4.8 GB of int32 traffic through HBM per wave. XLA usually fuses the
reduction, but the fusion is at the compiler's mercy; this kernel makes
the tiling explicit the Pallas way (pallas_guide.md): grid over
(P, N) tiles, node arrays transposed to [R, N] so each resource row is
a [1, N_BLK] lane vector, the R loop unrolled in-register — the [P,N,R]
cube never exists, each (bp, bn) output tile is produced from one
[bp, R] pod block + two [R, bn] node blocks resident in VMEM.

Semantics are bit-identical to resources_fit (the scratch/overlay
fallback of predicates.go:590-604 included); `resources_fit_fast`
dispatches to the kernel on TPU backends and to the reference jnp path
on the CPU test backend, and the tests pin kernel-vs-jnp equality in
interpret mode. Each dispatch counts its branch and shape at trace time
(`kernel.<op>.<branch>[<shape>]` in utils.trace.COUNTERS), so a run can
show which branch every compiled shape took.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kubernetes_tpu.state.snapshot import R_OVERLAY, R_SCRATCH
from kubernetes_tpu.utils.trace import COUNTERS

P_BLK = 128
N_BLK = 256


def _capacity_kernel(pod_req_ref, alloc_t_ref, req_t_ref, out_ref, *,
                     n_res: int):
    """One (P_BLK, N_BLK) output tile.

    pod_req_ref [P_BLK, Rpad] int32 — pod requests, resource axis last;
    alloc_t_ref / req_t_ref [Rpad, N_BLK] int32 — node arrays TRANSPOSED
    so slicing a resource yields a [1, N_BLK] lane row. The resource loop
    is a Python loop: n_res is static, so it unrolls at trace time into
    n_res fused VPU compare-ands — no [P,N,R] cube.
    """
    # everything stays int32 0/1 — Mosaic (this jax build) cannot place
    # i1 vector intermediates/stores ("Unsupported target bitwidth for
    # truncation"), so AND is multiply and select is arithmetic blend
    ok = None
    for r in range(n_res):
        if r in (R_SCRATCH, R_OVERLAY):
            continue  # handled by the storage special-case below
        total = pod_req_ref[:, r:r + 1] + req_t_ref[r:r + 1, :]
        fit_r = (total <= alloc_t_ref[r:r + 1, :]).astype(jnp.int32)
        ok = fit_r if ok is None else ok * fit_r
    # storage special-case (predicates.go:590-604): no overlay capacity
    # -> overlay requests fall back onto scratch space
    alloc_s = alloc_t_ref[R_SCRATCH:R_SCRATCH + 1, :]
    alloc_o = alloc_t_ref[R_OVERLAY:R_OVERLAY + 1, :]
    node_s = req_t_ref[R_SCRATCH:R_SCRATCH + 1, :]
    node_o = req_t_ref[R_OVERLAY:R_OVERLAY + 1, :]
    pod_s = pod_req_ref[:, R_SCRATCH:R_SCRATCH + 1]
    pod_o = pod_req_ref[:, R_OVERLAY:R_OVERLAY + 1]
    no_overlay = (alloc_o == 0).astype(jnp.int32)  # [1, bn]
    spill_ok = (pod_s + pod_o + node_s + node_o <= alloc_s).astype(jnp.int32)
    plain_ok = (pod_s + node_s <= alloc_s).astype(jnp.int32)
    scratch_ok = no_overlay * spill_ok + (1 - no_overlay) * plain_ok
    overlay_fit = (pod_o + node_o <= alloc_o).astype(jnp.int32)
    overlay_ok = no_overlay + (1 - no_overlay) * overlay_fit
    out_ref[:, :] = ok * scratch_ok * overlay_ok


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    want = ((size + mult - 1) // mult) * mult
    if want == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, want - size)
    return jnp.pad(x, pads)


def capacity_fits_pallas(pod_req: jnp.ndarray, alloc: jnp.ndarray,
                         requested: jnp.ndarray,
                         interpret: bool = False) -> jnp.ndarray:
    """The resource-fit mask [P, N] via the tiled kernel. Zero-padding is
    exact: padded pods request 0 (fit everywhere, rows sliced off), padded
    nodes have alloc 0 (total 0 <= 0 passes, columns sliced off)."""
    p, n_res = pod_req.shape
    n = alloc.shape[0]
    # resource axis padded to the sublane quantum so [Rpad, N_BLK] node
    # blocks tile cleanly; padded resources: 0 + 0 <= 0 -> pass
    r_pad = max(8, ((n_res + 7) // 8) * 8)
    pod_p = _pad_to(_pad_to(pod_req, 1, r_pad), 0, P_BLK)
    alloc_t = _pad_to(_pad_to(alloc, 1, r_pad).T, 1, N_BLK)
    req_t = _pad_to(_pad_to(requested, 1, r_pad).T, 1, N_BLK)
    pp, nn = pod_p.shape[0], alloc_t.shape[1]
    out = pl.pallas_call(
        functools.partial(_capacity_kernel, n_res=n_res),
        out_shape=jax.ShapeDtypeStruct((pp, nn), jnp.int32),
        grid=(pp // P_BLK, nn // N_BLK),
        in_specs=[
            pl.BlockSpec((P_BLK, r_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((r_pad, N_BLK), lambda i, j: (0, j)),
            pl.BlockSpec((r_pad, N_BLK), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((P_BLK, N_BLK), lambda i, j: (i, j)),
        interpret=interpret,
    )(pod_p, alloc_t, req_t)
    return out[:p, :n] != 0


# ---------------------------------------------------------------------------
# topology-incidence matmul (SURVEY §7 phase 2's flagship kernel):
# [C,S,L] x [N,L] -> [C,S,N] — the static affinity hit matrix
# ---------------------------------------------------------------------------

M_BLK = 128
K_BLK = 512


def _incidence_kernel(a_ref, b_ref, o_ref):
    """One (M_BLK, N_BLK) tile of A @ B with the L (contraction) axis
    blocked over the third grid dimension — the canonical Pallas matmul
    shape (pallas_guide.md): zero the accumulator on the first k step,
    accumulate an MXU dot per k block. f32 is exact here: entries are
    0/1 incidences (or small int weights), so every partial sum stays
    far below 2^24."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)
    o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32)


def incidence_matmul_pallas(a: jnp.ndarray, b_t: jnp.ndarray,
                            interpret: bool = False) -> jnp.ndarray:
    """A [M, L] int x B_t [N, L] int -> [M, N] int32, tiled (M,N,L) on
    the MXU. Zero padding is exact (0-rows/cols contribute 0)."""
    m, l = a.shape
    n = b_t.shape[0]
    a_p = _pad_to(_pad_to(a.astype(jnp.float32), 0, M_BLK), 1, K_BLK)
    b_p = _pad_to(_pad_to(b_t.astype(jnp.float32), 0, N_BLK), 1, K_BLK).T
    mm, kk = a_p.shape
    nn = b_p.shape[1]
    out = pl.pallas_call(
        _incidence_kernel,
        out_shape=jax.ShapeDtypeStruct((mm, nn), jnp.float32),
        grid=(mm // M_BLK, nn // N_BLK, kk // K_BLK),
        in_specs=[
            pl.BlockSpec((M_BLK, K_BLK), lambda i, j, k: (i, k)),
            pl.BlockSpec((K_BLK, N_BLK), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((M_BLK, N_BLK), lambda i, j, k: (i, j)),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n].astype(jnp.int32)


def precompute_static_fast(aff, labels: jnp.ndarray,
                           force: Optional[bool] = None,
                           interpret: bool = False):
    """Drop-in for affinity.precompute_static with the [C,S,L]x[N,L]
    allow-hit contraction (and the [C,L] forbid/prio ones, which batch
    into the same call) Pallas-tiled.

    Measured A/B on the real TPU chip (r5; 20-iter steady-state, jitted,
    block_until_ready, parity asserted on device):

        C=8   S=4 L=2048 N=5120   jnp 0.221 ms   pallas 0.044 ms  (5.0x)
        C=64  S=8 L=2048 N=5120   jnp 10.772 ms  pallas 10.658 ms (1.01x)
        C=256 S=8 L=4096 N=5120   jnp 13.108 ms  pallas 12.661 ms (1.04x)

    Stacking the three einsums into ONE tiled matmul dominates at small
    class counts (the common case: density batches have few classes) and
    never loses at large ones — so unlike resources_fit_fast (where the
    measurement said sub-tile shapes lose), the gate here is simply "a
    TPU backend". On the CPU test backend the reference jnp path runs."""
    from kubernetes_tpu.ops.affinity import precompute_static
    c, s, l = aff["aff_allow"].shape
    n = labels.shape[0]
    use = force if force is not None else _on_tpu()
    _count_branch("precompute_static", use, (c, s, l, n))
    if not use:
        return precompute_static(aff, labels)
    # one [C*(S+2), L] stack: allow terms, then forbid, then prio rows —
    # a single tiled matmul instead of three
    stacked = jnp.concatenate([
        aff["aff_allow"].reshape(c * s, l).astype(jnp.int32),
        aff["forbid_static"].astype(jnp.int32),
        aff["prio_static"].astype(jnp.int32)], axis=0)
    hits = incidence_matmul_pallas(stacked, labels.astype(jnp.int32),
                                   interpret=interpret)
    allow_hit = hits[:c * s].reshape(c, s, n) > 0
    forbid_hit = hits[c * s:c * s + c] > 0
    prio_counts = hits[c * s + c:]
    return {"allow_hit": allow_hit, "forbid_hit": forbid_hit,
            "prio_counts": prio_counts}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _count_branch(op: str, kernel: bool, shape) -> None:
    """Trace-time record of the branch one dispatch took at one shape."""
    dims = "x".join(str(int(d)) for d in shape)
    branch = "pallas" if kernel else "reference"
    COUNTERS.inc(f"kernel.{op}.{branch}[{dims}]")


def _capacity_fits(pod_req, alloc, requested, interpret, mesh):
    """capacity_fits_pallas, per node shard under shard_map when the node
    arrays are sharded over `mesh` in a GSPMD program: XLA cannot
    partition a Mosaic kernel by itself."""
    if mesh is None:
        return capacity_fits_pallas(pod_req, alloc, requested,
                                    interpret=interpret)
    from jax.sharding import PartitionSpec as PS
    ax = mesh.axis_names[0]
    return jax.shard_map(
        functools.partial(capacity_fits_pallas, interpret=interpret),
        mesh=mesh, in_specs=(PS(), PS(ax), PS(ax)),
        out_specs=PS(None, ax), check_vma=False)(pod_req, alloc, requested)


def resources_fit_fast(pod_req: jnp.ndarray, zero_req: jnp.ndarray,
                       alloc: jnp.ndarray, requested: jnp.ndarray,
                       force: Optional[bool] = None,
                       interpret: bool = False,
                       mesh=None) -> jnp.ndarray:
    """Drop-in for predicates.resources_fit: Pallas-tiled on TPU, the
    reference jnp path elsewhere (and for sub-tile batches where tile
    padding would dominate). The zero-request override (predicates.go
    :576-578) composes outside the kernel — a [P,N] op XLA fuses into
    the surrounding AND-chain either way. `mesh` is the node-axis mesh
    alloc/requested are sharded over when the caller is a GSPMD program
    (not already inside shard_map)."""
    shape = (pod_req.shape[0], alloc.shape[0], pod_req.shape[1])
    if force:
        # explicit force bypasses the size gate — the tests rely on it to
        # actually exercise the kernel on small hand cases
        _count_branch("resources_fit", True, shape)
        fit = _capacity_fits(pod_req, alloc, requested, interpret, mesh)
        return fit | zero_req[:, None]
    # per-dimension gate, set by MEASUREMENT (density bench A/B): the
    # kernel only pays off when both axes fill their tiles — the one-shot
    # full-batch fits() (P in the thousands). Inside the wave loop the
    # class axis is small (C~10): padding 7->128 rows plus the per-call
    # [N,R]->[R,N] transpose made waves 40-70% slower than the jnp path
    # XLA already fuses (0.83-1.17s vs 0.52-0.56s), so sub-tile axes
    # stay on the reference path.
    use = force is None and _on_tpu() \
        and pod_req.shape[0] >= P_BLK and alloc.shape[0] >= N_BLK
    _count_branch("resources_fit", use, shape)
    if use:
        fit = _capacity_fits(pod_req, alloc, requested, interpret, mesh)
        return fit | zero_req[:, None]
    from kubernetes_tpu.ops.predicates import resources_fit
    return resources_fit(pod_req, zero_req, alloc, requested)
