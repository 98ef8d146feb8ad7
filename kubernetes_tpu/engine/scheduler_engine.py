"""Host-side scheduling engine: snapshot -> device batch -> assume.

The TPU-native replacement for genericScheduler.Schedule
(reference: plugin/pkg/scheduler/core/generic_scheduler.go:88-142) operating
on the whole pending queue at once:

  1. delta-refresh the tensor snapshot from the SchedulerCache (the analog of
     cache.UpdateNodeNameToInfoMap at generic_scheduler.go:101);
  2. run engine/batch.place_batch on device — sequential semantics preserved
     (see batch.py docstring);
  3. map node indices back to names and AssumePod each placement into the
     cache (scheduler.go:188 assume; binding is the caller's async job,
     scheduler.go:224-250).

Pods whose features the kernels over-approximate (PodBatch.needs_host_check)
take the exact object-level oracle path against the updated cache — the
"exact host-side verification" safety net of SURVEY.md §7(e).

Device arrays are cached keyed on snapshot.version so an unchanged cluster
uploads nothing between batches.

The pipelined drain rides the dispatch_waves / harvest_waves pair instead
of schedule(): dispatch encodes a chunk (vocab_gen-keyed encoding reuse),
launches waves_loop WITHOUT the device→host sync, and returns a WaveHandle;
harvest blocks on the handle, re-validates the blind wave's placements
against current occupancy (the capacity fence, its topology mirror, and —
for gang-bearing waves — the all-or-nothing gang fence), finishes
strict-tail pods via the conflict-round loop (waves.tail_rounds_loop),
assumes survivors columnar (grouped per node+class, folded into the
snapshot via raw-delta math), and hands conflicts back for requeue.
schedule() remains the synchronous path for everything the wave engine
can't take (host-check classes, Policy algorithms, workload spreading).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.analysis import sanitize
from kubernetes_tpu.api.types import MAX_PRIORITY, Pod
from kubernetes_tpu.engine.batch import NodeState, gather_place_batch
from kubernetes_tpu.engine import waves
from kubernetes_tpu.observability import podtrace
from kubernetes_tpu.observability import recorder as flightrec
from kubernetes_tpu.observability.podtrace import TRACER
from kubernetes_tpu.observability.recorder import RECORDER
from kubernetes_tpu.ops import oracle
from kubernetes_tpu.ops import priorities as prio
from kubernetes_tpu.ops.predicates import bucket
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.classes import ClassBatch
from kubernetes_tpu.state.snapshot import (
    ClusterSnapshot,
    R_CPU,
    R_MEM,
    R_OVERLAY,
    R_SCRATCH,
)


class EvalCache:
    """Per-request amortization for the extender's evaluate_pod hot path —
    the sidecar analog of the reference's 100-entry equivalence LRU
    (core/equivalence_cache.go:33-54) plus vocab-growth isolation:

    - pair collection (collect_pod_pairs over every NodeInfo) cached keyed
      on snapshot.version, with existing pods' topology keys interned ONCE
      per version (not per request);
    - (ClassBatch, AffinityData) LRU keyed on (snapshot.version, pod class
      key) so repeat evaluations of equivalent pods skip tensorization;
    - label-vocab isolation: a pod whose selectors/topology keys would GROW
      the shared vocab (adversarial label churn -> full snapshot rebuild +
      recompile per request) is routed to the exact object-level oracle
      instead, and its pairs are queued; the next cache sync interns the
      queue in one batch, so rebuilds are bounded at one per sync no matter
      the request pattern (VERDICT r3 weak #5)."""

    MAX_PENDING = 4096

    def __init__(self, lru_size: int = 100, result_size: int = 2048):
        from collections import OrderedDict
        self.lru_size = lru_size
        self.result_size = result_size
        self._lru = OrderedDict()
        self._results = OrderedDict()
        self._results_ver = None  # results are reachable only within one
        # snapshot-version window (rkey embeds the version); a version move
        # clears the memo wholesale instead of letting up to result_size
        # dead ~25KB (fits, scores) pairs rot in FIFO order
        self._pairs_version = -1
        self._pairs = None
        self._pending_pairs: set = set()
        self._pending_images: set = set()
        self._pending_conflicts: set = set()
        self._pending_pds: set = set()
        self._sync_seen = False
        self.oracle_routes = 0  # diagnostics for tests/metrics
        self.builds = 0
        self.result_hits = 0
        # affinity-relevance generation, maintained by the owner (the
        # extender backend): bumped whenever the set of cached pods that
        # carry pod (anti-)affinity may have changed. Affinity-free
        # encodings key on (vocab_gen, aff_gen) instead of the full
        # snapshot version, so a stream of plain binds (scheduleOne compat
        # mode) reuses them instead of re-tensorizing per capacity delta.
        self.aff_gen = 0
        # True when NO pod in the owner's cache carries pod (anti-)affinity
        # — lets plain-pod evaluations skip pair collection + AffinityData
        # entirely (the symmetry check has nothing to check). Owners that
        # cannot prove this leave it False; everything still works, slower.
        self.cluster_aff_free = False

    def on_sync(self) -> None:
        """Cluster state resynced (the sidecar's /cache/... endpoints) —
        queued request pairs may intern at the next evaluation."""
        self._sync_seen = True
        self.aff_gen += 1
        self._results.clear()

    def flush_pending(self, snap: ClusterSnapshot) -> None:
        """Intern the queued request vocab entries in ONE rebuild per vocab,
        only after a sync boundary — the bounded-growth half of the
        isolation story."""
        if not self._sync_seen:
            return
        if self._pending_pairs:
            for k, v in self._pending_pairs:
                snap.ensure_label_pair(k, v)
            self._pending_pairs.clear()
            snap.finalize_labels()
        if self._pending_images:
            for name in self._pending_images:
                snap.ensure_image(name)
            self._pending_images.clear()
            snap.finalize_images()
        if self._pending_conflicts or self._pending_pds:
            for key in self._pending_conflicts:
                snap.ensure_conflict_key(key)
            for kind, vid in self._pending_pds:
                snap.ensure_pd_id(kind, vid)
            self._pending_conflicts.clear()
            self._pending_pds.clear()
            snap.finalize_volumes()
        self._sync_seen = False

    # -------------------------------------------------------------- pairs

    def pairs_for(self, snap: ClusterSnapshot, infos):
        """(all_pairs, aff_pairs) for the current cluster state; interns
        existing-pod topology keys + any queued request pairs, then
        finalizes the label matrix so the version is stable afterwards."""
        from kubernetes_tpu.ops.affinity import (
            collect_pod_pairs,
            intern_topology_pairs,
        )
        if self._pairs_version == snap.version and self._pairs is not None:
            return self._pairs
        all_pairs, aff_pairs = collect_pod_pairs(infos)
        intern_topology_pairs(snap, [], aff_pairs)
        for k, v in self._pending_pairs:
            snap.ensure_label_pair(k, v)
        self._pending_pairs.clear()
        snap.finalize_labels()
        self._pairs = (all_pairs, aff_pairs)
        self._pairs_version = snap.version
        return self._pairs

    # ----------------------------------------------------- vocab isolation

    def vocab_missing(self, pod: Pod, snap: ClusterSnapshot,
                      volume_ctx=None) -> bool:
        """Would encoding this pod grow ANY snapshot vocab (label pairs,
        container images, volume conflict keys / PD ids)? If yes, queue the
        entries for the next sync and answer True (caller routes to the
        oracle). Guarding only labels would leave image/volume churn as a
        per-request rebuild vector — PodBatch interns those too
        (snapshot.py ensure_image/ensure_conflict_key/ensure_pd_id)."""
        pairs = set()
        vocab = snap.label_vocab
        grown = False
        pend = len(self._pending_images) + len(self._pending_conflicts) \
            + len(self._pending_pds)
        for c in pod.containers:
            if c.image and snap.image_vocab.get(c.image, "") < 0:
                grown = True
                if pend < self.MAX_PENDING:
                    self._pending_images.add(c.image)
        if pod.volumes:
            from kubernetes_tpu.state import volumes as volmod
            for key, _ro in volmod.pod_conflict_keys(pod):
                if snap.conflict_vocab.get(key, "") < 0:
                    grown = True
                    if pend < self.MAX_PENDING:
                        self._pending_conflicts.add(key)
            if volume_ctx is not None:
                for kind, vid in volmod.pd_filter_ids(pod, volume_ctx):
                    if snap.pd_vocab.get(str(kind) + "\x00" + vid, "") < 0:
                        grown = True
                        if pend < self.MAX_PENDING:
                            self._pending_pds.add((kind, vid))
        for k, v in pod.node_selector.items():
            if vocab.get(k, v) < 0:
                pairs.add((k, v))
        a = pod.affinity
        terms = []
        if a is not None and a.node_affinity is not None:
            if a.node_affinity.required_terms:
                terms.extend(a.node_affinity.required_terms)
            terms.extend(t for _w, t in a.node_affinity.preferred_terms)
        from kubernetes_tpu.api.types import SelectorOperator
        for t in terms:
            for r in t.match_expressions:
                if SelectorOperator(r.operator) == SelectorOperator.IN:
                    for v in r.values:
                        if vocab.get(r.key, v) < 0:
                            pairs.add((r.key, v))
                else:  # Exists/NotIn/Gt/Lt expand over node-present values
                    for v in snap.node_values_for_key(r.key):
                        if vocab.get(r.key, v) < 0:
                            pairs.add((r.key, v))
        from kubernetes_tpu.ops.affinity import _term_topology_keys
        for key in _term_topology_keys(pod):
            for v in snap.node_values_for_key(key):
                if vocab.get(key, v) < 0:
                    pairs.add((key, v))
        if pairs or grown:
            if len(self._pending_pairs) < self.MAX_PENDING:
                self._pending_pairs.update(pairs)
            self.oracle_routes += 1
            return True
        return False

    # ------------------------------------------------------------------ LRU

    @staticmethod
    def _wkey(workloads: Sequence) -> tuple:
        return tuple(sorted((w.kind, w.namespace, w.name, w.resource_version)
                            for w in workloads))

    def get_encoded(self, pod: Pod, snap: ClusterSnapshot, build,
                    workloads: Sequence = (), ckey=None, aff_free=False):
        """Encoded-class entry via the LRU; `build()` constructs on miss.

        Key: affinity-FREE classes (no pod affinity, no workloads, cluster
        proven affinity-free) key on (vocab_gen, aff_gen) — their encoding
        reads only vocabs and the node order, so capacity deltas (binds)
        don't invalidate them. Affinity-BEARING classes key on the full
        snapshot version, exactly as the reference re-derives predicate
        metadata against the live cache per pod."""
        from kubernetes_tpu.state.classes import pod_class_key
        wkey = self._wkey(workloads)
        struct = (snap.vocab_gen, self.aff_gen) if aff_free else snap.version
        key = (struct, wkey, ckey if ckey is not None else pod_class_key(pod))
        hit = self._lru.get(key)
        if hit is not None:
            self._lru.move_to_end(key)
            return hit
        val = build()
        self.builds += 1
        self._lru[key] = val
        if len(self._lru) > self.lru_size:
            self._lru.popitem(last=False)
        return val

    # ------------------------------------------------------------- results

    def _roll_results(self, version) -> None:
        if version != self._results_ver:
            self._results.clear()
            self._results_ver = version

    def get_result(self, key):
        """(fits, scores) memo for one (snapshot version, priority config,
        class) — the fused-verb cache: /prioritize after /filter for the
        same pod (or any equivalent pod at the same cluster state) returns
        without touching the device. Invalidation is structural: the
        snapshot version moving clears the whole window (old-version
        entries can never hit again — version is monotonic), on_sync
        clears outright."""
        self._roll_results(key[0])
        hit = self._results.get(key)
        if hit is not None:
            self._results.move_to_end(key)
            self.result_hits += 1
        return hit

    def put_result(self, key, value) -> None:
        self._roll_results(key[0])
        self._results[key] = value
        if len(self._results) > self.result_size:
            self._results.popitem(last=False)


class PlacementResult:
    __slots__ = ("pod", "node_name", "fit_count")

    def __init__(self, pod: Pod, node_name: Optional[str], fit_count: int):
        self.pod = pod
        self.node_name = node_name
        self.fit_count = fit_count

    def __repr__(self):
        return f"Placement({self.pod.key()} -> {self.node_name})"


def _oracle_eval(pod, infos, snap, priorities, workloads, hard_weight,
                 volume_ctx, policy_algos):
    """Exact object-level /filter + /prioritize (the reference's per-pod
    predicate/priority calls, no tensorization)."""
    from kubernetes_tpu.ops.oracle_ext import AffinityMeta, SchedulingContext
    ctx = SchedulingContext(infos, list(workloads),
                            hard_pod_affinity_weight=hard_weight,
                            volume_ctx=volume_ctx,
                            policy_algos=policy_algos)
    meta = AffinityMeta(pod, ctx)
    names = snap.node_names
    n_pad = snap.valid.shape[0]
    m = np.zeros(n_pad, dtype=bool)
    for i, nm in enumerate(names):
        m[i] = oracle.pod_fits(pod, infos[nm], ctx, meta)
    s = np.zeros(n_pad, dtype=np.int64)
    fit_idx = np.nonzero(m)[0]
    if len(fit_idx):
        fit_infos = [infos[names[i]] for i in fit_idx]
        per = oracle.prioritize(pod, fit_infos, priorities, ctx)
        s[fit_idx] = per
    return m, s


class _EncodedClass:
    """One LRU entry of the extender fast lane: the host encodings plus
    their DEVICE-resident uploads, so repeat evaluations of an equivalent
    pod re-dispatch the compiled kernel over buffers already in HBM instead
    of re-tensorizing + re-transferring per request."""

    __slots__ = ("batch", "adata", "parr", "aff")

    def __init__(self, batch, adata, parr, aff):
        self.batch = batch
        self.adata = adata
        self.parr = parr    # device pod-side pytree (shape-bucketed)
        self.aff = aff      # device affinity pytree, or None when inert


def _fused_eval(parr, narr, aff, priorities, weights, aff_mode):
    """The single-pod [1,N] evaluation as ONE traced program: predicate
    chain + weighted priorities + (when live) the zero-occupancy affinity/
    spread kernels. Fusing matters: the previous eager composition
    dispatched every jnp op on its own (~60+ dispatches per warm /filter);
    one jit call is one dispatch."""
    from kubernetes_tpu.ops.affinity import (
        interpod_score,
        spread_score,
        step_fits,
        step_prio_counts,
        step_spread_counts,
    )
    from kubernetes_tpu.ops.pallas_kernels import precompute_static_fast
    from kubernetes_tpu.ops.predicates import fits

    fits_on, prio_on, spread_on = aff_mode
    w_ip, w_sp = weights
    m = fits(parr, narr)[0]
    s = prio.score(parr, narr, priorities)[0]
    if fits_on or prio_on or spread_on:
        labels = narr["labels"]
        pre = precompute_static_fast(aff, labels)
        c_dim = aff["m_aff"].shape[0]
        commdom0 = jnp.zeros((c_dim, labels.shape[1]), dtype=jnp.int32)
        committed0 = jnp.zeros((c_dim, labels.shape[0]), dtype=jnp.int32)
        comm_cnt0 = jnp.zeros(c_dim, dtype=jnp.int32)
        if fits_on:
            m = m & step_fits(aff, pre, 0, commdom0, comm_cnt0, labels)
        if prio_on:
            cnt = step_prio_counts(aff, pre, 0, commdom0, labels)
            s = s + w_ip * interpod_score(cnt, m)
        if spread_on:
            cnt = step_spread_counts(aff, 0, committed0)
            s = s + w_sp * spread_score(aff, aff["sp_has"][0], cnt, m)
    if w_sp and not spread_on:
        # no workload selects the pod: selector_spreading.go scores every
        # node MaxPriority (spread_score's unscored value)
        s = s + w_sp * MAX_PRIORITY
    return m, s


_fused_eval_jit = jax.jit(_fused_eval,
                          static_argnames=("priorities", "weights",
                                           "aff_mode"))


def _fused_eval_batch(parr, narr, aff, priorities, weights, aff_mode):
    """The [C, N] sibling of _fused_eval (ISSUE 9): every row of a coalesced
    multi-frontend batch evaluated in ONE traced program — predicate chain +
    weighted priorities + (when live) the zero-occupancy affinity/spread
    kernels, class-vectorized via step_fits_all / step_prio_counts_all (the
    ISSUE 5 conflict-round forms; row c is bit-identical to _fused_eval of
    class c alone, since zero occupancy has no cross-row carry). 100
    concurrent frontends therefore cost ~1 dispatch, not 100."""
    from kubernetes_tpu.ops.affinity import (
        interpod_score,
        spread_score,
        step_fits_all,
        step_prio_counts_all,
    )
    from kubernetes_tpu.ops.pallas_kernels import precompute_static_fast
    from kubernetes_tpu.ops.predicates import fits

    fits_on, prio_on, spread_on = aff_mode
    w_ip, w_sp = weights
    m = fits(parr, narr)                       # [C, N]
    s = prio.score(parr, narr, priorities)     # [C, N]
    if fits_on or prio_on or spread_on:
        labels = narr["labels"]
        pre = precompute_static_fast(aff, labels)
        c_dim = aff["m_aff"].shape[0]
        commdom0 = jnp.zeros((c_dim, labels.shape[1]), dtype=jnp.int32)
        committed0 = jnp.zeros((c_dim, labels.shape[0]), dtype=jnp.int32)
        comm_cnt0 = jnp.zeros(c_dim, dtype=jnp.int32)
        if fits_on:
            m = m & step_fits_all(aff, pre, commdom0, comm_cnt0, labels)
        if prio_on:
            cnt = step_prio_counts_all(aff, pre, commdom0, labels)
            s = s + w_ip * interpod_score(cnt, m)
        if spread_on:
            dyn = aff["sp_cls"].astype(jnp.int32) @ committed0
            s = s + w_sp * spread_score(aff, aff["sp_has"],
                                        aff["sp_static"] + dyn, m)
    if w_sp and not spread_on:
        s = s + w_sp * MAX_PRIORITY  # as in _fused_eval
    return m, s


_fused_eval_batch_jit = jax.jit(_fused_eval_batch,
                                static_argnames=("priorities", "weights",
                                                 "aff_mode"))


def evaluate_pod(pod: Pod, infos, snap: ClusterSnapshot,
                 priorities: Tuple[Tuple[str, int], ...],
                 workloads: Sequence = (), hard_weight: int = 1,
                 volume_ctx=None, policy_algos=None, eval_cache=None,
                 device_nodes_provider=None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-node (fits [N] bool, scores [N] int32) for ONE pod against the
    cluster state — the extender's /filter + /prioritize evaluation
    (core/extender.go:100 Filter, :157 Prioritize). No state is committed:
    a single pod has no in-batch carry, so the affinity/spread kernels run
    with zero occupancy (the static side only — exactly what the reference's
    per-pod predicate/priority calls see through the scheduler cache).

    `snap` must already be refreshed against `infos`. Falls back to the
    exact host oracle when the pod's features over-approximate on device
    (needs_host_check / affinity slot overflow).

    Score caveat (pre-dating the fast lane, preserved): the oracle route
    normalizes reduce-priorities over the FILTERED set and reports 0 for
    non-fitting nodes, while the device route scores every node with
    fits=None normalization — so the two routes can differ on the exact
    integers (never on fit verdicts). A single pod always takes ONE route
    per call, and /filter+/prioritize share it via the result memo, so a
    scheduler never sees mixed-route scores for one pod.

    The warm fast lane (eval_cache given) is layered:
      1. result memo — same class at the same snapshot version returns the
         cached (m, s) with zero device work (the fused filter+prioritize
         contract: the second verb rides the first's evaluation);
      2. encoded-class LRU — holds device-RESIDENT pod/affinity arrays;
         affinity-free classes survive capacity deltas (vocab_gen keying);
      3. one fused jit dispatch over the caller's device-resident node
         arrays (device_nodes_provider — CALLED only after vocab flushes,
         so a label-matrix rebuild can never race a stale upload;
         node_arrays(snap) uploads fresh when absent).
    """
    from kubernetes_tpu.ops.affinity import (
        AffinityData,
        _has_affinity,
        collect_pod_pairs,
        intern_topology_pairs,
    )
    from kubernetes_tpu.ops.predicates import pod_arrays_bucketed
    from kubernetes_tpu.state.classes import pod_class_key
    from kubernetes_tpu.utils.trace import COUNTERS, timed_span

    w_ip = sum(w for nm, w in priorities if nm == "InterPodAffinityPriority")
    w_sp = sum(w for nm, w in priorities if nm == "SelectorSpreadPriority")

    if eval_cache is not None:
        # queued churn pairs intern in one batch at a sync boundary
        eval_cache.flush_pending(snap)
        # vocab isolation: a pod that would grow any snapshot vocab must
        # not touch the snapshot at all (EvalCache docstring)
        if eval_cache.vocab_missing(pod, snap, volume_ctx=volume_ctx):
            with timed_span("extender.oracle_eval"):
                return _oracle_eval(pod, infos, snap, priorities, workloads,
                                    hard_weight, volume_ctx, policy_algos)
        ckey = pod_class_key(pod)
        # priorities + hard_weight are part of BOTH cache keys: the
        # encoding's `need` gate and the scores depend on them, and nothing
        # forces a shared EvalCache to serve one fixed configuration
        cfg = (priorities, hard_weight)
        rkey = (snap.version, eval_cache._wkey(workloads), cfg, ckey)
        hit = eval_cache.get_result(rkey)
        if hit is not None:
            COUNTERS.inc("extender.result_hit")
            return hit
        # a pod with no pod (anti-)affinity in a cluster with no
        # affinity-carrying pods and no workloads has an all-zero
        # AffinityData by construction — skip pair collection and the
        # affinity build entirely, and key the encoding on the vocab
        # generation so binds don't invalidate it
        aff_free = (eval_cache.cluster_aff_free and not workloads
                    and not _has_affinity(pod))
        if aff_free:
            def _build():
                with timed_span("extender.encode"):
                    b = ClassBatch([pod], snap)
                    return _EncodedClass(b, None,
                                         pod_arrays_bucketed(b.reps_batch),
                                         None)
        else:
            with timed_span("extender.pairs"):
                all_pairs, aff_pairs = eval_cache.pairs_for(snap, infos)

            def _build():
                with timed_span("extender.encode"):
                    COUNTERS.inc("extender.affinity_data_build")
                    b = ClassBatch([pod], snap)
                    a = AffinityData(b.reps, snap, all_pairs, aff_pairs,
                                     list(workloads), hard_weight)
                    need = (a.fits_needed
                            or (bool(w_ip) and a.prio_needed)
                            or (bool(w_sp) and a.spread_needed))
                    return _EncodedClass(
                        b, a, pod_arrays_bucketed(b.reps_batch),
                        a.device_arrays() if need else None)

        enc = eval_cache.get_encoded(pod, snap, _build, workloads=workloads,
                                     ckey=(cfg, ckey), aff_free=aff_free)
        out = _eval_dispatch(pod, infos, snap, priorities, workloads,
                             hard_weight, volume_ctx, policy_algos, enc,
                             device_nodes_provider, w_ip, w_sp)
        eval_cache.put_result(rkey, out)
        return out

    # uncached path (no EvalCache owner): build fresh per call, then the
    # SAME dispatch tail — args-mode and the warm lane cannot drift
    all_pairs, aff_pairs = collect_pod_pairs(infos)
    intern_topology_pairs(snap, [pod], aff_pairs)
    batch = ClassBatch([pod], snap)
    adata = AffinityData(batch.reps, snap, all_pairs, aff_pairs,
                         list(workloads), hard_weight)
    need = (adata.fits_needed or (bool(w_ip) and adata.prio_needed)
            or (bool(w_sp) and adata.spread_needed))
    enc = _EncodedClass(batch, adata, pod_arrays_bucketed(batch.reps_batch),
                        adata.device_arrays() if need else None)
    return _eval_dispatch(pod, infos, snap, priorities, workloads,
                          hard_weight, volume_ctx, policy_algos, enc,
                          device_nodes_provider, w_ip, w_sp)


def _eval_dispatch(pod, infos, snap, priorities, workloads, hard_weight,
                   volume_ctx, policy_algos, enc: "_EncodedClass",
                   device_nodes_provider, w_ip: int, w_sp: int):
    """Shared routing tail of evaluate_pod: exact-oracle gate
    (needs_host_check / slot overflow / Policy algorithms), then ONE fused
    kernel dispatch over the caller's device-resident node arrays. Both the
    warm fast lane and the uncached args-mode path end here, so the
    dispatch contract cannot drift between them."""
    from kubernetes_tpu.ops.predicates import node_arrays
    from kubernetes_tpu.utils.trace import COUNTERS, timed_span

    batch, adata = enc.batch, enc.adata
    if batch.reps_batch.needs_host_check[0] \
            or (adata is not None and adata.overflow[0]) \
            or (policy_algos is not None and policy_algos.active):
        # exact object-level path (same routing as SchedulingEngine.schedule;
        # Policy-configured algorithms always evaluate exactly here — one
        # pod per extender call keeps the oracle cheap)
        with timed_span("extender.oracle_eval"):
            return _oracle_eval(pod, infos, snap, priorities, workloads,
                                hard_weight, volume_ctx, policy_algos)
    plain = tuple((nm, w) for nm, w in priorities
                  if nm not in prio.AFFINITY_PRIORITIES)
    fits_on = adata is not None and adata.fits_needed
    prio_on = adata is not None and bool(w_ip) and adata.prio_needed
    spread_on = adata is not None and bool(w_sp) and adata.spread_needed
    narr = device_nodes_provider() if device_nodes_provider is not None \
        else node_arrays(snap)
    with timed_span("extender.kernel"):
        COUNTERS.inc("extender.fused_eval")
        m, s = _fused_eval_jit(
            enc.parr, narr,
            enc.aff if (fits_on or prio_on or spread_on) else None,
            plain, (w_ip, w_sp), (fits_on, prio_on, spread_on))
        # the extender's one result fetch: the verb returns (fits, scores)
        # to an HTTP caller, so this stall IS the response (m must be
        # writable below; s stays a read-only view)
        m = np.array(m)  # graftlint: sync-ok
        s = np.asarray(s)  # graftlint: sync-ok (same blessed fetch)
    m[len(snap.node_names):] = False
    return m, s


def evaluate_pods_batch(pods: Sequence[Pod], infos, snap: ClusterSnapshot,
                        priorities: Tuple[Tuple[str, int], ...],
                        workloads: Sequence = (), hard_weight: int = 1,
                        volume_ctx=None, policy_algos=None, eval_cache=None,
                        device_nodes_provider=None
                        ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Coalesced multi-frontend evaluation (ISSUE 9): one (fits, scores)
    pair per pod, computed with at most ONE fused [C, N] kernel dispatch
    for the batch's unique pod classes — the device half of the extender's
    micro-batch window. Per-pod ROUTING is identical to evaluate_pod:

      - vocab growth       -> exact host oracle (isolation unchanged);
      - result-memo hit    -> served with zero device work;
      - one unique class   -> delegated to evaluate_pod (the single-pod
        warm lane, so its encoded-class LRU and span counters keep their
        exact contracts — and the fastlane tests their invariants);
      - several classes    -> ONE ClassBatch over the class reps, class
        axis padded to the bucket ladder (pod_arrays_bucketed rows=), one
        _fused_eval_batch_jit dispatch, rows scattered per request;
        host-check / slot-overflow / Policy classes drop to the oracle
        per class exactly as _eval_dispatch routes the single pod.

    Every class's (m, s) enters the result memo, so followers of the same
    coalescing window and later requests hit without dispatching. `snap`
    must already be refreshed; no state is committed (zero-occupancy
    evaluation, same contract as evaluate_pod)."""
    from collections import OrderedDict

    from kubernetes_tpu.ops.affinity import AffinityData, _has_affinity
    from kubernetes_tpu.ops.predicates import node_arrays, pod_arrays_bucketed
    from kubernetes_tpu.state.classes import pod_class_key
    from kubernetes_tpu.utils.trace import COUNTERS, timed_span

    n = len(pods)
    if eval_cache is None:
        # no cache owner: per-request evaluation is the only honest shape
        # (nothing to coalesce against between stateless snapshots)
        return [evaluate_pod(p, infos, snap, priorities, workloads,
                             hard_weight, volume_ctx, policy_algos, None,
                             device_nodes_provider) for p in pods]
    results: List[Optional[Tuple[np.ndarray, np.ndarray]]] = [None] * n
    eval_cache.flush_pending(snap)
    w_ip = sum(w for nm, w in priorities if nm == "InterPodAffinityPriority")
    w_sp = sum(w for nm, w in priorities if nm == "SelectorSpreadPriority")
    cfg = (priorities, hard_weight)
    wkey = eval_cache._wkey(workloads)

    def _oracle(pod):
        with timed_span("extender.oracle_eval"):
            return _oracle_eval(pod, infos, snap, priorities, workloads,
                                hard_weight, volume_ctx, policy_algos)

    # per-pod routing: vocab isolation + memo, then class dedup
    uniq = OrderedDict()  # ckey -> [pod indices], first-seen order
    rep_of = {}
    for i, pod in enumerate(pods):
        if eval_cache.vocab_missing(pod, snap, volume_ctx=volume_ctx):
            results[i] = _oracle(pod)
            continue
        ckey = pod_class_key(pod)
        rkey = (snap.version, wkey, cfg, ckey)
        hit = eval_cache.get_result(rkey)
        if hit is not None:
            COUNTERS.inc("extender.result_hit")
            results[i] = hit
            continue
        members = uniq.get(ckey)
        if members is None:
            uniq[ckey] = members = []
            rep_of[ckey] = pod
        members.append(i)
    # canonical class order (sorted by key repr): the encoded-batch LRU
    # entry is keyed on the class TUPLE, and the same class set arriving
    # in a different interleaving must hit the same entry — row c of the
    # encoding maps to canonical class c by construction
    order = sorted(uniq, key=repr)
    uniq = OrderedDict((ck, uniq[ck]) for ck in order)
    reps: List[Pod] = [rep_of[ck] for ck in order]
    if not uniq:
        return results  # type: ignore[return-value]
    if len(uniq) == 1 or (policy_algos is not None and policy_algos.active):
        # one class (the compat-storm common case) rides the single-pod
        # warm lane — encoded-class LRU, result memo, exact span counters;
        # Policy-configured algorithms always evaluate per pod exactly
        for ckey, members in uniq.items():
            out = evaluate_pod(pods[members[0]], infos, snap, priorities,
                               workloads, hard_weight, volume_ctx,
                               policy_algos, eval_cache,
                               device_nodes_provider)
            for i in members:
                results[i] = out
        return results  # type: ignore[return-value]

    COUNTERS.inc("extender.batch_classes", len(uniq))
    aff_free = (eval_cache.cluster_aff_free and not workloads
                and not any(_has_affinity(r) for r in reps))
    if not aff_free:
        with timed_span("extender.pairs"):
            all_pairs, aff_pairs = eval_cache.pairs_for(snap, infos)

    def _build():
        with timed_span("extender.encode"):
            b = ClassBatch(reps, snap)
            c_pad = bucket(b.num_classes, lo=4)
            if aff_free:
                return _EncodedClass(
                    b, None, pod_arrays_bucketed(b.reps_batch, rows=c_pad),
                    None)
            COUNTERS.inc("extender.affinity_data_build")
            a = AffinityData(b.reps, snap, all_pairs, aff_pairs,
                             list(workloads), hard_weight, c_pad=c_pad)
            need = (a.fits_needed or (bool(w_ip) and a.prio_needed)
                    or (bool(w_sp) and a.spread_needed))
            return _EncodedClass(
                b, a, pod_arrays_bucketed(b.reps_batch, rows=c_pad),
                a.device_arrays() if need else None)

    enc = eval_cache.get_encoded(reps[0], snap, _build, workloads=workloads,
                                 ckey=(cfg, tuple(uniq)), aff_free=aff_free)
    batch, adata = enc.batch, enc.adata
    fits_on = adata is not None and adata.fits_needed
    prio_on = adata is not None and bool(w_ip) and adata.prio_needed
    spread_on = adata is not None and bool(w_sp) and adata.spread_needed
    plain = tuple((nm, w) for nm, w in priorities
                  if nm not in prio.AFFINITY_PRIORITIES)
    m_all = s_all = None
    nhc = batch.reps_batch.needs_host_check
    for c, (ckey, members) in enumerate(uniq.items()):
        if nhc[c] or (adata is not None and adata.overflow[c]):
            out = _oracle(reps[c])  # exact object-level route, per class
        else:
            if m_all is None:
                narr = device_nodes_provider() \
                    if device_nodes_provider is not None \
                    else node_arrays(snap)
                with timed_span("extender.kernel_batch"):
                    COUNTERS.inc("extender.fused_eval_batch")
                    m_d, s_d = _fused_eval_batch_jit(
                        enc.parr, narr,
                        enc.aff if (fits_on or prio_on or spread_on)
                        else None,
                        plain, (w_ip, w_sp),
                        (fits_on, prio_on, spread_on))
                    # the batch's one result fetch: every coalesced verb
                    # returns its row to an HTTP caller, so this stall IS
                    # the response set
                    m_all = np.array(m_d)  # graftlint: sync-ok
                    s_all = np.asarray(s_d)  # graftlint: sync-ok (same
                    # blessed fetch)
                m_all[:, len(snap.node_names):] = False
            out = (m_all[c], s_all[c])
        eval_cache.put_result((snap.version, wkey, cfg, ckey), out)
        for i in members:
            results[i] = out
    return results  # type: ignore[return-value]


def _aff_node_views(adata, snap):
    """(key_node [C, A, N] int8, static_forbid_hit [C, N] int8): the
    per-NODE projections of the anti-term keymasks and static forbid rows.
    Wave-eligible classes have singleton domains, so "node n is in a
    forbidden domain of term (c, a)" reduces to "a matching pod sits ON n
    and n carries the term's key" — these two views are all the per-wave
    mask needs, and neither carries the label axis (which scales with the
    cluster once hostname keys are interned). Computed once per encoding
    build as dense float64 GEMMs restricted to the NONZERO rows (BLAS,
    counts far below 2^53 — exact)."""
    lab_t = snap.labels.astype(np.float64).T              # [L, N]
    C, A, L = adata.anti_keymask.shape
    n = lab_t.shape[1]
    km = adata.anti_keymask.reshape(C * A, L)
    key_node = np.zeros((C * A, n), dtype=np.int8)
    rows = np.nonzero(km.any(axis=1))[0]
    if rows.size:
        key_node[rows] = (km[rows].astype(np.float64) @ lab_t) > 0
    fs = adata.forbid_static
    static_hit = np.zeros((C, n), dtype=np.int8)
    frows = np.nonzero(fs.any(axis=1))[0]
    if frows.size:
        static_hit[frows] = (fs[frows].astype(np.float64) @ lab_t) > 0
    return key_node.reshape(C, A, n), static_hit


def _aff_tail_cols(adata, prio_on: bool) -> np.ndarray:
    """Label columns the SEEDED STRICT TAIL can actually read: domains of
    the wave_strict classes' own terms (allow + anti + static rows), of
    terms TARGETING them (the symmetry sources), and — when preferred
    scoring is live — of every priority-side keymask. Everything else in
    the label axis (hostname columns interned for the wave classes' anti
    terms, selector vocab) is provably inert inside the tail's
    step_fits/step_prio_counts contractions, so the tail runs at
    Lp = O(referenced domains), not L = O(cluster)."""
    sc = adata.wave_strict
    L = adata.forbid_static.shape[1]
    use = np.zeros(L, dtype=bool)
    if sc.any():
        use |= adata.aff_keymask[sc].astype(bool).any(axis=(0, 1))
        use |= adata.aff_allow[sc].astype(bool).any(axis=(0, 1))
        use |= adata.anti_keymask[sc].astype(bool).any(axis=(0, 1))
        use |= adata.forbid_static[sc].astype(bool).any(axis=0)
        tgt = adata.m_anti[:, :, sc].astype(bool).any(axis=2)   # [C, A]
        use |= (adata.anti_keymask.astype(bool)
                & tgt[:, :, None]).any(axis=(0, 1))
    if prio_on:
        use |= adata.p_keymask.astype(bool).any(axis=(0, 1))
        use |= adata.q_keymask.astype(bool).any(axis=(0, 1))
        use |= adata.prio_static.astype(bool).any(axis=0)
    cols = np.nonzero(use)[0]
    if cols.size == 0:
        cols = np.zeros(1, dtype=np.int64)  # degenerate: keep shapes sane
    return cols


_AFF_SLICE3 = ("aff_allow", "aff_keymask", "anti_keymask", "p_keymask",
               "q_keymask")
_AFF_SLICE2 = ("forbid_static", "prio_static")


def _aff_tail_arrays(adata, snap, cols: np.ndarray, rmesh=None):
    """AffinityData device arrays with every domain axis sliced to the
    tail's column projection, plus the matching `labels_aff` [N, Lp] node
    incidence the scan contracts against (place_batch swaps it in for
    nodes["labels"] on the affinity side only). With a resident mesh the
    node-axis members place sharded (mesh.aff_spec), everything else
    replicated — once per encoding, resident across every tail dispatch."""
    def _sh(k):
        return None if rmesh is None else rmesh.aff_sharding(k)
    out = {}
    for k in ("fail_all", "forbid_static", "aff_active", "aff_allow",
              "aff_has_static", "aff_self", "aff_keymask", "anti_active",
              "anti_keymask", "m_aff", "m_anti", "prio_static", "p_w",
              "p_keymask", "mp", "q_w", "q_keymask", "mq", "sp_static",
              "sp_cls", "sp_has", "Z", "node_has_zone", "wave_gate"):
        a = getattr(adata, k)
        if k in _AFF_SLICE3:
            a = a[:, :, cols]
        elif k in _AFF_SLICE2:
            a = a[:, cols]
        # static-per-encoding host arrays (AffinityData owns them, nothing
        # mutates them after build) — zero-copy is the point; the sanitizer
        # seals the sources so a violation crashes at the offending write
        out[k] = sanitize.upload_frozen(a, sharding=_sh(k))
    # advanced indexing already copies, so freezing the fresh row is free
    out["labels_aff"] = sanitize.upload_frozen(snap.labels[:, cols],
                                               sharding=_sh("labels_aff"))
    return out


class _WaveEncoding:
    """Device-resident class encoding reused across pipelined drain chunks.

    A 30k-pod storm arrives as ~8 pipelined chunks of the SAME handful of
    spec classes; re-running ClassBatch/PodBatch per chunk would re-pay the
    tensorization the equivalence classes exist to amortize. This caches the
    padded device class arrays keyed on snapshot.vocab_gen (capacity deltas
    never invalidate an encoding — only vocab growth / node-membership moves
    do, same keying as the extender's affinity-free fast lane) plus the host
    rows the harvest fence reads.

    Affinity chunks (ISSUE 3) add the AffinityData for the class set — its
    STATIC topology arrays (vs already-bound cluster pods) plus a host
    accumulator committed_nodes [C, N] recording this engine's OWN
    fence-accepted commits since the build, so each dispatch seeds the
    device wave loop with exact current occupancy without ever re-walking
    the bound-pod set. The occupancy axis is PER NODE, not per label
    column: wave-eligible classes have singleton domains (domain == node),
    and a [C, L] form would drag the label axis — which scales with the
    cluster once hostname keys are interned — through every wave and fence
    (the affinity-tail collapse it once caused). The strict tail gets a
    PROJECTED domain view instead (tail_cols: only columns its classes'
    terms touch). Validity is (vocab_gen, cache.aff_seq) plus — for
    affinity encodings, whose topology views bake label CONTENT —
    snapshot.labels_gen: the engine folds its own assumes into aff_seq
    expectations, so a mismatch means FOREIGN affinity churn (watch
    add/remove, TTL expiry, forgotten bind, node relabel) and the static
    arrays rebuild at the next dispatch."""

    __slots__ = ("vocab_gen", "labels_gen", "key_index", "reps", "cls_arr",
                 "num_classes",
                 "c_pad", "req_rows", "special", "derived", "ports_max",
                 "raw_rows", "delta_ok", "cls_prio", "adata", "wave_strict",
                 "has_aff_pod", "fits_on", "prio_on", "aff_seq",
                 "committed_nodes", "key_node", "static_forbid_hit",
                 "tail_cols", "aff_wave_dev", "aff_tail_dev",
                 "anti_terms", "aff_terms", "foreign_forbid",
                 "foreign_forbid_dom", "aff_patch_dirty",
                 "host_exact", "host_static", "policy_on", "spread_on",
                 "wkey", "has_static_cols")

    def __init__(self, vocab_gen, key_index, reps, cls_arr, num_classes,
                 c_pad, req_rows, special, derived, ports_max,
                 adata=None, fits_on=False, prio_on=False,
                 has_aff_pod=None, aff_seq=0, aff_wave_dev=None,
                 aff_tail_dev=None, key_node=None, static_forbid_hit=None,
                 tail_cols=None, n_pad=0, labels_gen=0,
                 host_exact=None, host_static=None, policy_on=False,
                 spread_on=False, wkey=(), has_static_cols=False):
        self.vocab_gen = vocab_gen
        self.labels_gen = labels_gen  # snapshot.labels_gen at build: the
        # topology views (key_node/static_forbid_hit/labels_aff) bake
        # label CONTENT, which vocab_gen does not cover (delta relabel)
        self.key_index = key_index
        self.reps = reps
        self.cls_arr = cls_arr
        self.num_classes = num_classes
        self.c_pad = c_pad
        self.req_rows = req_rows      # [C, R] int64, snapshot-quantized
        self.special = special        # [C] bool: ports/volumes classes
        self.derived = derived        # per-class (Resource, ncpu, nmem, ports)
        self.ports_max = ports_max    # highest requested host port, or -1
        self.adata = adata            # AffinityData at c_pad, or None
        self.fits_on = fits_on        # required (anti-)affinity live
        self.prio_on = prio_on        # preferred-affinity scoring live
        self.wave_strict = adata.wave_strict if adata is not None \
            else np.zeros(c_pad, dtype=bool)
        # host-check / Policy absorption (ISSUE 18): host_exact classes
        # ride the wave as inactive padding-class rows and place at the
        # harvest's exact oracle tail (live-NodeInfo ports, score-
        # affecting preference overflow, Policy order-dependence,
        # affinity slot overflow); host_static classes carry a
        # precomputed exact label-pure fit column (cls_arr["host_fit"])
        # and place on the wave itself. Neither shape flushes the
        # pipeline anymore.
        self.host_exact = host_exact if host_exact is not None \
            else np.zeros(c_pad, dtype=bool)
        self.host_static = host_static if host_static is not None \
            else np.zeros(c_pad, dtype=bool)
        self.policy_on = policy_on    # policy_fit/policy_score baked
        self.spread_on = spread_on    # SelectorSpread riding frozen score
        # workload-set identity at build (the scheduler replaces workload
        # objects on watch events, so `is`-comparison detects any change);
        # compared only when workloads are placement-relevant (policy or
        # spread weight) — see _wave_encoding
        self.wkey = wkey
        # host/policy static columns bake LABEL CONTENT and workload
        # state; a labels_gen move invalidates the whole encoding (no
        # patch path for these columns — conservative, they are rare)
        self.has_static_cols = has_static_cols
        self.has_aff_pod = has_aff_pod if has_aff_pod is not None \
            else np.zeros(c_pad, dtype=bool)
        self.aff_seq = aff_seq        # expected cache.aff_seq (own folds in)
        # device bundles: the wave loop's per-node form and the strict
        # tail's projected-domain form (see _wave_encoding)
        self.aff_wave_dev = aff_wave_dev
        self.aff_tail_dev = aff_tail_dev
        self.key_node = key_node                    # np int8 [C, A, N]
        self.static_forbid_hit = static_forbid_hit  # np int8 [C, N]
        self.tail_cols = tail_cols                  # np int64 [Lp]
        self.committed_nodes = np.zeros((c_pad, n_pad), dtype=np.int32) \
            if fits_on else None
        # Protean overlays (ISSUE 8): FOREIGN churn patched in since the
        # build instead of rebuilt over. foreign_forbid [C, N] counts
        # foreign pods matching class c's required-anti selectors resident
        # on node n (merged into the device static_forbid + both fence
        # views); foreign_forbid_dom is the same over the tail's projected
        # domain columns (multi-node-domain terms of strict-tail classes).
        # Counts, not booleans, so an unbind of a PATCHED source decrements
        # exactly; a build-time static source leaving keeps its baked 0/1
        # hit (forbidding too much is the safe side — the next full
        # rebuild, whenever vocab growth forces one, trues it up).
        self.foreign_forbid = np.zeros((c_pad, n_pad), dtype=np.int32) \
            if fits_on else None
        self.foreign_forbid_dom = np.zeros(
            (c_pad, len(tail_cols)), dtype=np.int32) \
            if fits_on and tail_cols is not None else None
        self.aff_patch_dirty = False
        # per-class required term lists for foreign-event matching
        # [(class, slot, term, rep)] — empty for affinity-free encodings
        self.anti_terms: list = []
        self.aff_terms: list = []
        # raw int64 per-class delta rows (requested cpu/mem/gpu/scratch/
        # overlay + nonzero cpu/mem) for snapshot.apply_assume_delta, and
        # which classes qualify for it (no ports/volumes/extended — those
        # touch more than the seven raw columns)
        self.raw_rows = np.empty((num_classes, 7), dtype=np.int64)
        self.delta_ok = np.empty(num_classes, dtype=bool)
        for c, (req, ncpu, nmem, ports) in enumerate(derived):
            self.raw_rows[c] = (req.milli_cpu, req.memory, req.nvidia_gpu,
                                req.storage_scratch, req.storage_overlay,
                                ncpu, nmem)
            self.delta_ok[c] = not (ports or req.extended or special[c])
        # per-class PRIORITY column (ISSUE 14): rides the raw-delta fold
        # into the snapshot's band aggregates — class keys include
        # priority (state/classes.py), so this is exact per class
        self.cls_prio = np.fromiter((rep.priority for rep in reps),
                                    dtype=np.int64, count=num_classes)


class WaveHandle:
    """One in-flight pipelined wave: the un-fetched device result plus
    everything the harvest fence needs. Holding this without calling
    np.asarray on `packed` is the whole point — the device computes while
    the host does the previous wave's bookkeeping."""

    __slots__ = ("pods", "pc", "enc", "packed", "state_out", "counter_out",
                 "nodes", "blind", "pop_ts", "dispatch_ts", "pad_floor",
                 "committed_out", "strict_idx", "gangs", "wave_id",
                 "host_idx")

    def __init__(self, pods, pc, enc, packed, state_out, counter_out, nodes,
                 blind, pop_ts, dispatch_ts, pad_floor=0,
                 committed_out=None, strict_idx=None, gangs=None,
                 wave_id=-1, host_idx=None):
        self.pad_floor = pad_floor
        self.pods = pods
        self.pc = pc                  # host int32 [n] class index per pod
        self.enc = enc
        self.packed = packed          # device [3P+2] (see waves_loop)
        self.state_out = state_out    # device NodeState after the waves
        self.counter_out = counter_out  # device uint32 RR counter
        self.nodes = nodes            # device node arrays at dispatch time
        self.blind = blind            # node NAMES mutated since dispatch
        self.pop_ts = pop_ts
        self.dispatch_ts = dispatch_ts
        self.committed_out = committed_out  # device [C,N] topology occupancy
        # pods routed to the seeded strict tail (wave_strict classes) —
        # inactive on the wave path, placed by harvest's tail scan
        self.strict_idx = strict_idx if strict_idx is not None \
            else np.empty(0, dtype=np.int64)
        # quorum-ready gangs riding this wave (ISSUE 5): [(name, member
        # indices into `pods`, quorum)] — the harvest's gang fence commits
        # or atomically rolls back each one
        self.gangs = gangs or []
        # host_exact rows (ISSUE 18): riding as inactive padding-class
        # rows, placed by the harvest's exact oracle tail AFTER the
        # fence — never counted unschedulable off the device result
        self.host_idx = host_idx if host_idx is not None \
            else np.empty(0, dtype=np.int64)
        # flight-recorder wave id (ISSUE 13): joins this wave's dispatch /
        # harvest / bind-flush events on the exported timeline; -1 when
        # the recorder was off at dispatch
        self.wave_id = wave_id

    def block(self) -> None:
        """Force device completion now (sequential/debug mode): the values
        are identical whenever fetched; only the overlap is forfeited."""
        self.packed.block_until_ready()  # graftlint: sync-ok — this
        # method EXISTS to stall (overlap=False debug mode)


class WaveHarvest:
    """Fenced result of one wave: pods to bind (node_name set, already
    assumed), fence conflicts to requeue WITHOUT backoff (a capacity race
    with the blind wave, not unschedulability), unschedulable pods, and —
    for gang-bearing waves (ISSUE 5) — the gangs whose quorum committed
    (the caller marks them degraded) plus the members of gangs the fence
    ROLLED BACK atomically (requeue WITH backoff: the gang lost as a
    unit, exactly the below-quorum rollback of the classic round)."""

    __slots__ = ("bound", "conflicts", "unschedulable", "t_block",
                 "gang_committed", "gang_requeued", "liveness_requeued",
                 "conflict_reasons")

    def __init__(self, bound, conflicts, unschedulable, t_block,
                 gang_committed=None, gang_requeued=None,
                 liveness_requeued=None, conflict_reasons=None):
        self.bound = bound
        self.conflicts = conflicts
        self.unschedulable = unschedulable
        self.t_block = t_block
        self.gang_committed = gang_committed or []
        self.gang_requeued = gang_requeued or []  # [(pod, reason)]
        # rows whose target node died / was cordoned mid-flight (ISSUE 8):
        # requeue WITH backoff — not a capacity race, not unschedulability
        self.liveness_requeued = liveness_requeued or []
        # typed requeue attribution (ISSUE 15): podtrace.REASON_* code
        # per entry of `conflicts`, parallel — capacity races vs topology
        # vs stale encodings stop folding into one count
        self.conflict_reasons = conflict_reasons or []


class SchedulingEngine:
    def __init__(self, cache: SchedulerCache,
                 priorities: Tuple[Tuple[str, int], ...] = prio.DEFAULT_PRIORITIES,
                 mem_shift: int = 10, workloads_provider=None,
                 hard_pod_affinity_weight: int = 1,
                 volume_ctx=None, policy_algos=None, mesh=None):
        from kubernetes_tpu.state.volumes import VolumeContext
        self.cache = cache
        self.priorities = priorities
        # Policy-configured parameterized algorithms (ServiceAffinity,
        # NodeLabelPresence, NodeLabel, ServiceAntiAffinity) — the
        # CreateFromConfig arguments (ops/policy_algos.py)
        self.policy_algos = policy_algos
        # resident device mesh (ISSUE 12): a 1-D jax.sharding.Mesh whose
        # axis is the NODE axis. When set, every node-indexed device
        # buffer this engine owns — the snapshot sync, the wave
        # encodings' topology views, the committed-occupancy seed — is
        # uploaded SHARDED across the mesh and stays resident between
        # waves; waves_loop runs its explicit two-stage SPMD path. A
        # single-device mesh is meaningless residency — treat as None
        # (the unsharded engine IS the one-device layout).
        self.mesh = None
        self._rmesh = None
        if mesh is not None and int(mesh.devices.size) > 1:
            from kubernetes_tpu.parallel.mesh import ResidentMesh
            self.mesh = mesh
            self._rmesh = ResidentMesh(mesh)
            # the node axis pads to a multiple of BOTH the baseline
            # alignment (8) and the device count so shard_map splits it
            # evenly on any mesh size (a bare max(8, D) breaks D=3/5/6/7:
            # N padded to a multiple of 8 need not divide by D)
            import math
            self.snapshot = ClusterSnapshot(
                mem_shift=mem_shift,
                node_pad=math.lcm(8, int(mesh.devices.size)))
        else:
            self.snapshot = ClusterSnapshot(mem_shift=mem_shift)
        # PV/PVC mirror (the pvInfo/pvcInfo listers of factory.go); the
        # owner (Scheduler) mutates it and bumps .version on watch events
        self.volume_ctx = volume_ctx if volume_ctx is not None else VolumeContext()
        self.rr = oracle.RoundRobin()  # shared counter, device + oracle paths
        # Service/RC/RS/SS objects for spreading & service affinity — the
        # factory's extra informers (factory.go:120-140)
        self.workloads_provider = workloads_provider or (lambda: [])
        self.hard_pod_affinity_weight = hard_pod_affinity_weight
        self._device_nodes = None
        self._device_version = -1
        # priority-band device bundle for the wave-path victim scan
        # (ISSUE 14): uploaded on demand, keyed on snapshot.version —
        # preemption rounds are rare next to waves, so this stays out of
        # _nodes_on_device and its upload counters entirely
        self._prio_dev = None
        self._prio_dev_version = -1
        # targeted-refresh bookkeeping: when the OWNER (one Scheduler that
        # routes every cache mutation through note_node_dirty/
        # note_full_refresh) sets track_dirty, _refresh() passes the dirty
        # node set as snapshot.refresh's changed_hint instead of walking all
        # N generation counters per round. Default off: a bare engine whose
        # cache is mutated behind its back (tests, ad-hoc callers) cannot
        # uphold the hint's assertion.
        self.track_dirty = False
        self._pending_dirty: set = set()
        self._need_full_refresh = True
        # liveness fence (ISSUE 8): node names the OWNER declared dying
        # (DELETED / cordoned / NotReady watch event observed but not yet
        # applied to the cache) — the harvest fence requeues any blind-wave
        # row targeting one instead of binding into a ghost. The owner
        # marks BEFORE flushing the pipeline and clears after the event is
        # applied (the refreshed snapshot then carries the verdict itself).
        self._doomed_nodes: set = set()
        # pipelined-drain state (dispatch_waves/harvest_waves)
        self._wave_enc = None
        self._rr_chain = None  # device RR counter chaining between waves
        # per-encoding cache of waves.precompute (the capacity-INdependent
        # [C, N] tensors): every wave/tail dispatch of a drain used to
        # recompute the selector/taint/node-affinity label-axis matmuls —
        # the largest per-dispatch device cost once the loops themselves
        # went round-granular (ISSUE 5). Keyed on the encoding object and
        # the IDENTITY of the static node device arrays (_nodes_on_device
        # replaces a buffer only when the snapshot marked it dirty, so
        # identity is the exact staleness signal).
        self._pre_cache = None
        self._blind_listeners: List[set] = []  # per-inflight-wave touch sets
        # pod-axis padding floor for dispatch_waves: the pipeline pins this
        # to its chunk size so an arrival stream's ragged pops (345, 589,
        # 100, ...) all reuse ONE compiled wave shape instead of paying a
        # multi-second XLA compile per fresh power-of-2 bucket mid-stream
        self.wave_pad_floor = 0
        # conflict-round tail (ISSUE 5): the harvest's seeded strict tail
        # runs as waves.tail_rounds_loop (round-depth sequentiality, exact
        # required-affinity semantics, wave-style tie-breaks) when the
        # tail is big enough to pay for the round body; small tails keep
        # the per-pod scan, whose per-step cost is a fraction of a round.
        # GRAFT_TAIL_ROUNDS=0 forces the scan everywhere (the oracle mode
        # the tail-round fuzz compares against); GRAFT_TAIL_ROUNDS_MIN
        # moves the crossover (0 = rounds always).
        import os as _os
        self.tail_rounds = _os.environ.get("GRAFT_TAIL_ROUNDS", "1") != "0"
        self.tail_rounds_min = int(
            _os.environ.get("GRAFT_TAIL_ROUNDS_MIN", "48"))

    # ------------------------------------------------------------------ api

    def schedule(self, pods: Sequence[Pod], assume: bool = True,
                 mode: str = "strict") -> List[PlacementResult]:
        """Schedule a batch. Returns one PlacementResult per pod, in input
        order. When assume=True, successful placements are assumed into the
        cache with pod.node_name set (the caller binds asynchronously).

        mode="strict" reproduces the reference's sequential scheduleOne
        semantics exactly (engine/batch.py lax.scan); mode="wave" is the
        wave-parallel throughput mode (engine/waves.py) with identical
        predicate/priority integer semantics but batch-defined tie-spreading.
        """
        if not pods:
            return []
        infos = self._refresh()
        from kubernetes_tpu.ops.affinity import AffinityData, \
            collect_pod_pairs, intern_topology_pairs
        all_pairs, aff_pairs = collect_pod_pairs(infos)
        # topology keys referenced by ANY affinity term (pending or existing)
        # must be in the label vocab BEFORE the label matrix is finalized —
        # a key only an existing pod's anti-affinity mentions would otherwise
        # have no domain columns and the symmetry forbid would silently
        # evaporate (r2 correctness bug; ref predicates.go:1146)
        intern_topology_pairs(self.snapshot, pods, aff_pairs)
        # ClassBatch next: selector compilation may grow the label vocab and
        # rebuild the label matrix; upload happens after, dirty-arrays only.
        # Encoding runs once per distinct pod spec (state/classes.py — the
        # tensor analog of the equivalence cache, equivalence_cache.go:54).
        batch = ClassBatch(pods, self.snapshot)

        # Affinity/spread class data (ops/affinity.py): static domain
        # vectors vs existing pods, class-to-class match matrices for
        # in-batch interactions, workload membership for spreading. Replaces
        # the round-1 host-path routing of every affinity-bearing pod —
        # only slot-overflow classes fall back to the oracle now.
        c_pad = bucket(batch.num_classes + 1)
        adata = AffinityData(batch.reps, self.snapshot, all_pairs, aff_pairs,
                             self.workloads_provider(),
                             self.hard_pod_affinity_weight, c_pad=c_pad)
        for c in np.nonzero(adata.overflow[:batch.num_classes])[0]:
            batch.mark_host_check_class(int(c))
        policy_active = self.policy_algos is not None \
            and self.policy_algos.active
        workloads_now = None
        if policy_active:
            workloads_now = self.workloads_provider()
            # service-coupled classes are order-dependent in-batch (the
            # reference's pod lister is the scheduler cache) -> host path
            for c in np.nonzero(self.policy_algos.needs_host(
                    batch.reps, workloads_now))[0]:
                batch.mark_host_check_class(int(c))

        # Split BEFORE the per-class static arrays and device transfers:
        # a mixed batch throws this call's remaining staging work away.
        nhc = batch.reps_batch.needs_host_check[batch.pod_class]
        if mode == "strict" and assume and nhc.any() and not nhc.all():
            # exact scheduleOne sequencing across the host/device boundary:
            # a host-path pod between two device pods must see the first's
            # commit and be seen by the second's (scheduler.go:253 is one
            # strict FIFO). Process maximal same-path runs in order, each
            # through the full pipeline against the updated cache; flags are
            # class-deterministic, so each run is homogeneous and recursion
            # terminates after one level.
            results = []
            i = 0
            while i < len(pods):
                j = i + 1
                while j < len(pods) and nhc[j] == nhc[i]:
                    j += 1
                results.extend(self.schedule(list(pods[i:j]), assume=True,
                                             mode=mode))
                i = j
            return results

        policy_arrays = None
        if policy_active:
            policy_arrays = self.policy_algos.static_class_arrays(
                batch.reps, self.snapshot, workloads_now, all_pairs, c_pad,
                skip=batch.reps_batch.needs_host_check[:batch.num_classes])
        w_ip = sum(w for nm, w in self.priorities
                   if nm == "InterPodAffinityPriority")
        w_sp = sum(w for nm, w in self.priorities
                   if nm == "SelectorSpreadPriority")
        fits_on = adata.fits_needed
        prio_on = bool(w_ip) and adata.prio_needed
        spread_on = bool(w_sp) and adata.spread_needed
        aff_mode = (fits_on, prio_on, spread_on)
        aff_arrays = adata.device_arrays() if any(aff_mode) else None
        kernel_priorities = self.priorities if aff_arrays is not None else \
            tuple((nm, w) for nm, w in self.priorities
                  if nm not in prio.AFFINITY_PRIORITIES)
        # size the port bitmap to the highest word any node uses or any batch
        # pod requests (power-of-2 bucketed so the compiled shapes are stable)
        max_words = self.snapshot.port_words_used()
        if np.any(batch.reps_batch.ports >= 0):
            max_words = max(max_words,
                            int(batch.reps_batch.ports.max()) // 32 + 1)
        port_words = bucket(max(max_words, 1), lo=1)
        nodes = self._nodes_on_device(port_words=port_words)

        fast_idx = np.nonzero(~nhc)[0]
        slow_idx = np.nonzero(nhc)[0].tolist()
        results: List[Optional[PlacementResult]] = [None] * len(pods)

        if len(fast_idx):
            # shape bucketing: pad the class axis and the pod axis to
            # power-of-2 buckets so round-over-round batch sizes reuse the
            # same compiled kernels. Padding classes are `impossible` (fit
            # nothing, commit nothing, no RR ticks) and padding pods map to
            # the first padding class.
            from kubernetes_tpu.ops.predicates import pod_arrays_padded
            cls_arr = pod_arrays_padded(batch.reps_batch, c_pad)
            if policy_arrays is not None:
                pfit, pscore = policy_arrays
                if pfit is not None:
                    cls_arr["policy_fit"] = jnp.asarray(pfit)
                if pscore is not None:
                    cls_arr["policy_score"] = jnp.asarray(pscore)
            pf = len(fast_idx)
            p_pad = bucket(pf)
            pc_fast = np.full(p_pad, batch.num_classes, dtype=np.int32)
            pc_fast[:pf] = batch.pod_class[fast_idx]
            state = NodeState(nodes["requested"], nodes["nonzero"],
                              nodes["pod_count"], nodes["port_bitmap"],
                              nodes["vol_present"], nodes["vol_rw"],
                              nodes["pd_present"], nodes["pd_counts"])
            if mode == "wave":
                selected, fit_counts, rr_end = self._run_wave(
                    batch, adata, cls_arr, nodes, state, fast_idx, pc_fast,
                    pf, aff_arrays, aff_mode, kernel_priorities,
                    (w_ip, w_sp))
            else:
                selected, fit_counts, _, rr_end = gather_place_batch(
                    cls_arr, jnp.asarray(pc_fast), nodes, state,
                    jnp.uint32(self.rr.counter), kernel_priorities,
                    aff=aff_arrays, aff_mode=aff_mode)
                # the synchronous engine's result fetch: schedule() owes
                # its caller host placements, so the stall is the contract
                selected = np.asarray(selected)[:pf]  # graftlint: sync-ok
                fit_counts = np.asarray(fit_counts)[:pf]  # graftlint: sync-ok
            self.rr.counter = int(rr_end)  # graftlint: sync-ok — scalar
            # draw-count fetch rides the result fetch above (device idle)
            names = self.snapshot.node_names
            placements = []
            # plain-int lists: numpy scalar indexing in a 30k-iteration loop
            # costs ~3x a list walk
            sel_l = np.asarray(selected).tolist()
            fc_l = np.asarray(fit_counts).tolist()
            pc_l = pc_fast.tolist()
            mk = PlacementResult
            for j, i in enumerate(fast_idx.tolist()):
                sel = sel_l[j]
                pod = pods[i]
                if sel >= 0:
                    name = names[sel]
                    results[i] = mk(pod, name, fc_l[j])
                    if assume:
                        pod.node_name = name
                        placements.append((pod, pc_l[j]))
                else:
                    results[i] = mk(pod, None, fc_l[j])
            if placements:
                # one lock + one derived-quantity walk per PLACED class
                derived: Dict[int, tuple] = {}
                for _, c in placements:
                    if c not in derived:
                        rep = batch.reps[c]
                        derived[c] = (rep.resource_request(),
                                      *rep.nonzero_request(),
                                      rep.used_ports())
                self.cache.assume_pods_bulk(placements, derived)
                self._touch(p.node_name for p, _ in placements)

        # exact host path for over-approximated pods, AFTER device placements
        # so they see committed capacity (FIFO order within themselves)
        if slow_idx:
            from kubernetes_tpu.ops.oracle_ext import SchedulingContext
            infos = self.cache.node_infos()
            names = self.snapshot.node_names
            ctx = SchedulingContext(
                infos, self.workloads_provider(),
                hard_pod_affinity_weight=self.hard_pod_affinity_weight,
                volume_ctx=self.volume_ctx,
                policy_algos=self.policy_algos)
            for i in slow_idx:
                name = oracle.schedule_one(pods[i], names, infos, self.rr,
                                           self.priorities, ctx)
                results[i] = PlacementResult(pods[i], name, 1 if name else 0)
                if name is not None and assume:
                    self._assume(pods[i], name)
                    infos = self.cache.node_infos()
                    ctx.infos = infos
                    ctx.invalidate()

        return results  # type: ignore[return-value]

    # ------------------------------------------------------------- internals

    def _run_wave(self, batch, adata, cls_arr, nodes, state, fast_idx,
                  pc_fast, pf, aff_arrays, aff_mode, kernel_priorities,
                  weights):
        """Wave mode with affinity routing: classes whose REQUIRED
        (anti-)affinity makes placement order-dependent run through the
        strict scan AFTER the wave pass — seeded with the wave's topology
        occupancy so in-batch interactions stay exact — while everything
        else takes the throughput path with batch-frozen spread/interpod
        scores (waves.frozen_affinity_scores)."""
        w_ip, w_sp = weights
        fits_on, prio_on, spread_on = aff_mode
        extra = None
        if prio_on or spread_on:
            extra = waves.frozen_affinity_scores(
                cls_arr, nodes, state, aff_arrays,
                (w_ip if prio_on else 0, w_sp if spread_on else 0))
        ser = adata.serialize[pc_fast[:pf]]
        selected = np.full(pf, -1, dtype=np.int32)
        fit_counts = np.zeros(pf, dtype=np.int32)
        rr = self.rr.counter
        wave_pos = np.nonzero(~ser)[0]
        strict_pos = np.nonzero(ser)[0]
        state_cur = state
        if len(wave_pos):
            wp = len(wave_pos)
            pcw = np.full(bucket(wp), batch.num_classes, dtype=np.int32)
            pcw[:wp] = pc_fast[wave_pos]
            # aff/aff_mode reach only the straggler fallback inside
            # place_waves: preferred scoring stays batch-frozen (extra),
            # so prio/spread are off there to avoid double-counting
            sel_w, fc_w, state_cur, rr = waves.place_waves(
                cls_arr, nodes, state_cur, pcw, rr, kernel_priorities,
                extra_score=extra, aff=aff_arrays,
                aff_mode=(fits_on, False, False))
            selected[wave_pos] = sel_w[:wp]
            fit_counts[wave_pos] = fc_w[:wp]
        if len(strict_pos):
            sp_n = len(strict_pos)
            pcs = np.full(bucket(sp_n), batch.num_classes, dtype=np.int32)
            pcs[:sp_n] = pc_fast[strict_pos]
            aff_init = None
            if aff_arrays is not None:
                c_dim = aff_arrays["m_aff"].shape[0]
                comm_np = np.zeros((c_dim, int(nodes["alloc"].shape[0])),
                                   dtype=np.int32)
                for j in wave_pos:
                    if selected[j] >= 0:
                        comm_np[pc_fast[j], selected[j]] += 1
                committed0 = jnp.asarray(comm_np)
                commdom0 = committed0 @ nodes["labels"].astype(jnp.int32)
                comm_cnt0 = committed0.sum(axis=1)
                aff_init = (commdom0, committed0, comm_cnt0)
            sel_s, fc_s, _, rr_d = gather_place_batch(
                cls_arr, jnp.asarray(pcs), nodes, state_cur,
                jnp.uint32(rr), kernel_priorities, aff=aff_arrays,
                aff_mode=aff_mode, aff_init=aff_init)
            # strict-tail result fetch (classic wave mode is synchronous
            # by definition — the caller consumes placements immediately)
            selected[strict_pos] = np.asarray(sel_s)[:sp_n]  # graftlint: sync-ok
            fit_counts[strict_pos] = np.asarray(fc_s)[:sp_n]  # graftlint: sync-ok
            rr = int(rr_d)  # graftlint: sync-ok (scalar, device idle)
        return selected, fit_counts, rr

    def _assume(self, pod: Pod, node_name: str) -> None:
        pod.node_name = node_name
        self.cache.assume_pod(pod)
        self._touch((node_name,))

    # ------------------------------------------------- targeted refresh

    def _touch(self, node_names) -> None:
        """Record cache mutations for BOTH consumers: the targeted-refresh
        dirty set (cleared each refresh) and any in-flight wave's blind set
        (cleared at that wave's harvest — its fence must re-validate
        against exactly these nodes)."""
        if self.track_dirty or self._blind_listeners:
            names = list(node_names)
            if self.track_dirty:
                self._pending_dirty.update(names)
            for s in self._blind_listeners:
                s.update(names)

    def note_node_dirty(self, *node_names: str) -> None:
        """The owner observed a cache mutation touching these nodes (watch
        event applied, bind forgotten)."""
        self._touch(node_names)

    def note_full_refresh(self) -> None:
        """The owner cannot name what changed (node membership/spec moved,
        assumed-pod TTL expiry) — the next refresh walks everything."""
        self._need_full_refresh = True

    def note_node_doomed(self, *node_names: str) -> None:
        """The owner observed a node-dying watch event (DELETED, cordon,
        NotReady) it has NOT yet applied: any in-flight wave row targeting
        these nodes must requeue at the fence, not bind (ISSUE 8)."""
        self._doomed_nodes.update(node_names)

    def clear_node_doomed(self, *node_names: str) -> None:
        """The dying event is applied — the snapshot now carries the
        verdict (schedulable=False / node absent), so the doom mark is
        redundant for every later dispatch."""
        self._doomed_nodes.difference_update(node_names)

    def _refresh(self) -> Dict[str, object]:
        """Snapshot refresh with the targeted-hint fast path when the owner
        tracks dirt (ISSUE 2: the batch drain's analog of the extender's
        per-bind changed_hint). Returns the infos map."""
        infos = self.cache.node_infos()
        hint = None
        if self.track_dirty and not self._need_full_refresh \
                and self.snapshot._shape_sig is not None:
            hint = sorted(self._pending_dirty)
        self.snapshot.refresh(infos, volume_ctx=self.volume_ctx,
                              changed_hint=hint)
        self._pending_dirty.clear()
        self._need_full_refresh = False
        return infos

    _NODE_ARRAY_KEYS = ("alloc", "requested", "nonzero", "pod_count",
                        "allowed_pods", "schedulable", "mem_pressure",
                        "disk_pressure", "labels", "taints_sched",
                        "taints_pref", "port_bitmap", "valid", "avoid",
                        "image_sizes", "has_zone", "vol_present", "vol_rw",
                        "pd_present", "pd_counts", "pd_kind", "pd_max")

    def _nodes_on_device(self, port_words: int = 1):
        """Incremental host->HBM sync: re-upload an array only when its shape
        changed or the snapshot marked it dirty. Steady-state rounds move only
        requested/nonzero/pod_count (~KBs), not the 40MB+ full snapshot.

        port_words: how many 32-bit words of the 65536-bit per-node port
        bitmap to ship — the caller sizes it to cover the highest port in use
        by any node or requested by any batch pod (bucketed, so width changes
        rarely); a cluster with no host ports uploads one zero word per node
        instead of 8KB.

        With a resident mesh (ISSUE 12) every array uploads SHARDED via the
        shared spec tables and the dynamic arrays ride the ROW-DELTA path:
        when the snapshot can name the touched rows (snapshot.dirty_rows —
        the apply_assume_delta / bulk-writer contract), only the shards
        owning those rows re-upload; untouched shards keep their existing
        device buffers by reference. The upload unit is a whole shard, so
        a micro-wave's assume fold moves O(touched_shards x N/D) rows —
        a fraction of the full [N, R] mirror whenever the fold doesn't
        touch every shard (engine.shard_upload_bytes counts the actual
        traffic)."""
        snap = self.snapshot
        if self._device_nodes is None:
            self._device_nodes = {}
        rmesh = self._rmesh
        rows = snap.dirty_rows if rmesh is not None else None
        uploaded = 0
        delta_used = False
        delta_bytes = 0
        for k in self._NODE_ARRAY_KEYS:
            if k == "port_bitmap":
                host = snap.port_bitmap[:, :port_words]
            else:
                host = getattr(snap, k)
            cur = self._device_nodes.get(k)
            if cur is None or cur.shape != host.shape or k in snap.dirty:
                # COPY, never alias: the CPU backend zero-copies aligned
                # numpy buffers, and these snapshot arrays are mutated in
                # place (refresh deltas, apply_assume_delta) while a
                # pipelined wave may still be executing against them
                # asynchronously. The pragma makes GL001 reject any future
                # jnp.asarray "optimization" here; GRAFT_SANITIZE=1
                # additionally asserts the upload really did not alias.
                # (The mesh path inherits the contract: upload_copied
                # sharded copies host-side before placement, and
                # ResidentMesh.update_rows copies each touched slice.)
                if rmesh is not None:
                    if rows is not None and cur is not None \
                            and cur.shape == host.shape \
                            and k in snap.DYNAMIC:
                        self._device_nodes[k] = rmesh.update_rows(
                            cur, host, rows)
                        delta_used = True
                        delta_bytes += rmesh.touched_nbytes(host, rows)
                        continue
                    self._device_nodes[k] = sanitize.upload_copied(  # graftlint: copy-required
                        host, sharding=rmesh.node_sharding(k, host.ndim))
                else:
                    self._device_nodes[k] = sanitize.upload_copied(  # graftlint: copy-required
                        np.ascontiguousarray(host)
                        if k == "port_bitmap" else host)
                uploaded += 1
        if uploaded or delta_used:
            from kubernetes_tpu.utils.trace import COUNTERS
            if uploaded:
                COUNTERS.inc("engine.device_upload_arrays", uploaded)
            if delta_used:
                # DISTINCT rows this sync shipped through the per-shard
                # delta path (counted once, not once per dynamic array —
                # comparable to snapshot.assume_delta_rows' per-placement
                # count), plus the actual bytes moved (whole touched
                # shards, every dynamic array included)
                COUNTERS.inc("engine.shard_delta_rows", len(rows))
                COUNTERS.inc("engine.shard_upload_bytes", delta_bytes)
        snap.dirty.clear()
        if rmesh is not None:
            snap.dirty_rows = set()  # arm row tracking for the next sync
        self._device_version = snap.version
        return self._device_nodes

    # ------------------------------------------- wave-path preemption

    def _prio_on_device(self):
        """Device bundle for the victim scan: spare capacity columns plus
        the priority-band aggregates, quantized at upload (band sums
        CEIL, need floors — the over-approximation direction
        ops/preempt.py documents). Re-uploaded whenever the snapshot
        version moved; ~[N, B] int32s, a fraction of one wave upload."""
        snap = self.snapshot
        if self._prio_dev is not None \
                and self._prio_dev_version == snap.version:
            return self._prio_dev
        shift = snap.mem_shift
        host = {
            "spare_cpu": (snap.alloc[:, R_CPU].astype(np.int64)
                          - snap.requested[:, R_CPU]).astype(np.int32),
            "spare_mem": (snap.alloc[:, R_MEM].astype(np.int64)
                          - snap.requested[:, R_MEM]).astype(np.int32),
            "pod_count": snap.pod_count,
            "allowed": snap.allowed_pods,
            "band_cpu": snap.band_cpu.astype(np.int32),
            "band_mem": (-((-snap.band_mem) >> shift)).astype(np.int32),
            "band_count": snap.band_count,
            "band_prio": np.clip(snap.band_prio_host, -(2 ** 31) + 1,
                                 2 ** 31 - 1).astype(np.int32),
        }
        # COPY, never alias: pod_count/allowed/band_* are live snapshot
        # arrays mutated in place between preemption rounds (refresh
        # deltas, apply_assume_delta band folds)
        self._prio_dev = {
            k: sanitize.upload_copied(v)  # graftlint: copy-required
            for k, v in host.items()}
        self._prio_dev_version = snap.version
        return self._prio_dev

    def preempt_scan(self, pods: Sequence[Pod]):
        """ONE fused [C, N] victim pre-filter for a round of preemptors
        (ISSUE 14): returns (candidate [C, N] bool, bound [C, N] int32,
        class_of [len(pods)]) with C the padded unique-(need, priority)
        class count — or None when the band vocab overflowed / priorities
        exceed int32, routing the caller to the exact host pre-filter."""
        from kubernetes_tpu.ops import preempt as preempt_ops
        from kubernetes_tpu.utils.trace import COUNTERS

        snap = self.snapshot
        if snap.prio_band_overflow or not hasattr(snap, "band_cpu") \
                or not pods:
            return None
        shift = snap.mem_shift
        uniq: Dict[tuple, int] = {}
        rows: List[tuple] = []
        class_of: List[int] = []
        for p in pods:
            if not (-(2 ** 31) < p.priority < 2 ** 31):
                return None
            req = p.resource_request()
            key = (req.milli_cpu, req.memory, p.priority)
            c = uniq.get(key)
            if c is None:
                c = len(rows)
                uniq[key] = c
                # need: cpu exact, mem FLOOR-quantized (under-estimates
                # need — the superset direction)
                rows.append((req.milli_cpu, req.memory >> shift,
                             p.priority))
            class_of.append(c)
        # pad the class axis to the bucket ladder (GL003: a ragged
        # per-round preemptor count must never reach the jit); padding
        # rows carry PAD_PRIO, below every band — no candidates
        c_pad = bucket(len(rows), lo=4)
        need_cpu = np.zeros(c_pad, dtype=np.int32)
        need_mem = np.zeros(c_pad, dtype=np.int32)
        prio = np.full(c_pad, preempt_ops.PAD_PRIO, dtype=np.int32)
        for c, (cpu, mem_q, pr) in enumerate(rows):
            need_cpu[c] = min(cpu, 2 ** 31 - 1)
            need_mem[c] = min(mem_q, 2 ** 31 - 1)
            prio[c] = pr
        dev = self._prio_on_device()
        COUNTERS.inc("engine.preempt_scan_dispatch")
        cand_d, bound_d = preempt_ops.victim_scan_jit(
            jnp.asarray(need_cpu), jnp.asarray(need_mem),
            jnp.asarray(prio), dev["spare_cpu"], dev["spare_mem"],
            dev["pod_count"], dev["allowed"], dev["band_cpu"],
            dev["band_mem"], dev["band_count"], dev["band_prio"])
        # the scan's one result fetch: the host planner consumes the
        # candidate rows NOW — a preemption round is synchronous by
        # contract (it runs inside the harvest tail)
        cand = np.asarray(cand_d)  # graftlint: sync-ok
        bound = np.asarray(bound_d)  # graftlint: sync-ok (same fetch)
        return cand, bound, class_of

    # ------------------------------------------------- pipelined drain

    def _kernel_priorities(self) -> Tuple[Tuple[str, int], ...]:
        return tuple((nm, w) for nm, w in self.priorities
                     if nm not in prio.AFFINITY_PRIORITIES)

    _STATE_NODE_KEYS = frozenset({
        "requested", "nonzero", "pod_count", "port_bitmap",
        "vol_present", "vol_rw", "pd_present", "pd_counts",
        # node CONDITION arrays flip under churn (kills, NotReady flaps,
        # cordons, respawns) but precompute does not read them since
        # ISSUE 8 (node_condition_fit is ANDed fresh per dispatch) —
        # keying on them rebuilt the ~1s-at-5k-nodes static pre once per
        # fault event, which IS the churn throughput collapse
        "schedulable", "valid", "mem_pressure", "disk_pressure"})

    def _tail_wave_pre(self, enc: "_WaveEncoding", nodes):
        """The drain's shared waves.precompute instance (see _pre_cache).
        precompute reads only the class encoding and STATIC node arrays —
        the evolving NodeState is threaded separately — and it skips
        InterPodAffinity/SelectorSpread names outright, so one instance
        computed at the kernel priorities serves both the wave loop and
        the (possibly IP-bearing) tail priorities byte-for-byte."""
        from kubernetes_tpu.utils.trace import COUNTERS

        # the key holds the STATIC device arrays THEMSELVES (not their
        # id()s): the cache must keep them alive so a freed buffer's
        # recycled address can never alias a fresh upload into a stale hit
        key = tuple(nodes[k] for k in sorted(nodes)
                    if k not in self._STATE_NODE_KEYS)
        hit = self._pre_cache
        if hit is not None and hit[0] is enc and len(hit[1]) == len(key) \
                and all(a is b for a, b in zip(hit[1], key)):
            return hit[2]
        COUNTERS.inc("engine.wave_pre_build")
        pre = waves.precompute_jit(enc.cls_arr, nodes,
                                   self._kernel_priorities())
        self._pre_cache = (enc, key, pre)
        return pre

    # ---------------------------------------- Protean delta patch (ISSUE 8)

    def _try_patch_foreign(self, enc: "_WaveEncoding") -> bool:
        """Absorb FOREIGN occupancy churn into the cached wave encoding by
        patching exactly the rows it touched (PAPERS.md §Protean: key the
        cache on what invalidates it) instead of rebuilding AffinityData
        wholesale. Patchable events are plain pods entering/leaving known
        nodes: a plain pod matching an encoded class's required-ANTI
        selector adds/removes a forbidden source on exactly one node (and,
        for strict-tail classes, its projected domain columns); a plain
        pod matching nothing is a no-op for every topology view. Returns
        False — rebuild — when the event log no longer covers the gap, a
        churned pod CARRIES (anti-)affinity terms (it is a potential
        symmetry source whose own terms bake into forbid_static), it
        matches an encoded class's own required-AFFINITY selector (the
        allow set must both grow and shrink exactly), or its node is
        unknown to the snapshot. Delta-0 events (the pod's NodeInfo
        became a tombstone stub under the same name) are no-op patches:
        the snapshot keeps the row and its labels, so nothing the build
        resolved through that node moved."""
        from kubernetes_tpu.ops.affinity import _has_affinity
        from kubernetes_tpu.ops.oracle_ext import term_matches_pod
        from kubernetes_tpu.utils.trace import COUNTERS

        events = self.cache.aff_events_since(enc.aff_seq)
        if events is None:
            return False
        if not events:
            return True
        snap = self.snapshot
        ad = enc.adata
        patched = 0
        touched = False
        for _seq, pod, node_name, delta in events:
            if delta == 0:
                # "structure moved" sentinel: the pod's NodeInfo became a
                # TOMBSTONE stub under the same name (cache.remove_node).
                # The snapshot keeps the row and its label content, so
                # every domain the build resolved through that node —
                # the pod's own contributions AND any symmetry terms it
                # carries — is still exact: a tombstone move is a no-op
                # for the topology views whatever the pod carries.
                patched += 1
                continue
            if _has_affinity(pod):
                return False  # potential symmetry source entering or
                # leaving: its own terms bake into forbid_static — no
                # row patch expresses that
            if ad is None:
                # affinity-free encoding: plain churn cannot touch it —
                # advancing the expectation IS the patch
                patched += 1
                continue
            for _c, _s, term, rep in enc.aff_terms:
                if term_matches_pod(term, rep, pod):
                    return False  # allow-set delta: must be exact both ways
            n_idx = snap.node_index.get(node_name, -1)
            if n_idx < 0:
                return False
            for c, a, term, rep in enc.anti_terms:
                if not term_matches_pod(term, rep, pod):
                    continue
                ff = enc.foreign_forbid
                if ff is not None:
                    if delta > 0:
                        ff[c, n_idx] += 1
                        touched = True
                    elif ff[c, n_idx] > 0:
                        ff[c, n_idx] -= 1
                        touched = True
                    # else: a build-time static source left — the baked
                    # 0/1 hit cannot decrement; stay forbidden (safe side)
                fd = enc.foreign_forbid_dom
                if fd is not None and enc.tail_cols is not None \
                        and enc.tail_cols.size:
                    cols_hit = (
                        (ad.anti_keymask[c, a, enc.tail_cols] > 0)
                        & (snap.labels[n_idx, enc.tail_cols] > 0))
                    if delta > 0:
                        fd[c, cols_hit] += 1
                        touched = True
                    else:
                        dec = cols_hit & (fd[c] > 0)
                        if dec.any():
                            fd[c, dec] -= 1
                            touched = True
            patched += 1
        enc.aff_seq = events[-1][0]
        if touched:
            enc.aff_patch_dirty = True
        COUNTERS.inc("engine.aff_patch_rows", patched)
        if patched and RECORDER.enabled:
            RECORDER.record(flightrec.PATCH, a=patched)
        return True

    def _try_patch_labels(self, enc: "_WaveEncoding", infos) -> bool:
        """Absorb label-CONTENT churn (relabels to already-interned
        columns) into the cached encoding by re-deriving the topology
        projections of exactly the touched node ROWS. The gate is
        COLUMN-aware: a relabel only forces a rebuild when the changed
        columns intersect the domains a baked array actually resolved
        through — a zone flip on a node hosting anti-affinity targets is
        patchable when every anti term keys on hostname columns (the
        dominant production shape). Rebuild triggers: a changed column
        under a term keymask whose selector matches a resident pod (the
        baked forbid/allow domain moved), a resident pods_with_affinity
        whose OWN term topology keys cover a changed column (its symmetry
        contribution moved), patched foreign-forbid weight riding changed
        columns, or a relabel that merges two nodes into one anti domain
        of a wave-eligible class (the singleton-domain invariant the
        per-node wave mask rides)."""
        from kubernetes_tpu.ops.affinity import _term_topology_keys
        from kubernetes_tpu.ops.oracle_ext import term_matches_pod
        from kubernetes_tpu.utils.trace import COUNTERS

        snap = self.snapshot
        entries = snap.labels_rows_since(enc.labels_gen)
        if entries is None:
            return False
        if not entries:
            return True
        ad = enc.adata
        if ad is None:
            enc.labels_gen = snap.labels_gen
            return True
        L = ad.anti_keymask.shape[2]
        by_row: Dict[int, set] = {}
        for r, cols in entries:
            by_row.setdefault(r, set()).update(
                int(c) for c in cols if c < L)
        rows = sorted(by_row)
        names = snap.node_names
        vocab_cols = snap.label_vocab.by_key
        for r in rows:
            if r >= len(names):
                return False
            info = infos.get(names[r])
            if info is None:
                return False
            cols = np.asarray(sorted(by_row[r]), dtype=np.int64)
            if cols.size == 0:
                continue
            for c, a, term, rep in enc.anti_terms:
                if ad.anti_keymask[c, a, cols].any() and any(
                        term_matches_pod(term, rep, q) for q in info.pods):
                    return False  # a baked forbid source's domain moved
            for c, s, term, rep in enc.aff_terms:
                if ad.aff_keymask[c, s, cols].any() and any(
                        term_matches_pod(term, rep, q) for q in info.pods):
                    return False  # a baked allow source's domain moved
            colset = by_row[r]
            for q in info.pods_with_affinity:
                for key in _term_topology_keys(q):
                    if any(k < L and k in colset
                           for k in vocab_cols.get(key, ())):
                        return False  # a symmetry source's domain moved
            if enc.foreign_forbid is not None \
                    and enc.foreign_forbid[:, r].any() and any(
                        ad.anti_keymask[c, a, cols].any()
                        for c, a, _t, _rep in enc.anti_terms):
                return False  # patched per-node weight resolved through
                # a column this relabel moved
            if enc.foreign_forbid_dom is not None \
                    and enc.tail_cols is not None and enc.tail_cols.size:
                in_tail = np.isin(enc.tail_cols, cols)
                if in_tail.any() \
                        and enc.foreign_forbid_dom[:, in_tail].any():
                    return False
        if enc.key_node is not None:
            km = ad.anti_keymask                            # [C, A, L]
            wave_cls = ~ad.wave_strict                      # [C]
            km_wave = km[wave_cls]
            all_cols = sorted(set().union(*by_row.values())) \
                if by_row else []
            if km_wave.size and all_cols:
                # singleton-domain invariant check over the wave-eligible
                # classes' anti columns this relabel touched
                cols_arr = np.asarray(all_cols, dtype=np.int64)
                active = km_wave.astype(bool).any(axis=(0, 1))[cols_arr]
                hit = cols_arr[active]
                if hit.size and np.any(
                        snap.domain_node_counts()[hit] > 1):
                    return False
            C_, A_, L_ = km.shape
            lab_t = snap.labels[rows].astype(np.float64).T  # [L, r]
            kn_rows = ((km.reshape(C_ * A_, L_).astype(np.float64) @ lab_t)
                       > 0).reshape(C_, A_, len(rows))
            # copy-on-write: the current arrays back frozen device uploads
            # (sanitize seals them) — never mutate them in place
            key_node = enc.key_node.copy()
            key_node[:, :, rows] = kn_rows.astype(np.int8)
            enc.key_node = key_node
            sfh = enc.static_forbid_hit.copy()
            sfh[:, rows] = ((ad.forbid_static.astype(np.float64) @ lab_t)
                            > 0).astype(np.int8)
            enc.static_forbid_hit = sfh
            enc.aff_patch_dirty = True
        if enc.tail_cols is not None and enc.aff_tail_dev is not None:
            enc.aff_tail_dev["labels_aff"] = sanitize.upload_frozen(
                snap.labels[:, enc.tail_cols],
                sharding=None if self._rmesh is None
                else self._rmesh.aff_sharding("labels_aff"))
        enc.labels_gen = snap.labels_gen
        COUNTERS.inc("engine.label_patch_rows", len(rows))
        if rows and RECORDER.enabled:
            RECORDER.record(flightrec.PATCH, b=len(rows))
        return True

    def _flush_aff_patches(self, enc: "_WaveEncoding") -> None:
        """Re-upload the device views a patch invalidated — one batched
        refresh per dispatch, however many events were absorbed. Fresh
        temporaries are frozen (never the live overlays: those keep
        mutating patch over patch)."""
        if not enc.aff_patch_dirty:
            return

        def _sh(k):
            return None if self._rmesh is None \
                else self._rmesh.aff_sharding(k)
        if enc.aff_wave_dev is not None:
            merged = enc.static_forbid_hit.astype(np.int32)
            if enc.foreign_forbid is not None:
                merged = merged + enc.foreign_forbid
            enc.aff_wave_dev["static_forbid"] = sanitize.upload_frozen(
                np.minimum(merged, 127).astype(np.int8),
                sharding=_sh("static_forbid"))
            enc.aff_wave_dev["key_node"] = sanitize.upload_frozen(
                enc.key_node.copy(), sharding=_sh("key_node"))
        if enc.aff_tail_dev is not None and enc.tail_cols is not None:
            base = enc.adata.forbid_static[:, enc.tail_cols].astype(np.int32)
            if enc.foreign_forbid_dom is not None:
                base = base + enc.foreign_forbid_dom
            enc.aff_tail_dev["forbid_static"] = sanitize.upload_frozen(
                np.minimum(base, 127).astype(np.int8),
                sharding=_sh("forbid_static"))
        enc.aff_patch_dirty = False

    def _wave_encoding(self, pods: Sequence[Pod], infos):
        """(encoding, pod_class[n]) for a pipeline chunk, via the
        (vocab_gen, aff_seq, workload-identity)-keyed reuse cache.
        EVERY chunk shape is wave-eligible now (ISSUE 18): affinity
        classes the topology counters express run per-wave on device
        (ISSUE 3), label-pure host-check classes carry an exact
        precomputed host_fit column, Policy classes carry frozen
        policy_fit/policy_score columns with a fence-side exact
        re-check, and everything else (live-NodeInfo ports, preference
        overflow, Policy order-dependence, affinity slot overflow)
        rides inactive and places at the harvest's exact oracle tail."""
        import dataclasses as _dc

        from kubernetes_tpu.ops.affinity import (
            AffinityData,
            _has_affinity,
            collect_pod_pairs,
            intern_topology_pairs,
        )
        from kubernetes_tpu.ops.predicates import pod_arrays_padded
        from kubernetes_tpu.state.classes import pod_class_key
        from kubernetes_tpu.utils.trace import COUNTERS

        snap = self.snapshot
        enc = self._wave_enc
        policy_active = self.policy_algos is not None \
            and self.policy_algos.active
        w_ip = sum(w for nm, w in self.priorities
                   if nm == "InterPodAffinityPriority")
        w_sp = sum(w for nm, w in self.priorities
                   if nm == "SelectorSpreadPriority")
        # workloads are placement-relevant only through Policy predicates
        # or a live SelectorSpread weight; otherwise their churn can never
        # change a placement and the encoding ignores them entirely
        workloads_now = tuple(self.workloads_provider()) \
            if (policy_active or w_sp) else ()
        fresh = enc is not None and enc.vocab_gen == snap.vocab_gen
        if fresh and enc.policy_on != policy_active:
            fresh = False
        if fresh and (policy_active or w_sp):
            wk = enc.wkey
            if len(wk) != len(workloads_now) or not all(
                    a is b for a, b in zip(wk, workloads_now)):
                # workload set moved (the scheduler replaces workload
                # objects on watch events, so identity detects every
                # change): the frozen policy/spread arrays and the
                # needs_host classification are stale — full rebuild
                fresh = False
        if fresh and enc.has_static_cols \
                and enc.labels_gen != snap.labels_gen:
            # host/policy static columns bake label content; checked
            # BEFORE the affinity label-patch path so a patched encoding
            # can never keep a stale column
            fresh = False
        if fresh and enc.adata is not None \
                and enc.labels_gen != snap.labels_gen:
            # label content moved: patch the touched rows (Protean,
            # ISSUE 8) or fall through to the rebuild
            fresh = self._try_patch_labels(enc, infos)
        if fresh and enc.aff_seq != self.cache.aff_seq:
            # foreign occupancy churn: patch the touched rows or rebuild
            fresh = self._try_patch_foreign(enc)
        if fresh:
            key_index = enc.key_index
            pc = np.empty(len(pods), dtype=np.int32)
            hit = True
            for i, p in enumerate(pods):
                c = key_index.get(pod_class_key(p), -1)
                if c < 0:
                    hit = False
                    break
                pc[i] = c
            if hit:
                COUNTERS.inc("engine.wave_encode_reuse")
                return enc, pc
        # rebuild over the union with the cached reps so chunks alternating
        # between two class sets don't thrash the cache. Seeding FIRST also
        # keeps prior class indices stable, so a mid-drain rebuild leaves
        # any in-flight handle's class rows meaningful.
        seed: List[Pod] = []
        if enc is not None and enc.vocab_gen == snap.vocab_gen:
            seed = enc.reps
        aff_seq0 = self.cache.aff_seq
        chunk_aff = any(_has_affinity(p) for p in seed) \
            or any(_has_affinity(p) for p in pods)
        cluster_aff = any(bool(i.pods_with_affinity) for i in infos.values())
        # spread-only chunks build AffinityData too (ISSUE 18): the
        # workload-membership arrays drive the frozen SelectorSpread
        # score, so workload-bearing streams no longer flush the pipeline
        build_adata = chunk_aff or cluster_aff \
            or (bool(w_sp) and bool(workloads_now))
        all_pairs: list = []
        aff_pairs: list = []
        if build_adata or policy_active:
            all_pairs, aff_pairs = collect_pod_pairs(infos)
        if build_adata:
            # topology keys referenced by ANY affinity term must be interned
            # BEFORE the label matrix finalizes (the r2 symmetry bug), same
            # ordering contract as schedule()
            intern_topology_pairs(snap, seed + list(pods), aff_pairs)
        batch = ClassBatch(seed + list(pods), snap)
        n_cls = batch.num_classes
        rb = batch.reps_batch
        c_pad = bucket(n_cls + 1)
        # host-check absorption (ISSUE 18): label-pure host classes get an
        # exact precomputed fit column and ride the wave; the rest (live-
        # NodeInfo ports, score-affecting preference overflow, shapes the
        # column cannot derive, Policy order-dependence, affinity slot
        # overflow below) ride as inactive rows and place at the harvest's
        # exact oracle tail. No chunk SHAPE flushes the pipeline anymore.
        host_exact = np.zeros(c_pad, dtype=bool)
        host_static = np.zeros(c_pad, dtype=bool)
        nhc = rb.needs_host_check[:n_cls]
        host_exact[:n_cls] = nhc & rb.host_check_dynamic[:n_cls]
        host_fit_rows: Dict[int, np.ndarray] = {}
        for c in np.nonzero(nhc & ~rb.host_check_dynamic[:n_cls])[0]:
            row = rb.host_static_fit(int(c), snap)
            if row is None:
                host_exact[c] = True  # not derivable from labels alone
            else:
                host_static[c] = True
                host_fit_rows[int(c)] = row
        if policy_active:
            # service-coupled classes are order-dependent in-batch (the
            # reference's pod lister is the scheduler cache) -> exact tail
            host_exact[:n_cls] |= np.asarray(
                self.policy_algos.needs_host(batch.reps, workloads_now),
                dtype=bool)[:n_cls]
        adata = None
        fits_on = prio_on = spread_on = False
        has_aff_pod = None
        aff_wave_dev = aff_tail_dev = None
        key_node = static_forbid_hit = tail_cols = None
        if build_adata:
            COUNTERS.inc("engine.wave_aff_build")
            # the churn-robustness observable (ISSUE 8): every wholesale
            # AffinityData build the patch paths could NOT absorb. Under
            # the churn profile this must stay O(vocab growth + class-set
            # growth), not O(foreign binds) — the bench reports it.
            COUNTERS.inc("engine.aff_full_rebuilds")
            adata = AffinityData(batch.reps, snap, all_pairs, aff_pairs,
                                 workloads_now,
                                 self.hard_pod_affinity_weight,
                                 c_pad=c_pad)
            # slot overflow no longer flushes (ISSUE 18): overflow classes
            # join the exact oracle tail — the classic round marked them
            # host-check; same semantics, minus the pipeline drain
            host_exact[:n_cls] |= adata.overflow[:n_cls]
            fits_on = adata.fits_needed
            prio_on = bool(w_ip) and adata.prio_needed
            spread_on = bool(w_sp) and adata.spread_needed
            has_aff_pod = np.zeros(c_pad, dtype=bool)
            for c, rep in enumerate(batch.reps):
                has_aff_pod[c] = _has_affinity(rep)
            if fits_on:
                key_node, static_forbid_hit = _aff_node_views(adata, snap)

                def _sh(k):
                    return None if self._rmesh is None \
                        else self._rmesh.aff_sharding(k)
                # static per encoding — frozen-alias seam, like the tail;
                # node-axis members shard over the resident mesh
                aff_wave_dev = {
                    "m_anti": sanitize.upload_frozen(adata.m_anti,
                                                     sharding=_sh("m_anti")),
                    "key_node": sanitize.upload_frozen(
                        key_node, sharding=_sh("key_node")),
                    "static_forbid": sanitize.upload_frozen(
                        static_forbid_hit, sharding=_sh("static_forbid")),
                    "wave_gate": sanitize.upload_frozen(
                        adata.wave_gate, sharding=_sh("wave_gate")),
                }
            if fits_on or prio_on or spread_on:
                tail_cols = _aff_tail_cols(adata, prio_on)
                aff_tail_dev = _aff_tail_arrays(adata, snap, tail_cols,
                                                rmesh=self._rmesh)
        COUNTERS.inc("engine.wave_encode_build")
        cls_arr = pod_arrays_padded(rb, c_pad)
        if host_fit_rows:
            # the host-check static column: exact label-pure fit rows for
            # host_static classes, folded into the fused [C, N] eval via
            # predicates.static_fits (padding rows True — the validity
            # mask already excludes them)
            hf = np.ones((c_pad, snap.valid.shape[0]), dtype=bool)
            for c, row in host_fit_rows.items():
                hf[c] = row
            cls_arr["host_fit"] = sanitize.upload_frozen(hf)
        policy_cols = False
        if policy_active:
            pfit, pscore = self.policy_algos.static_class_arrays(
                batch.reps, snap, workloads_now, all_pairs, c_pad,
                skip=host_exact[:n_cls])
            if pfit is not None:
                cls_arr["policy_fit"] = jnp.asarray(pfit)
                policy_cols = True
            if pscore is not None:
                cls_arr["policy_score"] = jnp.asarray(pscore)
                policy_cols = True
        key_index = {pod_class_key(rep): c
                     for c, rep in enumerate(batch.reps)}
        special = ((rb.ports[:n_cls, 0] >= 0)
                   | (rb.vol_hard[:n_cls].sum(axis=1)
                      + rb.vol_ro[:n_cls].sum(axis=1)
                      + rb.pd_req[:n_cls].sum(axis=1) > 0))
        derived = [(rep.resource_request(), *rep.nonzero_request(),
                    rep.used_ports()) for rep in batch.reps]
        ports_max = int(rb.ports.max()) if np.any(rb.ports >= 0) else -1
        # clone the reps for reuse: the originals get node_name assigned at
        # assume time, which would corrupt their class key as seeds
        reps = [_dc.replace(p) for p in batch.reps]
        self._wave_enc = enc2 = _WaveEncoding(
            snap.vocab_gen, key_index, reps, cls_arr, n_cls, c_pad,
            rb.req[:n_cls].astype(np.int64), special, derived, ports_max,
            adata=adata, fits_on=fits_on, prio_on=prio_on,
            has_aff_pod=has_aff_pod, aff_seq=aff_seq0,
            aff_wave_dev=aff_wave_dev, aff_tail_dev=aff_tail_dev,
            key_node=key_node, static_forbid_hit=static_forbid_hit,
            tail_cols=tail_cols, n_pad=snap.valid.shape[0],
            labels_gen=snap.labels_gen,
            host_exact=host_exact, host_static=host_static,
            policy_on=policy_active, spread_on=spread_on,
            wkey=workloads_now,
            has_static_cols=bool(host_fit_rows) or policy_cols)
        if adata is not None:
            from kubernetes_tpu.ops.oracle_ext import _own_terms
            for c, rep in enumerate(reps):
                for a, term in enumerate(_own_terms(rep, anti=True)):
                    enc2.anti_terms.append((c, a, term, rep))
                for s, term in enumerate(_own_terms(rep, anti=False)):
                    enc2.aff_terms.append((c, s, term, rep))
        return enc2, batch.pod_class[len(seed):].copy()

    def dispatch_waves(self, pods: Sequence[Pod], pop_ts: float = 0.0,
                       gangs=None) -> Optional[WaveHandle]:
        """Encode a chunk and launch its wave placement WITHOUT blocking —
        the device computes while the caller does the previous wave's
        bookkeeping (JAX async dispatch). The chunk is evaluated against the
        snapshot as of NOW, which is blind to the still-unharvested wave's
        commits; harvest_waves' fence re-validates (capacity AND topology
        occupancy). Required (anti-)affinity chunks are wave-eligible
        (ISSUE 3): counter-expressible classes re-evaluate their masks per
        wave on device, inexpressible ones ride as inactive rows and the
        harvest finishes them via the seeded strict tail. Host-check and
        Policy chunks ride too (ISSUE 18): label-pure host classes via
        the precomputed host_fit column, the rest as inactive rows placed
        at the harvest's exact oracle tail. Returns None only for the one
        disclosed corner — a gang whose quorum is unreachable from its
        wave-eligible members (it would roll back forever); every other
        chunk shape dispatches, and the only remaining pipeline flush
        triggers are Node SPEC events (_node_event_needs_flush, r11).

        `gangs` = [(name, member indices into `pods`, quorum)]: quorum-
        ready gangs riding this wave as ordinary batch rows (ISSUE 5).
        Dispatch treats them like any other pod; atomicity lives entirely
        in harvest_waves' gang fence, so the pipeline never drains for a
        gang chunk."""
        import time as _time

        from kubernetes_tpu.utils.trace import COUNTERS, timed_span

        if not pods:
            return None
        with timed_span("pipeline.dispatch") as span:
            infos = self._refresh()
            with timed_span("pipeline.encode"):
                out = self._wave_encoding(pods, infos)
            if out is None:
                return None
            enc, pc = out
            hx = enc.host_exact[pc]
            host_idx = np.nonzero(hx)[0].astype(np.int64)
            if gangs and host_idx.size:
                # the one remaining chunk-shape flush corner (disclosed):
                # a gang whose quorum is unreachable from its wave-
                # eligible members would roll back on every re-dispatch —
                # only IT flushes to the classic round
                hset = set(host_idx.tolist())
                for _gname, idxs, quorum in gangs:
                    if sum(1 for i in idxs if i not in hset) < quorum:
                        COUNTERS.inc("engine.wave_flush_gang_host")
                        return None
            if enc.adata is not None:
                # patched topology views re-upload once per dispatch,
                # however many churn events were absorbed since the last
                self._flush_aff_patches(enc)
            n = len(pods)
            p_pad = bucket(max(n, self.wave_pad_floor or 1))
            pc_pad = np.full(p_pad, enc.num_classes, dtype=np.int32)
            pc_pad[:n] = pc
            if host_idx.size:
                # host_exact rows ride as the PADDING class: impossible on
                # device (fit nothing, no RR ticks, retire on the first
                # wave) — the harvest's exact oracle tail places them
                # against live NodeInfo truth after the fence
                pc_pad[host_idx] = enc.num_classes
                COUNTERS.inc("engine.wave_host_rows", int(host_idx.size))
            max_words = self.snapshot.port_words_used()
            if enc.ports_max >= 0:
                max_words = max(max_words, enc.ports_max // 32 + 1)
            port_words = bucket(max(max_words, 1), lo=1)
            nodes = dict(self._nodes_on_device(port_words=port_words))
            state = NodeState(nodes["requested"], nodes["nonzero"],
                              nodes["pod_count"], nodes["port_bitmap"],
                              nodes["vol_present"], nodes["vol_rw"],
                              nodes["pd_present"], nodes["pd_counts"])
            counter = self._rr_chain if self._rr_chain is not None \
                else jnp.uint32(self.rr.counter)
            extra = None
            if enc.prio_on or enc.spread_on:
                # preferred-affinity / SelectorSpread scores, frozen
                # against the encoding's static topology view (the
                # wave-mode approximation, same as the classic _run_wave's
                # batch-frozen extra_score) — over the tail's projected
                # domain axis, which covers every priority-side keymask
                # column by construction. Spread rides frozen too (ISSUE
                # 18): within-batch drift of workload counts is the same
                # documented score-only approximation.
                w_ip = sum(w for nm, w in self.priorities
                           if nm == "InterPodAffinityPriority")
                w_sp = sum(w for nm, w in self.priorities
                           if nm == "SelectorSpreadPriority")
                extra = waves.frozen_affinity_scores(
                    enc.cls_arr, nodes, state, enc.aff_tail_dev,
                    (w_ip if enc.prio_on else 0,
                     w_sp if enc.spread_on else 0))
            strict_idx = np.empty(0, dtype=np.int64)
            committed_out = None
            if enc.fits_on:
                ser = enc.wave_strict[pc] & ~hx
                strict_idx = np.nonzero(ser)[0]
                act = np.zeros(p_pad, dtype=bool)
                act[:n] = ~(ser | hx)
                # committed_nodes must upload as a COPY: the harvest FOLD
                # mutates it in place (np.add.at) while this wave may
                # still be executing against it asynchronously (the same
                # race class _nodes_on_device documents). GL001's
                # copy-required contract + the class-scoped alias check
                # both reject a jnp.asarray regression here.
                committed_dev = sanitize.upload_copied(  # graftlint: copy-required
                    enc.committed_nodes,
                    sharding=None if self._rmesh is None
                    else self._rmesh.committed_sharding())
                packed, state_out, committed_out = waves.waves_loop(
                    enc.cls_arr, nodes, state, jnp.asarray(pc_pad), counter,
                    self._kernel_priorities(), 64, extra_score=extra,
                    aff=enc.aff_wave_dev,
                    committed0=committed_dev,
                    active0=jnp.asarray(act),
                    pre=self._tail_wave_pre(enc, nodes),
                    spmd_mesh=self.mesh)
                if strict_idx.size:
                    COUNTERS.inc("engine.affinity_strict_tail",
                                 int(strict_idx.size))
            else:
                packed, state_out = waves.waves_loop(
                    enc.cls_arr, nodes, state, jnp.asarray(pc_pad), counter,
                    self._kernel_priorities(), 64, extra_score=extra,
                    pre=self._tail_wave_pre(enc, nodes),
                    spmd_mesh=self.mesh)
            counter_out = packed[3 * p_pad].astype(jnp.uint32)
            self._rr_chain = counter_out
            blind: set = set()
            self._blind_listeners.append(blind)
            COUNTERS.inc("engine.wave_dispatch")
            # admitted-pod count per dispatch: wave_dispatch_pods /
            # wave_dispatch is the realized micro-wave size, the stream
            # loop's admission observable (ISSUE 7)
            COUNTERS.inc("engine.wave_dispatch_pods", n)
            if gangs:
                COUNTERS.inc("engine.gang_wave_dispatch", len(gangs))
            wave_id = -1
            if RECORDER.enabled or TRACER.enabled or span.recording:
                # one wave-id sequence for every observer, so a pod's
                # WAVE_DISPATCHED joins the ring's dispatch/harvest
                # events and the profiler's spans of the same wave
                wave_id = RECORDER.next_wave()
                if span.recording:
                    span.annotate(wave=wave_id, pods=n)
            if TRACER.enabled:
                TRACER.batch_event(podtrace.WAVE_DISPATCHED,
                                   [p.key() for p in pods], a=wave_id)
            handle = WaveHandle(list(pods), pc, enc, packed, state_out,
                                counter_out, nodes, blind, pop_ts,
                                _time.monotonic(), self.wave_pad_floor,
                                committed_out=committed_out,
                                strict_idx=strict_idx, gangs=gangs,
                                wave_id=wave_id, host_idx=host_idx)
        if RECORDER.enabled:
            # the flight recorder takes the dispatch span's own stamps,
            # emitted after the async launch with host scalars in hand
            RECORDER.record(flightrec.DISPATCH, wave=wave_id, t0=span.t0,
                            dur=span.dur, a=n,
                            b=len(gangs) if gangs else 0)
        return handle

    def harvest_waves(self, handle: WaveHandle) -> WaveHarvest:
        """Block on one wave's device→host sync, fence its placements
        against post-blind-window occupancy, and assume the survivors
        (columnar). The fence is exact for resources and pod count (the
        snapshot is re-refreshed here, so it reflects every commit and
        watch event the device did not see); port/volume classes requeue
        conservatively when their node was touched in the blind window.
        Conflicting pods are returned for requeue WITHOUT backoff — they
        lost a capacity race, they are not unschedulable."""
        from kubernetes_tpu.utils.trace import timed_span

        with timed_span("pipeline.harvest") as span:
            if span.recording:
                span.annotate(wave=handle.wave_id, pods=len(handle.pods))
            res = self._harvest(handle)
        if RECORDER.enabled:
            # the flight recorder takes the harvest span's own stamps
            RECORDER.record(flightrec.HARVEST, wave=handle.wave_id,
                            t0=span.t0, dur=span.dur, a=len(res.bound),
                            b=len(res.conflicts)
                            + len(res.liveness_requeued))
            if res.conflicts or res.liveness_requeued:
                RECORDER.record(flightrec.FENCE_REQUEUE,
                                wave=handle.wave_id, a=len(res.conflicts),
                                b=len(res.liveness_requeued))
        return res

    def _harvest(self, handle: WaveHandle) -> WaveHarvest:
        """The body of harvest_waves, inside its span."""
        import time as _time

        from kubernetes_tpu.utils.trace import COUNTERS, timed_span

        # the fence below compares against snapshot arrays — fold in any
        # commits/events since the last dispatch (hinted: near-free when
        # nothing moved)
        self._refresh()
        enc = handle.enc
        snap = self.snapshot
        if enc is self._wave_enc and enc.adata is not None \
                and enc.aff_seq != self.cache.aff_seq:
            # foreign churn landed while this wave was in flight: patch
            # the overlays NOW so the topology fence below compares
            # against it exactly; a failed patch leaves the mismatch and
            # _fence_affinity requeues every relevant row conservatively
            self._try_patch_foreign(enc)
        n = len(handle.pods)
        p_pad = bucket(max(n, handle.pad_floor or 1))
        with timed_span("pipeline.device_block") as block:
            # THE pipeline's blessed block: harvest exists to absorb this
            # wave's device wait while the NEXT wave already runs
            packed_h = np.asarray(handle.packed)  # graftlint: sync-ok
        t_block = block.dur
        # the per-wave device->host payload: [3P+2] int32 regardless of N —
        # the scale_sweep's proof that harvesting never fetches node-axis
        # tensors (the winner reduce already collapsed them on device)
        COUNTERS.inc("engine.host_fetch_bytes", int(packed_h.nbytes))
        if self.mesh is not None:
            # structural traffic accounting for the two-stage winner
            # reduce (ISSUE 12): each INNER wave iteration's cross-shard
            # stage moves the [D, C] tie-count table + O(P) candidate
            # combines — scale by waves_used (packed[3P+1]), not per
            # dispatch, so the counter states actual cross-device traffic.
            # The bench reads this against the O(N) rows a single-device
            # gather would have moved.
            COUNTERS.inc("engine.reduce_candidate_rows",
                         int(self.mesh.devices.size) * handle.enc.c_pad
                         * int(packed_h[3 * p_pad + 1]))
        sel = packed_h[:n].copy()
        fc = packed_h[p_pad:p_pad + n].copy()
        act = packed_h[2 * p_pad:2 * p_pad + n].astype(bool)
        counter_h = int(np.uint32(packed_h[3 * p_pad]))
        tail_idx = np.nonzero(act)[0]
        if handle.host_idx.size:
            # host_exact rows retire inactive off the padding class on the
            # first wave; they never ride the device tail — the exact
            # oracle tail below places them after the fence
            tail_idx = np.setdiff1d(tail_idx, handle.host_idx)
        straggler_idx = np.empty(0, dtype=np.int64)
        if enc.adata is not None and tail_idx.size:
            # max-waves stragglers may NOT ride the seeded tail in an
            # affinity chunk: the tail's domain projection carries only
            # the wave_strict classes' columns (_aff_tail_cols), so a
            # straggler's own anti terms — and the symmetry sources
            # targeting its labels — would be invisible to the scan.
            # Requeue without backoff instead; the next dispatch re-waves
            # them against the updated occupancy (each re-dispatch of the
            # bottleneck commits at least one pod, so this terminates).
            straggler_idx = tail_idx
            tail_idx = np.empty(0, dtype=np.int64)
            COUNTERS.inc("engine.affinity_straggler_requeues",
                         int(straggler_idx.size))
        if handle.strict_idx.size:
            # wave_strict classes (own required affinity, multi-node-domain
            # anti shapes, fail_all) never entered the waves: finish them —
            # together with any max_waves stragglers (affinity-free
            # encodings only, see above) — via ONE seeded strict scan, in
            # FIFO order, against the wave's final device state AND its
            # final topology occupancy, exactly what the classic
            # _run_wave's strict branch would have seen.
            tail_idx = np.unique(np.concatenate([tail_idx,
                                                 handle.strict_idx]))
        if tail_idx.size:
            # the straggler/tail RR draws land after the next wave's
            # (already-chained) counter — deterministic in both pipelined
            # and sequential modes, since dispatch k+1 always precedes
            # harvest k in either.
            n_tail = len(tail_idx)
            pcs = np.full(bucket(n_tail), enc.num_classes, dtype=np.int32)
            pcs[:n_tail] = handle.pc[tail_idx]
            aff_arrays = None
            aff_init = None
            aff_mode = (False, False, False)
            tail_prios = self._kernel_priorities()
            if enc.adata is not None and (enc.fits_on or enc.prio_on):
                aff_arrays = enc.aff_tail_dev
                committed0 = handle.committed_out.astype(jnp.int32) \
                    if handle.committed_out is not None else jnp.zeros(
                        (enc.c_pad, int(handle.nodes["alloc"].shape[0])),
                        dtype=jnp.int32)
                # project the wave's per-node occupancy onto the tail's
                # domain columns: commdom[c, j] = committed @ labels[:, j]
                # (device GEMM over the SMALL projected axis)
                commdom0 = jnp.matmul(
                    committed0, aff_arrays["labels_aff"].astype(jnp.int32),
                    preferred_element_type=jnp.int32)
                aff_init = (commdom0, committed0, committed0.sum(axis=1))
                aff_mode = (enc.fits_on, enc.prio_on, False)
                if enc.prio_on:
                    tail_prios = tuple(
                        (nm, w) for nm, w in self.priorities
                        if nm != "SelectorSpreadPriority")
            COUNTERS.inc("engine.wave_tail_dispatch")
            if self.tail_rounds and n_tail >= self.tail_rounds_min:
                # conflict-round tail (ISSUE 5): the whole tail as ONE
                # while_loop dispatch whose sequential depth is the round
                # count — required semantics exact at every commit, tie-
                # breaks wave-style (waves.tail_rounds_loop docstring)
                COUNTERS.inc("engine.tail_round_dispatch")
                with timed_span("pipeline.tail"):
                    packed_t, _st = waves.tail_rounds_loop(
                        enc.cls_arr, handle.nodes, handle.state_out,
                        jnp.asarray(pcs), jnp.uint32(counter_h), tail_prios,
                        aff=aff_arrays, aff_mode=aff_mode, aff_init=aff_init,
                        pre=self._tail_wave_pre(enc, handle.nodes),
                        spmd_mesh=self.mesh)
                    # seeded tail fetch: the fence below needs these rows
                    # on host NOW — the tail is the last device work in
                    # this harvest
                    packed_th = np.asarray(packed_t)  # graftlint: sync-ok
                p_t = len(pcs)
                sel[tail_idx] = packed_th[:n_tail]
                fc[tail_idx] = packed_th[p_t:p_t + n_tail]
                counter_h = int(np.uint32(packed_th[2 * p_t]))
                COUNTERS.inc("engine.tail_rounds",
                             int(packed_th[2 * p_t + 1]))
            else:
                # per-pod scan (small tails, and the GRAFT_TAIL_ROUNDS=0
                # oracle mode): classic sequential semantics, the
                # constraint reference the round fuzz compares against
                with timed_span("pipeline.tail"):
                    sel_s, fc_s, _st, rr_d = gather_place_batch(
                        enc.cls_arr, jnp.asarray(pcs), handle.nodes,
                        handle.state_out, jnp.uint32(counter_h), tail_prios,
                        aff=aff_arrays, aff_mode=aff_mode, aff_init=aff_init)
                    # same fetch contract as the rounds branch above
                    sel[tail_idx] = np.asarray(sel_s)[:n_tail]  # graftlint: sync-ok
                    fc[tail_idx] = np.asarray(fc_s)[:n_tail]  # graftlint: sync-ok
                    counter_h = int(rr_d)  # graftlint: sync-ok (scalar)
        if self._rr_chain is handle.counter_out:
            self._rr_chain = None
        self.rr.counter = counter_h
        self._blind_listeners.remove(handle.blind)

        pods = handle.pods
        strag = set(straggler_idx.tolist())
        placed_idx = np.nonzero(sel >= 0)[0]
        acc_idx = np.empty(0, dtype=np.int64)
        acc_node = np.empty(0, dtype=np.int64)
        acc_cls = np.empty(0, dtype=np.int32)
        conflict_idx: List[int] = []
        conflict_codes: List[int] = []
        liveness_idx: List[int] = []
        if placed_idx.size:
            with timed_span("pipeline.fence"):
                (acc_idx, acc_node, acc_cls, conflict_idx, liveness_idx,
                 conflict_codes) = self._fence(handle, sel, placed_idx)
        # the GANG FENCE (ISSUE 5): all-or-nothing atomicity for gangs that
        # rode this wave as ordinary batches. A gang COMMITS when >= quorum
        # members survived placement AND the capacity/topology fence; below
        # quorum, every member — placed, fenced, or unschedulable — is
        # dropped from the accepted set BEFORE anything is assumed (atomic
        # rollback with zero partial residue, by construction: nothing of a
        # losing gang ever reaches the cache) and requeues WITH backoff,
        # exactly the classic round's below-quorum semantics.
        gang_committed: List[str] = []
        gang_requeued: List[Tuple[Pod, str]] = []
        drop = None
        if handle.gangs:
            acc_mask = np.zeros(n, dtype=bool)
            acc_mask[acc_idx] = True
            drop = np.zeros(n, dtype=bool)
            for gname, idxs, quorum in handle.gangs:
                ia = np.asarray(idxs, dtype=np.int64)
                ok_n = int(acc_mask[ia].sum())
                if ok_n >= quorum:
                    gang_committed.append(gname)
                    continue
                COUNTERS.inc("engine.gang_fence_rollbacks")
                COUNTERS.inc("engine.fence_reason_gang", len(ia))
                drop[ia] = True
                reason = (f"gang {gname}: only {ok_n}/{len(ia)} members "
                          f"placeable past the wave fence (quorum {quorum})")
                gang_requeued.extend((pods[int(i)], reason) for i in ia)
            if drop.any():
                keep = ~drop[acc_idx]
                acc_idx = acc_idx[keep]
                acc_node = acc_node[keep]
                acc_cls = acc_cls[keep]
            else:
                drop = None
        host_rows = set(handle.host_idx.tolist())
        unschedulable = [(pods[i], int(fc[i]))
                         for i in np.nonzero(sel < 0)[0].tolist()
                         if i not in strag and i not in host_rows
                         and (drop is None or not drop[i])]
        bound: List[Pod] = []
        # conflicts + their typed reason codes, parallel (ISSUE 15):
        # max-waves stragglers are an affinity-routing verdict
        conflicts: List[Pod] = []
        conflict_reasons: List[int] = []
        for i in straggler_idx.tolist():
            if drop is None or not drop[i]:
                conflicts.append(pods[i])
                conflict_reasons.append(podtrace.REASON_AFFINITY)
        for i, code in zip(conflict_idx, conflict_codes):
            if drop is None or not drop[i]:
                conflicts.append(pods[i])
                conflict_reasons.append(code)
        # liveness rejects (ISSUE 8): the target node died / was cordoned
        # mid-flight — requeue WITH backoff (the caller's contract): the
        # node is not coming back on a capacity-race timescale, and a
        # plain re-add would hot-loop the doomed rows against the same
        # dying topology until the event drains
        liveness = [pods[i] for i in liveness_idx
                    if drop is None or not drop[i]]
        if acc_idx.size:
            names = snap.node_names
            groups = []
            acc_l = acc_idx.tolist()
            node_l = acc_node.tolist()
            cls_l = acc_cls.tolist()
            change = np.nonzero((acc_node[1:] != acc_node[:-1])
                                | (acc_cls[1:] != acc_cls[:-1]))[0] + 1
            bounds = [0] + change.tolist() + [len(acc_l)]
            with timed_span("pipeline.assume"):
                for b0, b1 in zip(bounds[:-1], bounds[1:]):
                    name = names[node_l[b0]]
                    run = [pods[i] for i in acc_l[b0:b1]]
                    for p in run:
                        p.node_name = name
                    groups.append((name, run) + enc.derived[cls_l[b0]])
                infos_touched = self.cache.assume_pods_grouped(groups)
                # fold the assumes into the snapshot WITHOUT a node
                # walk: classes with pure base-resource footprints go
                # through the exact raw-delta path (generation synced
                # so the next refresh skips these nodes); the rest take
                # the normal dirty-note rewrite
                dok = enc.delta_ok[acc_cls]
                dirty_names = {names[i] for i in
                               set(acc_node[~dok].tolist())}
                if dok.any():
                    snap.apply_assume_delta(
                        acc_node[dok], enc.raw_rows[acc_cls[dok]],
                        [(nm, info) for nm, info in
                         infos_touched.items()
                         if nm not in dirty_names],
                        prio_rows=enc.cls_prio[acc_cls[dok]])
                if dirty_names:
                    self._touch(dirty_names)
                blind_names = [nm for nm in infos_touched
                               if nm not in dirty_names]
                for s in self._blind_listeners:
                    s.update(blind_names)
            if enc is self._wave_enc:
                # fold fence-accepted commits into the encoding's
                # cumulative per-node topology occupancy — the host
                # mirror the next dispatch seeds the device loop from —
                # and into its aff_seq expectation (assume_pods_grouped
                # just bumped cache.aff_seq once per assumed pod; the
                # churn sequence covers ALL pods since ISSUE 8). A stale
                # enc skips both: its aff_seq mismatch routes the next
                # dispatch through the patch/rebuild gate, which already
                # sees these assumes in the live NodeInfos.
                if enc.committed_nodes is not None:
                    np.add.at(enc.committed_nodes, (acc_cls, acc_node),
                              1)
                enc.aff_seq += len(acc_l)
            bound = [pods[i] for i in sorted(acc_l)]
        if host_rows:
            # the exact oracle tail (ISSUE 18): host_exact rows place
            # AFTER the wave rows' assume, against live NodeInfo truth —
            # exactly the classic round's slow_idx FIFO loop, so each
            # host pod sees every commit this harvest just made (and each
            # other's). Rolled-back gangs' members are excluded (their
            # gang fence already requeued them WITH backoff — zero
            # partial residue holds).
            h_rows = [i for i in sorted(host_rows)
                      if drop is None or not drop[i]]
            if h_rows:
                from kubernetes_tpu.ops.oracle_ext import SchedulingContext
                COUNTERS.inc("engine.wave_host_tail", len(h_rows))
                with timed_span("pipeline.host_tail"):
                    infos_t = self.cache.node_infos()
                    names_t = snap.node_names
                    ctx = SchedulingContext(
                        infos_t, self.workloads_provider(),
                        hard_pod_affinity_weight=(
                            self.hard_pod_affinity_weight),
                        volume_ctx=self.volume_ctx,
                        policy_algos=self.policy_algos)
                    for i in h_rows:
                        name = oracle.schedule_one(
                            pods[i], names_t, infos_t, self.rr,
                            self.priorities, ctx)
                        if name is not None:
                            self._assume(pods[i], name)
                            infos_t = self.cache.node_infos()
                            ctx.infos = infos_t
                            ctx.invalidate()
                            bound.append(pods[i])
                        else:
                            unschedulable.append((pods[i], 0))
        if TRACER.enabled:
            # per-pod harvest/fence stamps (ISSUE 15): survivors get
            # HARVESTED (the in_flight phase's right edge on their
            # timeline), losers a FENCE_REQUEUED carrying the typed
            # reason — host ints only, the sync above already happened
            t_h = _time.monotonic()
            if bound:
                TRACER.batch_event(podtrace.HARVESTED,
                                   [p.key() for p in bound],
                                   a=handle.wave_id, t0=t_h)
            for p, code in zip(conflicts, conflict_reasons):
                TRACER.event(p.key(), podtrace.FENCE_REQUEUED, a=code,
                             b=handle.wave_id, t0=t_h)
            for p in liveness:
                TRACER.event(p.key(), podtrace.FENCE_REQUEUED,
                             a=podtrace.REASON_LIVENESS,
                             b=handle.wave_id, t0=t_h)
            for p, _why in gang_requeued:
                TRACER.event(p.key(), podtrace.FENCE_REQUEUED,
                             a=podtrace.REASON_GANG,
                             b=handle.wave_id, t0=t_h)
        return WaveHarvest(bound, conflicts, unschedulable, t_block,
                           gang_committed=gang_committed,
                           gang_requeued=gang_requeued,
                           liveness_requeued=liveness,
                           conflict_reasons=conflict_reasons)

    def _fence(self, handle: WaveHandle, sel: np.ndarray,
               placed_idx: np.ndarray):
        """Vectorized re-validation of a blind wave's placements against
        current occupancy: exact prefix-capacity + pod-count math, plus the
        TOPOLOGY mirror (ISSUE 3) — required (anti-)affinity placements
        made against the pre-k occupancy re-check against the engine's
        post-k commdom and requeue conservatively instead of colliding.
        Returns (accepted original indices grouped by (node, class) with
        FIFO order inside each node, their node indices, their class
        indices, conflict original indices in FIFO order, liveness
        original indices, typed podtrace.REASON_* codes parallel to the
        conflict list)."""
        from kubernetes_tpu.utils.trace import COUNTERS

        snap = self.snapshot
        enc = handle.enc
        node_of = sel[placed_idx]
        order = np.argsort(node_of, kind="stable")
        gidx = placed_idx[order]
        gnode = node_of[order]
        m = len(gidx)
        seg_start = np.empty(m, dtype=bool)
        seg_start[0] = True
        seg_start[1:] = gnode[1:] != gnode[:-1]
        starts = np.nonzero(seg_start)[0]
        grp = np.cumsum(seg_start) - 1
        rank = np.arange(m) - starts[grp]
        cls_rows = handle.pc[gidx]
        req = enc.req_rows[cls_rows]                      # [m, R] int64
        csum = np.cumsum(req, axis=0)
        prefix = csum - (csum[starts] - req[starts])[grp]  # incl., per node
        # slice snapshot columns to the ENCODING's resource width: vocab
        # growth between dispatch and harvest appends columns these classes
        # cannot request (their rows predate the column), so ignoring the
        # suffix is exact — and indexing with the live width would tear
        ncols = enc.req_rows.shape[1]
        alloc = snap.alloc[gnode][:, :ncols].astype(np.int64)
        used = snap.requested[gnode][:, :ncols].astype(np.int64)
        avail = alloc - used
        plain = [c for c in range(ncols) if c not in (R_SCRATCH, R_OVERLAY)]
        ok = (prefix[:, plain] <= avail[:, plain]).all(axis=1)
        # storage fallback (predicates.go:590-604): overlay-less nodes charge
        # overlay requests against scratch
        no_ov = alloc[:, R_OVERLAY] == 0
        scr_pref = prefix[:, R_SCRATCH] + np.where(no_ov,
                                                   prefix[:, R_OVERLAY], 0)
        scr_avail = avail[:, R_SCRATCH] - np.where(no_ov,
                                                   used[:, R_OVERLAY], 0)
        ok &= scr_pref <= scr_avail
        ok &= no_ov | (prefix[:, R_OVERLAY] <= avail[:, R_OVERLAY])
        ok &= (snap.pod_count[gnode].astype(np.int64) + rank + 1
               <= snap.allowed_pods[gnode])
        spc = enc.special[cls_rows]
        if spc.any() and handle.blind:
            # ports/volume predicates are per-object host state — exact
            # vector re-check is not worth it for these rare classes; a
            # touched node in the blind window requeues them conservatively
            bl = np.zeros(snap.valid.shape[0], dtype=bool)
            idx_map = snap.node_index
            for nm in handle.blind:
                i = idx_map.get(nm, -1)
                if i >= 0:
                    bl[i] = True
            ok &= ~(spc & bl[gnode])
        # typed requeue attribution (ISSUE 15): one reason code per
        # rejected row, first-cause ordering (capacity checks ran first,
        # affinity only re-colors rows capacity passed). The ports/
        # volume conservative requeue above is a capacity-class verdict.
        reason = np.full(m, -1, dtype=np.int8)
        reason[~ok] = podtrace.REASON_CAPACITY
        if enc.fits_on and enc.adata is not None:
            aff_out = self._fence_affinity(enc, cls_rows, gnode)
            if aff_out is not None:
                aff_bad, aff_stale = aff_out
                n_rej = int((aff_bad & ok).sum())
                if n_rej:
                    COUNTERS.inc("engine.affinity_fence_requeues", n_rej)
                reason[aff_bad & (reason < 0)] = \
                    podtrace.REASON_STALE if aff_stale \
                    else podtrace.REASON_AFFINITY
                ok &= ~aff_bad
        # host-check re-validation (ISSUE 18): the host_fit column baked
        # label CONTENT at build; a relabel landing while this wave was
        # in flight makes the column stale — conservative requeue of
        # every host_static row (relabels are rare; the re-dispatch
        # rebuilds the encoding against fresh truth, the has_static_cols
        # invalidation above guarantees it)
        hs_bad = enc.host_static[cls_rows]
        if hs_bad.any() and snap.labels_gen != enc.labels_gen:
            n_h = int((hs_bad & ok).sum())
            if n_h:
                COUNTERS.inc("engine.hostcheck_fence_requeues", n_h)
            reason[hs_bad & (reason < 0)] = podtrace.REASON_HOSTCHECK
            ok &= ~hs_bad
        if enc.policy_on and self.policy_algos is not None \
                and self.policy_algos.active:
            # Policy re-validation (ISSUE 18): the frozen policy_fit
            # column was exact against the build-time workload set and
            # pod locations; re-check the EXACT oracle predicate against
            # live truth for every surviving row — ServiceAffinity moves
            # with every commit, and this fence is what lets Policy
            # chunks ride blind without ghost-binding on stale state
            cand = np.nonzero(ok)[0]
            if cand.size:
                from kubernetes_tpu.ops.oracle_ext import SchedulingContext
                infos_f = self.cache.node_infos()
                ctx = SchedulingContext(
                    infos_f, self.workloads_provider(),
                    hard_pod_affinity_weight=self.hard_pod_affinity_weight,
                    volume_ctx=self.volume_ctx,
                    policy_algos=self.policy_algos)
                names_f = snap.node_names
                p_bad = np.zeros(m, dtype=bool)
                for r in cand.tolist():
                    info = infos_f.get(names_f[int(gnode[r])])
                    node = info.node if info is not None else None
                    if node is None or not self.policy_algos.oracle_fit(
                            handle.pods[int(gidx[r])], node, ctx):
                        p_bad[r] = True
                if p_bad.any():
                    COUNTERS.inc("engine.policy_fence_requeues",
                                 int(p_bad.sum()))
                    reason[p_bad & (reason < 0)] = podtrace.REASON_POLICY
                    ok &= ~p_bad
        # liveness re-validation (ISSUE 8): a row targeting a node the
        # owner declared dying (watch event seen, not yet applied — the
        # doomed set) or one the refreshed snapshot already rules out
        # (deleted membership, cordon/NotReady since dispatch) must not
        # bind into a ghost. These rows requeue WITH backoff, separately
        # from capacity conflicts.
        live_bad = ~(snap.schedulable[gnode] & snap.valid[gnode])
        if self._doomed_nodes:
            idx_map = snap.node_index
            dm = [idx_map[nm] for nm in self._doomed_nodes if nm in idx_map]
            if dm:
                live_bad |= np.isin(gnode, np.asarray(dm))
        if live_bad.any():
            COUNTERS.inc("engine.liveness_fence_requeues",
                         int(live_bad.sum()))
            COUNTERS.inc("engine.fence_reason_liveness",
                         int(live_bad.sum()))
            ok &= ~live_bad
        conflict_mask = ~ok & ~live_bad
        for code in (podtrace.REASON_CAPACITY, podtrace.REASON_AFFINITY,
                     podtrace.REASON_STALE, podtrace.REASON_HOSTCHECK,
                     podtrace.REASON_POLICY):
            n_r = int(((reason == code) & conflict_mask).sum())
            if n_r:
                COUNTERS.inc("engine.fence_reason_"
                             + podtrace.REASON_NAMES[code], n_r)
        conf_pairs = sorted(zip(gidx[conflict_mask].tolist(),
                                reason[conflict_mask].tolist()))
        return (gidx[ok], gnode[ok], cls_rows[ok],
                [i for i, _r in conf_pairs],
                sorted(gidx[live_bad].tolist()),
                [int(r) for _i, r in conf_pairs])

    def _fence_affinity(self, enc: "_WaveEncoding", cls_rows: np.ndarray,
                        gnode: np.ndarray) -> Optional[np.ndarray]:
        """Topology half of the fence: re-evaluate required (anti-)affinity
        for the wave's placements against the engine's CURRENT cumulative
        occupancy (every prior harvest folded). Exactly mirrors the device
        mask (waves._wave_aff_mask) plus the allow side for strict-tail
        classes; in-harvest interactions need no re-check — they ran inside
        one device program against a shared carry. Returns a (bool [m]
        "must requeue" mask, stale flag) pair, or None when no placement
        is affinity-relevant. A STALE encoding (foreign affinity churn
        since dispatch, detected via cache.aff_seq) conservatively
        requeues every relevant placement — the retry re-dispatches
        against a rebuilt encoding; the stale flag types those requeues
        distinctly (ISSUE 15: stale-encoding is an operability story —
        churn outran the patch path — not a capacity race)."""
        ad = enc.adata
        rel = ad.wave_relevant[cls_rows]
        if not rel.any():
            return None
        if enc is not self._wave_enc or enc.aff_seq != self.cache.aff_seq \
                or enc.labels_gen != self.snapshot.labels_gen:
            return rel.copy(), True
        snap = self.snapshot
        cn = enc.committed_nodes.astype(np.float64)           # [C, N]
        C_, A_ = ad.m_anti.shape[:2]
        m2 = ad.m_anti.reshape(C_ * A_, C_).astype(np.float64)
        kn = enc.key_node.reshape(C_ * A_, -1)                # [C*A, N]
        # anti side, per-node form (float64 GEMMs — exact for these counts)
        occ = (m2 @ cn).reshape(C_, A_, -1)
        own_forb = (occ * enc.key_node).sum(axis=1)           # [C, N]
        sym = (m2.T @ (kn * np.repeat(cn, A_, axis=0)))       # [C, N]
        forb = own_forb + sym + enc.static_forbid_hit
        if enc.foreign_forbid is not None:
            # Protean overlay (ISSUE 8): foreign churn patched in since
            # the build — exactly the rows the wholesale rebuild would
            # have re-derived
            forb = forb + enc.foreign_forbid
        aff_bad = forb[cls_rows, gnode] > 0
        cols = enc.tail_cols
        lab_p = cd = None
        if cols is not None and cols.size:
            lab_p = snap.labels[:, cols].astype(np.float64)   # [N, Lp]
            cd = cn @ lab_p                                   # [C, Lp]
            # anti + symmetry over the PROJECTED DOMAIN columns: the
            # per-node form above is exact only for singleton domains
            # (the wave-eligibility invariant); a strict-tail class's
            # zone-scoped term forbids the whole DOMAIN, and a blind
            # placement can land on a DIFFERENT node of a domain another
            # chunk's harvest just occupied. Multi-domain terms — own and
            # symmetry sources — always project into tail_cols
            # (_aff_tail_cols includes wave_strict classes' anti rows and
            # every term targeting them), so this closes the window the
            # per-node mirror cannot see. Hostname columns double-count
            # with the per-node form; harmless in a bool requeue mask.
            m3 = ad.m_anti.astype(np.float64)
            kp = ad.anti_keymask[:, :, cols].astype(np.float64)
            occ_dom = np.einsum("cad,dl->cal", m3, cd)
            own_dom = (occ_dom * kp).sum(axis=1)              # [C, Lp]
            sym_dom = np.einsum("dac,dal->cl", m3,
                                kp * cd[:, None, :])          # [C, Lp]
            dom = own_dom + sym_dom
            if enc.foreign_forbid_dom is not None:
                dom = dom + enc.foreign_forbid_dom
            aff_bad |= np.einsum("ml,ml->m", dom[cls_rows],
                                 lab_p[gnode]) > 0
        own = ad.aff_active.any(axis=1)
        own_rows = np.nonzero(own[cls_rows])[0]
        if own_rows.size and lab_p is not None:
            # allow side (strict-tail classes only), over the tail's
            # projected domain columns: a blind-window bootstrap or
            # co-location choice re-validates against domains occupied NOW
            # — monotone growth can only widen the allow set, so the one
            # true hazard is two chunks bootstrapping the same group into
            # different domains
            c_r = cls_rows[own_rows]
            lab_r = lab_p[gnode[own_rows]]
            m_aff = ad.m_aff.astype(np.float64)
            occp = (np.einsum("csd,dl->csl", m_aff, cd)
                    * ad.aff_keymask[:, :, cols])
            dyn = np.einsum("msl,ml->ms", occp[c_r], lab_r) > 0
            stat = np.einsum(
                "msl,ml->ms",
                ad.aff_allow[c_r][:, :, cols].astype(np.float64), lab_r) > 0
            dyn_total = np.einsum("csd,d->cs", m_aff, cn.sum(axis=1))
            boot = ad.aff_self & ~ad.aff_has_static & (dyn_total == 0)
            ok_terms = (~ad.aff_active[c_r]) | stat | dyn | boot[c_r]
            aff_bad[own_rows] |= ~ok_terms.all(axis=1)
        return aff_bad & rel, False
