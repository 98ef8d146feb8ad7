"""Extender warm fast lane: device-resident state between requests.

The r6 perf work (VERDICT r5 "Next round" #1): a warm /filter+/prioritize
round must be a single fused [1,N] kernel dispatch over device-resident
cluster state — not a per-request snapshot rebuild. These tests pin the
STRUCTURE of that fast lane via the utils.trace.COUNTERS spans the lane
emits and the EvalCache's own counters:

  - a second /filter for an equivalent pod serves from the result memo:
    no AffinityData rebuild, no precompute_static re-run (the fused kernel
    — counted as extender.fused_eval — is not dispatched at all);
  - /prioritize after /filter rides the same evaluation (fused verbs);
  - sync_nodes invalidates everything: full refresh, re-encode,
    device re-upload;
  - a bind invalidates RESULTS (capacity moved) but keeps the encoding
    (vocab_gen keying) and refreshes exactly one dynamic row
    (snapshot.refresh changed_hint);
  - the warm path agrees with the stateless args-mode evaluation.
"""

from __future__ import annotations

import pytest

from kubernetes_tpu.api.types import (
    Affinity,
    PodAffinity,
    PodAffinityTerm,
    LabelSelector,
    make_node,
    make_pod,
)
from kubernetes_tpu.models.hollow import hollow_nodes
from kubernetes_tpu.server.extender import TPUExtenderBackend
from kubernetes_tpu.utils.trace import COUNTERS

N_NODES = 200


@pytest.fixture()
def backend():
    b = TPUExtenderBackend()
    nodes = hollow_nodes(N_NODES)
    for i, n in enumerate(nodes):
        n.labels["zone"] = f"z{i % 4}"
    b.sync_nodes(nodes)
    b.filter(make_pod("warm", cpu=100), None, None)  # compile + first encode
    return b


def _pod(name: str, cpu: int = 100):
    return make_pod(name, cpu=cpu, memory=256 << 20)


def test_second_filter_serves_from_result_memo(backend):
    """Equivalent pod, unchanged cluster: the second /filter must not
    rebuild AffinityData, re-run the static precompute, or even dispatch
    the kernel — pure memo hit."""
    backend.filter(_pod("a"), None, None)
    before = COUNTERS.snapshot()
    builds0 = backend.eval_cache.builds
    passed, failed = backend.filter(_pod("b"), None, None)
    assert len(passed) == N_NODES and not failed
    assert backend.eval_cache.builds == builds0
    assert COUNTERS.count("extender.affinity_data_build") == \
        before.get("extender.affinity_data_build", (0, 0))[0]
    assert COUNTERS.count("extender.fused_eval") == \
        before.get("extender.fused_eval", (0, 0))[0]
    assert COUNTERS.count("extender.result_hit") == \
        before.get("extender.result_hit", (0, 0))[0] + 1


def test_prioritize_rides_the_filter_evaluation(backend):
    """The fused-verb contract: /prioritize for the pod /filter just
    evaluated reuses the (fits, scores) pair — zero device work."""
    pod = _pod("fused")
    backend.filter(pod, None, None)
    evals0 = COUNTERS.count("extender.fused_eval")
    hits0 = backend.eval_cache.result_hits
    scores = backend.prioritize(pod, None, None)
    assert len(scores) == N_NODES
    assert COUNTERS.count("extender.fused_eval") == evals0
    assert backend.eval_cache.result_hits == hits0 + 1


def test_sync_nodes_invalidates_device_resident_cache(backend):
    backend.filter(_pod("pre-sync"), None, None)
    refresh0 = COUNTERS.count("extender.refresh_full")
    uploads0 = COUNTERS.count("engine.device_upload_arrays")
    builds0 = backend.eval_cache.builds
    # re-sync with one node's allocatable changed: full refresh + fresh
    # evaluation (the memo and encodings keyed on the old version/sync gen
    # must not serve)
    nodes = [info.node for info in backend.cache.node_infos().values()]
    nodes[0] = make_node(nodes[0].name, cpu=8000, memory=64 << 30, pods=110,
                         labels=dict(nodes[0].labels))
    backend.sync_nodes(nodes)
    passed, _ = backend.filter(_pod("post-sync"), None, None)
    assert len(passed) == N_NODES
    assert COUNTERS.count("extender.refresh_full") == refresh0 + 1
    assert COUNTERS.count("engine.device_upload_arrays") > uploads0
    assert backend.eval_cache.builds == builds0 + 1


def test_bind_invalidates_results_but_keeps_encoding(backend):
    """A bind moves capacity: the (fits, scores) memo for the new snapshot
    version must MISS (one fused dispatch), but the pod-side encoding is
    capacity-independent and survives (vocab_gen keying) — and the refresh
    is the targeted one-row delta, not a full N-node generation walk."""
    backend.filter(_pod("pre-bind"), None, None)
    builds0 = backend.eval_cache.builds
    evals0 = COUNTERS.count("extender.fused_eval")
    full0 = COUNTERS.count("extender.refresh_full")
    hint0 = COUNTERS.count("extender.refresh_hint")
    version0 = backend.engine.snapshot.version
    assert backend.bind("bound-1", "default", "u1", "hollow-node-3") == ""
    scores = backend.prioritize(_pod("post-bind"), None, None)
    assert len(scores) == N_NODES
    assert backend.engine.snapshot.version == version0 + 1
    assert COUNTERS.count("extender.fused_eval") == evals0 + 1  # re-eval
    assert backend.eval_cache.builds == builds0                 # no re-encode
    assert COUNTERS.count("extender.refresh_full") == full0     # no full walk
    assert COUNTERS.count("extender.refresh_hint") == hint0 + 1
    # the committed pod really moved the node's row
    i = backend.engine.snapshot.node_index["hollow-node-3"]
    assert backend.engine.snapshot.pod_count[i] == 1


def test_warm_path_agrees_with_stateless_args_mode(backend):
    """Same pod, same cluster: the cached fast lane and the per-request
    args-mode evaluation (fresh snapshot per call) must agree on both the
    verdicts and the integer scores."""
    pod = _pod("parity")
    warm_passed, _ = backend.filter(pod, None, None)
    warm_scores = dict(backend.prioritize(pod, None, None))
    nodes = [info.node for info in backend.cache.node_infos().values()
             if info.node is not None]
    args_passed, _ = backend.filter(pod, nodes, None)
    args_scores = dict(backend.prioritize(pod, nodes, None))
    assert sorted(warm_passed) == sorted(args_passed)
    assert warm_scores == args_scores


def test_device_scores_equal_the_oracle_for_an_unselected_pod(backend):
    """No workload selects the pod: selector_spreading.go gives every node
    MaxPriority, so the device route's integer scores equal the exact
    oracle's (they once sat SelectorSpread's weight x 10 below it)."""
    from kubernetes_tpu.ops import oracle
    from kubernetes_tpu.ops.oracle_ext import SchedulingContext
    pod = _pod("spread-free")
    passed, _ = backend.filter(pod, None, None)
    got = dict(backend.prioritize(pod, None, passed))
    infos = backend.cache.node_infos()
    want = oracle.prioritize(pod, [infos[nm] for nm in passed],
                             backend.engine.priorities,
                             SchedulingContext(infos, []))
    assert got == dict(zip(passed, want))


def test_affinity_sync_demotes_the_aff_free_lane(backend):
    """The /bind wire carries identifiers only, so affinity knowledge
    arrives with the BULK SYNC: once a synced bound pod carries
    pod-affinity, cluster_aff_free flips and later evaluations rebuild
    AffinityData against the live pair set (the symmetry check now has
    something to check)."""
    assert backend.eval_cache.cluster_aff_free
    aff = Affinity(pod_affinity=PodAffinity(required_terms=[
        PodAffinityTerm(label_selector=LabelSelector(
            match_labels={"app": "guard"}), topology_key="zone")]))
    guard = make_pod("guard", cpu=100, labels={"app": "guard"}, affinity=aff)
    guard.node_name = "hollow-node-0"
    backend.sync_pods([guard])
    assert not backend.eval_cache.cluster_aff_free
    # plain pods now take the affinity-aware path (symmetry vs the guard)
    builds0 = backend.eval_cache.builds
    passed, _ = backend.filter(_pod("plain-after-aff"), None, None)
    assert len(passed) == N_NODES  # guard's affinity forbids nothing here
    assert backend.eval_cache.builds == builds0 + 1
    # and a later sync that removes the guard restores the fast lane
    backend.sync_pods([])
    assert backend.eval_cache.cluster_aff_free


def test_compat_scheduleone_loop_commits_capacity(backend):
    """A scheduleOne-shaped stream (filter -> prioritize -> bind) against
    the warm lane: every bind is visible to the next evaluation, and the
    full-refresh count stays flat (per-bind refreshes ride the hint)."""
    full0 = COUNTERS.count("extender.refresh_full")
    chosen = []
    for i in range(8):
        pod = _pod(f"so-{i}")
        passed, _ = backend.filter(pod, None, None)
        scores = backend.prioritize(pod, None, None)
        host = max(scores, key=lambda e: e[1])[0]
        assert backend.bind(pod.name, pod.namespace, pod.uid, host) == ""
        chosen.append(host)
    snap = backend.engine.snapshot
    for host in set(chosen):
        assert snap.pod_count[snap.node_index[host]] >= 1
    assert COUNTERS.count("extender.refresh_full") == full0
