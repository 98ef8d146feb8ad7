"""The reference's v1.7 rules on small clusters, each case worked by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_reference.py -q

Every case also checks that `fits` (all nodes at once) and `fits_at`
(one node, the replay's per-bind check) agree, and that the 32-bit
control computes the same scores where no number is large.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import spec  # noqa: E402
from reference import ClusterState, place_background  # noqa: E402
from spec import (  # noqa: E402
    HOSTNAME_KEY,
    NodeSpec,
    PodSpec,
    Taint,
    Term,
    Toleration,
)

Gi = 1024 ** 3


def _nodes(n, taints=None, labels=None, extended=None):
    taints, labels, extended = taints or {}, labels or {}, extended or {}
    return [NodeSpec(f"n{i}", 4000, 32 * Gi, 110,
                     dict(labels.get(i, {}), **{HOSTNAME_KEY: f"n{i}"}),
                     tuple(taints.get(i, ())), tuple(extended.get(i, ())))
            for i in range(n)]


def _pod(name, ns="a", labels=(), **kw):
    return PodSpec(name, ns, 100, 500 * 1024 * 1024, tuple(labels), **kw)


def _fits(st, pod):
    got = st.fits(pod)
    assert [st.fits_at(pod, i) for i in range(len(st.names))] == got.tolist()
    return got.tolist()


def _scores(st, pod, prio, nodes=None):
    nodes = np.arange(len(st.names)) if nodes is None else np.array(nodes)
    s64 = st.scores(pod, nodes, [prio]).tolist()
    assert st.scores(pod, nodes, [prio], bits=32).tolist() == s64
    return s64


# ---------------------------------------------------------------- taints


@pytest.mark.parametrize("tols,want", [
    # no toleration: NoSchedule (n0) and NoExecute (n2) filter,
    # PreferNoSchedule (n1) only scores, n3 is untainted
    ((), [False, True, False, True]),
    # Equal k=v with no effect tolerates all three
    ((Toleration("k", "Equal", "v"),), [True, True, True, True]),
    # Exists k for NoSchedule only: NoExecute still filters
    ((Toleration("k", "Exists", "", "NoSchedule"),),
     [True, True, False, True]),
    # Equal k=w: the value differs, nothing is tolerated
    ((Toleration("k", "Equal", "w"),), [False, True, False, True]),
    # Exists with an empty key tolerates every taint
    ((Toleration("", "Exists"),), [True, True, True, True]),
])
def test_taint_effects_and_tolerations(tols, want):
    st = ClusterState(_nodes(4, taints={
        0: [Taint("k", "v", "NoSchedule")],
        1: [Taint("k", "v", "PreferNoSchedule")],
        2: [Taint("k", "v", "NoExecute")]}))
    assert _fits(st, _pod("p", tolerations=tols)) == want


def test_taint_toleration_priority_normalizes():
    """Intolerable PreferNoSchedule taints: n0 two, n1 one, n2 none.
    10 x (1 - count / max): max 2 gives 0, 5, 10; over the filtered
    nodes n1, n2 alone max is 1: 0, 10. Tolerating `a` leaves n0 one,
    so max 1: 0, 10, 10. Tolerating both: max 0, all 10."""
    st = ClusterState(_nodes(3, taints={
        0: [Taint("a", "x", "PreferNoSchedule"),
            Taint("b", "x", "PreferNoSchedule")],
        1: [Taint("a", "x", "PreferNoSchedule")]}))
    prio = "TaintTolerationPriority"
    assert _scores(st, _pod("p"), prio) == [0, 5, 10]
    assert _scores(st, _pod("p"), prio, nodes=[1, 2]) == [0, 10]
    tol_a = (Toleration("a", "Exists"),)
    assert _scores(st, _pod("p", tolerations=tol_a), prio) == [0, 10, 10]
    # a NoSchedule toleration does not cover a PreferNoSchedule taint
    tol_ns = (Toleration("a", "Exists", "", "NoSchedule"),)
    assert _scores(st, _pod("p", tolerations=tol_ns), prio) == [0, 5, 10]
    every = (Toleration("", "Exists"),)
    assert _scores(st, _pod("p", tolerations=every), prio) == [10, 10, 10]


# ------------------------------------------------- resources and selector


def test_extended_resource_to_capacity_and_past():
    """n0 has 2 GPUs, n1 none. One-GPU pods: the first two fit n0 (2 of
    2), the third does not (3 > 2); n1 never fits."""
    gpu = "alpha.kubernetes.io/nvidia-gpu"
    st = ClusterState(_nodes(2, extended={0: [(gpu, 2)]}))
    pods = [_pod(f"g{i}", extended=((gpu, 1),)) for i in range(3)]
    assert _fits(st, pods[0]) == [True, False]
    st.add(pods[0], 0)
    assert _fits(st, pods[1]) == [True, False]
    st.add(pods[1], 0)
    assert _fits(st, pods[2]) == [False, False]
    # removing one frees its GPU again
    st.remove(pods[0].key)
    assert _fits(st, pods[2]) == [True, False]
    # a resource no node has fits nowhere
    assert _fits(st, _pod("x", extended=(("example.com/fpga", 1),))) == \
        [False, False]


def test_node_selector():
    """nodeSelector accelerator=k80: only n1 carries it; n2 carries
    another value."""
    st = ClusterState(_nodes(3, labels={1: {"accelerator": "k80"},
                                        2: {"accelerator": "p100"}}))
    pod = _pod("p", node_selector=(("accelerator", "k80"),))
    assert _fits(st, pod) == [False, True, False]
    assert _fits(st, _pod("q")) == [True, True, True]


# --------------------------------------------------- inter-pod affinity


def test_anti_affinity_term_namespaces():
    """A color=green pod in namespace `a` on n0. An incoming pod in `b`
    whose anti term names `a` is kept off n0; the same term with no
    namespaces looks in `b` only and finds nothing. The symmetry holds
    across namespaces too: an existing pod in `a` whose anti term names
    `b` keeps a matching pod of `b` off its node."""
    green = (("color", "green"),)
    st = ClusterState(_nodes(2))
    st.add(_pod("e", "a", green), 0)
    other = Term(True, green, HOSTNAME_KEY, ("a",))
    own = Term(True, green, HOSTNAME_KEY)
    assert _fits(st, _pod("p", "b", terms=(other,))) == [False, True]
    assert _fits(st, _pod("q", "b", terms=(own,))) == [True, True]
    st.add(_pod("h", "a", (("role", "x"),),
                terms=(Term(True, green, HOSTNAME_KEY, ("b",)),)), 1)
    # a green pod of `b` with no terms: n1's holder selects it
    assert _fits(st, _pod("r", "b", green)) == [True, False]
    # a green pod of `a`: n1's holder looks only in `b`
    assert _fits(st, _pod("s", "a", green)) == [True, True]


def test_affinity_first_of_group_and_namespaces():
    """Required affinity to app=web in namespace `b`: with no such pod
    anywhere, a pod that matches its own term lands anywhere, one that
    does not lands nowhere; once a web pod of `b` sits on n1, only n1."""
    web = (("app", "web"),)
    term = Term(False, web, HOSTNAME_KEY, ("b",))
    st = ClusterState(_nodes(3))
    assert _fits(st, _pod("self", "b", web, terms=(term,))) == [True] * 3
    assert _fits(st, _pod("other", "a", web, terms=(term,))) == [False] * 3
    st.add(_pod("w", "b", web), 1)
    assert _fits(st, _pod("x", "a", terms=(term,))) == [False, True, False]


def test_preferred_terms_weighted_and_normalized():
    """The incoming pod prefers app=web at weight 5 and shuns app=db at
    weight 3, by hostname. n0 holds a web and a db pod (5 - 3 = 2), n1 a
    db pod (-3), n2 nothing (0). min -3, max 2: 10 x (c + 3) / 5 gives
    10, 0, 6."""
    st = ClusterState(_nodes(3))
    st.add(_pod("w", labels=(("app", "web"),)), 0)
    st.add(_pod("d0", labels=(("app", "db"),)), 0)
    st.add(_pod("d1", labels=(("app", "db"),)), 1)
    pod = _pod("p", preferred=(
        (5, Term(False, (("app", "web"),), HOSTNAME_KEY)),
        (3, Term(True, (("app", "db"),), HOSTNAME_KEY))))
    assert _scores(st, pod, "InterPodAffinityPriority") == [10, 0, 6]


def test_existing_pods_terms_score_the_incoming_pod():
    """Existing pods' terms that select the incoming app=web pod: n0's
    pod prefers app=web at weight 4 (+4), n1's shuns it at weight 2 (-2),
    n2's requires it (hardPodAffinityWeight, +1). min -2, max 4:
    10 x (c + 2) / 6 gives 10, 0, 5. A pod of another namespace is not
    selected by terms with no namespaces: all 0."""
    web = (("app", "web"),)
    st = ClusterState(_nodes(3))
    st.add(_pod("e0", preferred=((4, Term(False, web, HOSTNAME_KEY)),)), 0)
    st.add(_pod("e1", preferred=((2, Term(True, web, HOSTNAME_KEY)),)), 1)
    st.add(_pod("e2", labels=web, terms=(Term(False, web, HOSTNAME_KEY),)),
           2)
    prio = "InterPodAffinityPriority"
    assert _scores(st, _pod("p", labels=web), prio) == [10, 0, 5]
    assert _scores(st, _pod("q", "z", web), prio) == [0, 0, 0]


# ----------------------------------------------------------------- set-up


def _walk_config(count):
    green = {"color": "green"}
    return {
        "node_groups": [{"count": 6, "cpu_m": 4000, "memory_mi": 32768,
                         "pods": 110},
                        {"count": 2, "cpu_m": 4000, "memory_mi": 32768,
                         "pods": 110, "labels": {"pool": "gpu"},
                         "taints": [{"key": "dedicated", "value": "gpu",
                                     "effect": "NoSchedule"}]}],
        "pod_templates": {"green": {
            "namespace": "sched-0", "cpu_m": 100, "memory_mi": 500,
            "labels": green,
            "affinity": {"required": [{
                "anti": True, "match_labels": green,
                "topology_key": HOSTNAME_KEY,
                "namespaces": ["sched-0", "sched-1"]}]}}},
        "setup": {"count": count, "template": "green"},
        "mix": [{"share": 100, "template": "green"}],
    }


def test_setup_count_places_pods_that_all_fit():
    """Six one-per-host pods on six untainted nodes (two more nodes
    carry a NoSchedule taint they do not tolerate): each lands on its
    own untainted node, and the reference finds no rule broken. A
    seventh fits nowhere and is refused at set-up."""
    cfg = _walk_config(6)
    nodes = spec.make_nodes(cfg)
    placed = spec.background(cfg, nodes, 2 ** 40 + 3)
    assert sorted(i for _s, i in placed) == list(range(6))
    assert place_background(ClusterState(nodes), placed, check=True) == 0
    with pytest.raises(ValueError, match="fits no node"):
        spec.background(_walk_config(7), nodes, 1)


# ---------------------------------------------------------------- schema


def test_node_groups_number_and_label_nodes():
    cfg = _walk_config(0)
    cfg["node_groups"][0]["zones"] = ["z1", "z2"]
    nodes = spec.make_nodes(cfg)
    assert [n.name for n in nodes] == [f"hollow-node-{i}" for i in range(8)]
    assert len({n.labels[HOSTNAME_KEY] for n in nodes}) == 8
    assert [n.labels.get(spec.ZONE_KEY) for n in nodes] == \
        ["z1", "z2"] * 3 + [None, None]
    assert nodes[7].labels["pool"] == "gpu"
    assert nodes[7].taints == (Taint("dedicated", "gpu", "NoSchedule"),)


@pytest.mark.parametrize("edit,match", [
    # preferred node affinity would move NodeAffinityPriority
    (lambda c: c["pod_templates"]["green"].update(node_affinity={}),
     "unknown keys"),
    # a Service would move SelectorSpreadPriority
    (lambda c: c.update(services=[]), "unknown keys"),
    (lambda c: c.update(nodes={}), "unknown keys"),
    (lambda c: c["node_groups"][0].update(taints=[
        {"key": "k", "effect": "NoSchedule"}]) or c.update(
        setup={"per_node": 1, "mix": [{"share": 100,
                                        "template": "green"}]}),
     "taint"),
    (lambda c: c["pod_templates"]["green"]["affinity"]["required"][0]
     .update(match_labels={}), "match_labels"),
    (lambda c: c["pod_templates"]["green"].update(
        tolerations=[{"operator": "Equal", "value": "x"}]), "toleration"),
    (lambda c: c.update(mix=[{"share": 50, "template": "green"}]),
     "not 100"),
])
def test_loader_refuses_what_the_reference_cannot_judge(edit, match):
    cfg = _walk_config(1)
    edit(cfg)
    with pytest.raises(ValueError, match=match):
        spec.mix_pods(cfg, 1, "p-")


@pytest.mark.parametrize("module", ["reference.py", "spec.py"])
def test_reference_and_specs_import_nothing_of_the_program(module):
    import ast
    tree = ast.parse(open(os.path.join(BENCH, module)).read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names if m.split(".")[0] == "kubernetes_tpu"]
