"""The one place that turns the benchmark's plain specs into the
program's objects, and the program's store events back into plain
tuples for the reference."""

from __future__ import annotations

import copy
from typing import Dict, List, Tuple

from spec import NodeSpec, PodSpec, Term


def program_nodes(nodes: List[NodeSpec]):
    from kubernetes_tpu.api.types import Taint, TaintEffect, make_node
    return [make_node(n.name, cpu=n.cpu_m, memory=n.mem, pods=n.pods,
                      labels=dict(n.labels),
                      taints=[Taint(t.key, t.value, TaintEffect(t.effect))
                              for t in n.taints],
                      extended=dict(n.extended))
            for n in nodes]


def _term(t: Term):
    from kubernetes_tpu.api.types import LabelSelector, PodAffinityTerm
    return PodAffinityTerm(
        label_selector=LabelSelector(match_labels=dict(t.match)),
        namespaces=list(t.namespaces), topology_key=t.key)


def _affinity(s: PodSpec):
    from kubernetes_tpu.api.types import Affinity, PodAffinity
    if not s.terms and not s.preferred:
        return None
    sides = []
    for anti in (False, True):
        req = [_term(t) for t in s.terms if t.anti == anti]
        pref = [(w, _term(t)) for w, t in s.preferred if t.anti == anti]
        sides.append(PodAffinity(required_terms=req, preferred_terms=pref)
                     if req or pref else None)
    return Affinity(pod_affinity=sides[0], pod_anti_affinity=sides[1])


def _tolerations(s: PodSpec):
    from kubernetes_tpu.api.types import (
        TaintEffect,
        Toleration,
        TolerationOperator,
    )
    return [Toleration(t.key, TolerationOperator(t.operator), t.value,
                       TaintEffect(t.effect) if t.effect else None)
            for t in s.tolerations]


class PodFactory:
    """One template per distinct spec shape, shallow-copied per pod with
    fresh identity fields: the columnar build of models/hollow.py, so
    300k pods cost a second, not the ~20 us a make_pod call takes."""

    def __init__(self):
        self._templates: Dict[Tuple, object] = {}

    def pod(self, s: PodSpec, node_name: str = ""):
        from kubernetes_tpu.api.types import make_pod
        t = self._templates.get(s.shape)
        if t is None:
            t = make_pod("template", namespace=s.namespace, cpu=s.cpu_m,
                         memory=s.mem, extended=dict(s.extended),
                         node_selector=dict(s.node_selector),
                         tolerations=_tolerations(s), affinity=_affinity(s))
            self._templates[s.shape] = t
        p = copy.copy(t)
        p.name = s.name
        p.uid = s.key
        p.labels = dict(s.labels)
        p.annotations = {}
        p.node_name = node_name
        # a copied template must not carry the template's memoized class
        # key (models/hollow.py _stamp)
        p.__dict__.pop("_class_key", None)
        return p


def pod_events(api, from_rv: int) -> List[Tuple[str, str, str, int]]:
    """Every pod event in the store after `from_rv`, in commit order, as
    (type, key, node name, resource version)."""
    return [(e.type, f"{e.obj.namespace}/{e.obj.name}",
             e.obj.node_name or "", e.rv)
            for e in api.watch_since(("Pod",), from_rv)]
