"""The plain reference that decides `correct`.

A straightforward reading of the v1.7 scheduler's rules over the
benchmark's own plain specs (spec.py). It imports nothing of the program
and takes nothing the program made: it replays the store's event log
(pod created, bound, deleted) from the set-up state the benchmark itself
laid down, and at each bind asks what the rules say.

- Predicates (predicates.go): PodFitsResources (cpu, memory, pod count
  and every extended resource, `alpha.kubernetes.io/nvidia-gpu` among
  them), PodMatchNodeSelector (the pod's nodeSelector),
  PodToleratesNodeTaints (`NoSchedule` and `NoExecute` taints; `Equal`
  and `Exists` tolerations) and MatchInterPodAffinity (the pod's own
  required affinity and anti-affinity, and the symmetry of every
  existing pod's required anti-affinity, each term over its
  `namespaces`, empty meaning the owner's; the first pod of a
  self-matching affinity group may land anywhere,
  predicates.go:1210-1230).
- Priorities (algorithmprovider/defaults), weight 1 each:
  LeastRequested (int64 floor division), BalancedResourceAllocation
  (float64), TaintToleration (intolerable `PreferNoSchedule` taints,
  10 x (1 - count / max) over the filtered nodes, 10 where max is 0),
  InterPodAffinity (the pod's preferred affinity and anti-affinity
  weights, existing pods' preferred terms, and existing pods' required
  affinity at hardPodAffinityWeight 1, normalized over the filtered
  nodes, float64) and NodePreferAvoidPods 10 x 10000. Two are constants
  because the configuration schema (spec.py) cannot state what would
  move them, and its loader refuses any key outside it:
  NodeAffinityPriority scores 0 (no preferred node affinity) and
  SelectorSpreadPriority 10 (no Services, RCs or ReplicaSets select a
  pod).

`bits=32` computes the scores in 32-bit types (int32 with wrap-around,
float32): the control the limits are set against.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from spec import NodeSpec, PodSpec, Term, Toleration

MAX_PRIORITY = 10
HARD_POD_AFFINITY_WEIGHT = 1
KNOWN_PRIORITIES = ("SelectorSpreadPriority", "InterPodAffinityPriority",
                    "LeastRequestedPriority", "BalancedResourceAllocation",
                    "NodePreferAvoidPodsPriority", "NodeAffinityPriority",
                    "TaintTolerationPriority")
FILTER_EFFECTS = ("NoSchedule", "NoExecute")


def _matches(sel: Tuple[Tuple[str, str], ...],
             labels: Tuple[Tuple[str, str], ...]) -> bool:
    return set(sel) <= set(labels)


def _term_ns(t: Term, owner_ns: str) -> Tuple[str, ...]:
    """GetNamespacesFromPodAffinityTerm: the term's own, else the owner's."""
    return t.namespaces or (owner_ns,)


def _term_selects(t: Term, owner_ns: str, pod: PodSpec) -> bool:
    """PodMatchesTermsNamespaceAndSelector."""
    return pod.namespace in _term_ns(t, owner_ns) \
        and _matches(t.match, pod.labels)


def _tolerated(tols: Tuple[Toleration, ...], taint) -> bool:
    return any(t.tolerates(taint) for t in tols)


class ClusterState:
    """Nodes, the pods on them, and the counts the affinity rules need,
    kept per topology domain of every node label key."""

    def __init__(self, nodes: Sequence[NodeSpec]):
        self.names = [n.name for n in nodes]
        self.index = {n.name: i for i, n in enumerate(nodes)}
        n = len(nodes)
        self.alloc_cpu = np.array([x.cpu_m for x in nodes], np.int64)
        self.alloc_mem = np.array([x.mem for x in nodes], np.int64)
        self.max_pods = np.array([x.pods for x in nodes], np.int64)
        self.cpu = np.zeros(n, np.int64)
        self.mem = np.zeros(n, np.int64)
        self.count = np.zeros(n, np.int64)
        # extended resources: capacity and use per node, by name
        names = sorted({r for x in nodes for r, _q in x.extended})
        self.ext_cap = {r: np.array([dict(x.extended).get(r, 0)
                                     for x in nodes], np.int64)
                        for r in names}
        self.ext_used = {r: np.zeros(n, np.int64) for r in names}
        self.node_labels = [x.labels for x in nodes]
        self.taints = [x.taints for x in nodes]
        self.tainted = any(self.taints)
        self._masks: Dict[Tuple, np.ndarray] = {}
        # domain index of every node, per label key
        self.keys = sorted({k for x in nodes for k in x.labels})
        self.dom: Dict[str, np.ndarray] = {}
        self.n_dom: Dict[str, int] = {}
        for k in self.keys:
            vals = sorted({x.labels.get(k) for x in nodes if k in x.labels})
            at = {v: i for i, v in enumerate(vals)}
            # a node without the key is in no domain: index -1
            self.dom[k] = np.array([at.get(x.labels.get(k), -1)
                                    for x in nodes], np.int64)
            self.n_dom[k] = len(vals)
        # (key, namespace, labels) -> pods per domain
        self.label_counts: Dict[Tuple, np.ndarray] = {}
        # (namespace, labels) -> pods anywhere, nodes without a key too
        self.label_totals: Dict[Tuple, int] = {}
        # (owner namespace, required term) -> holders per domain of its key
        self.holders: Dict[Tuple, np.ndarray] = {}
        # (signed weight, owner namespace, preferred term) -> holders
        self.soft: Dict[Tuple, np.ndarray] = {}
        self.pods: Dict[str, Tuple[PodSpec, int]] = {}
        self._entry_cache: Dict[Tuple, List[np.ndarray]] = {}
        self._anti_cache: Dict[Tuple, List] = {}
        self._score_cache: Dict[Tuple, List] = {}

    # ---------------------------------------------------------- updates

    def _dom_of(self, key: str, node: int) -> int:
        d = self.dom.get(key)
        return -1 if d is None else int(d[node])

    def add(self, pod: PodSpec, node: int) -> None:
        self._move(pod, node, +1)
        self.pods[pod.key] = (pod, node)

    def remove(self, key: str) -> Optional[Tuple[PodSpec, int]]:
        got = self.pods.pop(key, None)
        if got is not None:
            self._move(got[0], got[1], -1)
        return got

    def _bump(self, table: Dict, hk: Tuple, key: str, d: int,
              sign: int) -> None:
        arr = table.get(hk)
        if arr is None:
            arr = table[hk] = np.zeros(self.n_dom[key], np.int64)
        arr[d] += sign

    def _move(self, pod: PodSpec, node: int, sign: int) -> None:
        self.cpu[node] += sign * pod.cpu_m
        self.mem[node] += sign * pod.mem
        self.count[node] += sign
        for r, q in pod.extended:
            if r not in self.ext_used:
                self.ext_used[r] = np.zeros(len(self.names), np.int64)
            self.ext_used[r][node] += sign * q
        if pod.labels:
            lk = (pod.namespace, pod.labels)
            self.label_totals[lk] = self.label_totals.get(lk, 0) + sign
            for k in self.keys:
                d = self._dom_of(k, node)
                if d >= 0:
                    self._bump(self.label_counts, (k,) + lk, k, d, sign)
        for t in pod.terms:
            d = self._dom_of(t.key, node)
            if d >= 0:
                self._bump(self.holders, (pod.namespace, t), t.key, d, sign)
        for w, t in pod.preferred:
            d = self._dom_of(t.key, node)
            if d >= 0:
                self._bump(self.soft, (-w if t.anti else w, pod.namespace,
                                       t), t.key, d, sign)

    # ------------------------------------------------------------ rules

    def _entries(self, key: str, namespaces, sel) -> List[np.ndarray]:
        """The count arrays, per domain of `key`, of every label set in
        `namespaces` that holds `sel` (cached until a new one shows)."""
        ck = (key, namespaces, sel, len(self.label_counts))
        got = self._entry_cache.get(ck)
        if got is None:
            got = [arr for (k, ns, labels), arr in self.label_counts.items()
                   if k == key and ns in namespaces
                   and _matches(sel, labels)]
            self._entry_cache[ck] = got
        return got

    def _matching(self, key: str, namespaces, sel) -> Optional[np.ndarray]:
        """Pods in `namespaces` whose labels hold `sel`, per domain of
        `key`; None where no node has the key."""
        if key not in self.dom:
            return None
        return sum(self._entries(key, namespaces, sel),
                   np.zeros(self.n_dom[key], np.int64))

    def _exists(self, namespaces, sel) -> bool:
        """Any such pod on any node, one without the key too: v1.7's
        `matchingPodExists`."""
        return any(n for (ns, labels), n in self.label_totals.items()
                   if ns in namespaces and _matches(sel, labels))

    def _anti_against(self, pod: PodSpec) -> List[Tuple[str, np.ndarray]]:
        """(key, holders per domain) of every existing required
        anti-affinity term that selects `pod` (the symmetry rule)."""
        ck = (pod.namespace, pod.labels, len(self.holders))
        got = self._anti_cache.get(ck)
        if got is None:
            got = [(t.key, arr) for (owner_ns, t), arr
                   in self.holders.items()
                   if t.anti and _term_selects(t, owner_ns, pod)]
            self._anti_cache[ck] = got
        return got

    def _per_node(self, key: str, per_dom: Optional[np.ndarray]
                  ) -> np.ndarray:
        d = self.dom.get(key)
        if d is None or per_dom is None:
            return np.zeros(len(self.names), np.int64)
        return np.where(d >= 0, per_dom[np.maximum(d, 0)], 0)

    def _mask(self, kind: str, arg) -> np.ndarray:
        """Per-node verdicts that depend on the node alone, cached: the
        nodeSelector, the filtering taints, or (`prefer`) the count of
        intolerable PreferNoSchedule taints."""
        got = self._masks.get((kind, arg))
        if got is None:
            got = np.array([self._node_rule(kind, arg, i)
                            for i in range(len(self.names))])
            self._masks[(kind, arg)] = got
        return got

    def _node_rule(self, kind: str, arg, node: int):
        if kind == "selector":
            labels = self.node_labels[node]
            return all(labels.get(k) == v for k, v in arg)
        if kind == "taints":
            return all(_tolerated(arg, t) for t in self.taints[node]
                       if t.effect in FILTER_EFFECTS)
        return sum(1 for t in self.taints[node]
                   if t.effect == "PreferNoSchedule"
                   and not _tolerated(arg, t))

    def fits(self, pod: PodSpec) -> np.ndarray:
        """Every node's verdict for `pod`: bool [N]."""
        ok = ((self.count + 1 <= self.max_pods)
              & (self.cpu + pod.cpu_m <= self.alloc_cpu)
              & (self.mem + pod.mem <= self.alloc_mem))
        for r, q in pod.extended:
            cap = self.ext_cap.get(r)
            if cap is None:
                ok[:] = False
            else:
                ok &= self.ext_used[r] + q <= cap
        if pod.node_selector:
            ok &= self._mask("selector", pod.node_selector)
        if self.tainted:
            ok &= self._mask("taints", pod.tolerations)
        for key, arr in self._anti_against(pod):
            ok &= self._per_node(key, arr) == 0
        for t in pod.terms:
            ns = _term_ns(t, pod.namespace)
            here = self._per_node(t.key, self._matching(t.key, ns, t.match))
            if t.anti:
                ok &= here == 0
            elif self._exists(ns, t.match):
                ok &= here > 0
            elif not _term_selects(t, pod.namespace, pod):
                ok[:] = False
        return ok

    def fits_at(self, pod: PodSpec, node: int) -> bool:
        """One node's verdict, in O(terms): the replay's per-bind check."""
        if (self.count[node] + 1 > self.max_pods[node]
                or self.cpu[node] + pod.cpu_m > self.alloc_cpu[node]
                or self.mem[node] + pod.mem > self.alloc_mem[node]):
            return False
        for r, q in pod.extended:
            cap = self.ext_cap.get(r)
            if cap is None or self.ext_used[r][node] + q > cap[node]:
                return False
        if pod.node_selector and \
                not self._node_rule("selector", pod.node_selector, node):
            return False
        if self.tainted and \
                not self._node_rule("taints", pod.tolerations, node):
            return False
        for key, arr in self._anti_against(pod):
            d = self._dom_of(key, node)
            if d >= 0 and arr[d] > 0:
                return False
        for t in pod.terms:
            ns = _term_ns(t, pod.namespace)
            d = self._dom_of(t.key, node)
            here = 0 if d < 0 else sum(
                int(a[d]) for a in self._entries(t.key, ns, t.match))
            if t.anti:
                if here:
                    return False
            elif not here:
                if self._exists(ns, t.match):
                    return False
                if not _term_selects(t, pod.namespace, pod):
                    return False
        return True

    def scores(self, pod: PodSpec, nodes: np.ndarray,
               priorities: Sequence[str], bits: int = 64) -> np.ndarray:
        """Weighted sum of the priorities over `nodes` (the filtered set,
        node indices), as ints."""
        it = np.int64 if bits == 64 else np.int32
        ft = np.float64 if bits == 64 else np.float32
        n = len(nodes)
        total = np.zeros(n, np.int64)
        with np.errstate(over="ignore"):
            for name in priorities:
                if name not in KNOWN_PRIORITIES:
                    raise KeyError(f"reference has no priority {name!r}")
                if name == "SelectorSpreadPriority":
                    total += MAX_PRIORITY
                elif name == "NodePreferAvoidPodsPriority":
                    total += MAX_PRIORITY * 10000
                elif name == "NodeAffinityPriority":
                    pass
                elif name == "TaintTolerationPriority":
                    total += self._taint_toleration(pod, nodes, ft)
                elif name == "LeastRequestedPriority":
                    total += self._least_requested(pod, nodes, it)
                elif name == "BalancedResourceAllocation":
                    total += self._balanced(pod, nodes, ft)
                elif name == "InterPodAffinityPriority":
                    total += self._interpod(pod, nodes, ft)
        return total

    def _taint_toleration(self, pod, nodes, ft) -> np.ndarray:
        """taint_toleration.go: map, then the normalizing reduce."""
        if not self.tainted:
            return np.full(len(nodes), MAX_PRIORITY, np.int64)
        c = self._mask("prefer", pod.tolerations)[nodes]
        hi = int(c.max()) if len(c) else 0
        if hi == 0:
            return np.full(len(nodes), MAX_PRIORITY, np.int64)
        return ((ft(1) - c.astype(ft) / ft(hi))
                * ft(MAX_PRIORITY)).astype(np.int64)

    def _least_requested(self, pod, nodes, it) -> np.ndarray:
        out = np.zeros(len(nodes), np.int64)
        for used, cap, req in ((self.cpu, self.alloc_cpu, pod.cpu_m),
                               (self.mem, self.alloc_mem, pod.mem)):
            tot = (used[nodes] + req).astype(it)
            c = cap[nodes].astype(it)
            s = ((c - tot) * it(MAX_PRIORITY)) // np.maximum(c, it(1))
            out += np.where((c == 0) | (tot > c), 0, s).astype(np.int64)
        return out // 2

    def _balanced(self, pod, nodes, ft) -> np.ndarray:
        fc = (self.cpu[nodes] + pod.cpu_m).astype(ft) \
            / self.alloc_cpu[nodes].astype(ft)
        fm = (self.mem[nodes] + pod.mem).astype(ft) \
            / self.alloc_mem[nodes].astype(ft)
        s = ((ft(1) - np.abs(fc - fm)) * ft(MAX_PRIORITY)).astype(np.int64)
        return np.where((fc >= 1) | (fm >= 1), 0, s)

    def _existing_terms(self, pod: PodSpec
                        ) -> List[Tuple[int, str, np.ndarray]]:
        """(weight, key, holders per domain) of every existing pod's term
        that selects `pod` and scores: required affinity at
        hardPodAffinityWeight, preferred terms at their signed weight."""
        ck = (pod.namespace, pod.labels, len(self.holders), len(self.soft))
        got = self._score_cache.get(ck)
        if got is None:
            got = [(HARD_POD_AFFINITY_WEIGHT, t.key, arr)
                   for (owner_ns, t), arr in self.holders.items()
                   if not t.anti and _term_selects(t, owner_ns, pod)]
            got += [(w, t.key, arr)
                    for (w, owner_ns, t), arr in self.soft.items()
                    if _term_selects(t, owner_ns, pod)]
            self._score_cache[ck] = got
        return got

    def _interpod(self, pod, nodes, ft) -> np.ndarray:
        """interpod_affinity.go CalculateInterPodAffinityPriority: integer
        weights, so the sum is exact before the float normalization."""
        counts = np.zeros(len(self.names), np.int64)
        for w, t in pod.preferred:
            per_dom = self._matching(t.key, _term_ns(t, pod.namespace),
                                     t.match)
            counts += (-w if t.anti else w) * self._per_node(t.key, per_dom)
        for w, key, arr in self._existing_terms(pod):
            counts += w * self._per_node(key, arr)
        c = counts[nodes].astype(ft)
        if not len(c):
            return np.zeros(0, np.int64)
        hi = max(c.max(), ft(0))
        lo = min(c.min(), ft(0))
        if hi - lo <= 0:
            return np.zeros(len(nodes), np.int64)
        return (ft(MAX_PRIORITY) * ((c - lo) / (hi - lo))).astype(np.int64)

    def best_gap(self, pod: PodSpec, node: int, priorities,
                 bits: int = 64) -> int:
        """How far the chosen node's score lies below the best feasible
        node's; the node must be feasible (checked apart)."""
        feas = np.nonzero(self.fits(pod))[0]
        if not len(feas):
            return 0
        s = self.scores(pod, feas, priorities, bits)
        at = np.nonzero(feas == node)[0]
        if not len(at):
            return int(s.max() - s.min()) + 1
        return int(s.max() - s[at[0]])


# ------------------------------------------------------------- replay


class Replay:
    """Walks the store's pod events in order over a ClusterState and
    judges every bind at its commit.

    `specs` maps pod key to its plain spec for every pod that may appear;
    `gap_at` is an optional set of bind ordinals (0-based, in commit
    order) at which to take the score gap."""

    def __init__(self, state: ClusterState, specs: Dict[str, PodSpec],
                 priorities: Sequence[str]):
        self.state = state
        self.specs = specs
        self.priorities = tuple(priorities)
        self.binds = 0
        self.duplicate = 0
        self.infeasible = 0
        self.unknown = 0
        self.gaps: List[int] = []
        self.bound_keys: set = set()
        self.first_breach: Optional[str] = None

    def run(self, events: Iterable[Tuple[str, str, str, int]],
            gap_at: Optional[set] = None, before=None) -> None:
        """events: (type, pod key, node name, resource version) with type
        ADDED, MODIFIED or DELETED, in the store's commit order.
        `before(rv)` is called ahead of each event."""
        st = self.state
        for etype, key, node_name, rv in events:
            if before is not None:
                before(rv)
            if etype == "DELETED":
                st.remove(key)
                continue
            if not node_name:
                continue          # created unbound, or a status change
            if key in st.pods:
                if st.pods[key][1] != st.index.get(node_name):
                    self.duplicate += 1
                continue
            spec = self.specs.get(key)
            node = st.index.get(node_name)
            if spec is None or node is None:
                self.unknown += 1
                continue
            ordinal = self.binds
            self.binds += 1
            if key in self.bound_keys:
                # bound, deleted and bound again under the same key
                self.duplicate += 1
            if not st.fits_at(spec, node):
                self.infeasible += 1
                if self.first_breach is None:
                    self.first_breach = f"{key} on {node_name}"
            elif gap_at is not None and ordinal in gap_at:
                self.gaps.append(st.best_gap(spec, node, self.priorities))
            st.add(spec, node)
            self.bound_keys.add(key)


def place_background(state: ClusterState,
                     placed: Iterable[Tuple[PodSpec, int]],
                     check: bool = False) -> int:
    """Lay down the set-up pods; with `check`, judge each against the
    rules first and return how many broke one (the benchmark's own
    generator must give 0; tests/test_harness.py holds it to that)."""
    bad = 0
    for spec, node in placed:
        if check and not state.fits_at(spec, node):
            bad += 1
        state.add(spec, node)
    return bad
