"""Plain specs of the cluster and the traffic, made from a seed.

Nothing here imports the program: the nodes, the set-up pods and the
arrival schedule are plain tuples, so the reference (reference.py) reads
the same inputs the program is given without touching program objects.
`adapter.py` turns them into the program's types.

A configuration file states its deployment as data (PERF.md section 4):

- `node_groups`: `[{count, cpu_m, memory_mi, pods, labels, zones,
  taints: [{key, value, effect}], extended: {name: count}}]`. Nodes are
  numbered across the groups in order; each has a unique
  `kubernetes.io/hostname`, and a group's `zones` go round-robin over
  its nodes as the zone label.
- `pod_templates`: `{name: {namespace, cpu_m, memory_mi, extended,
  labels, node_selector, tolerations: [{key, operator, value, effect}],
  affinity: {required: [term], preferred: [{weight, term}]}, apps}}`,
  a term being `{anti, match_labels, topology_key, namespaces}` (empty
  `namespaces`: the owner's). `{k}` in a label or selector value is the
  pod's app within its template, `0 .. apps - 1`, in turn.
- `setup`: `{per_node, mix}`, that many pods on every node by a stride
  over the mix's 100 slots, or `{count, template}`, pods placed one by
  one on the first node of a seeded walk where the reference's
  `fits_at` holds. Either way the set-up state keeps the rules by
  construction; the program never chooses it.
- `mix`: `[{share, template}]`, the traffic's pods in the 100-slot
  interleave.

The older form (`nodes`, `background_per_node` and a `mix` of the kinds
`plain`, `anti_host`, `aff_zone`) is translated by `_from_legacy`.

The stride, the app cycling and the node shape are the benchmark's own
copies of the generators in `kubernetes_tpu/models/hollow.py`
(density_pods, mixed_affinity_pods, hollow_nodes) and the open-loop
schedule of `bench.py run_arrival`, so a change to the program cannot
move them.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
Mi = 1024 * 1024
HOSTNAME_KEY = "kubernetes.io/hostname"
ZONE_KEY = "failure-domain.beta.kubernetes.io/zone"
EFFECTS = ("NoSchedule", "PreferNoSchedule", "NoExecute")

Pairs = Tuple[Tuple[str, str], ...]


@dataclass(frozen=True)
class Term:
    """One pod (anti-)affinity term: a match-labels selector, a topology
    key, and the namespaces it looks in (empty: the owner's)."""
    anti: bool
    match: Pairs
    key: str
    namespaces: Tuple[str, ...] = ()


@dataclass(frozen=True)
class Taint:
    key: str
    value: str
    effect: str


@dataclass(frozen=True)
class Toleration:
    key: str = ""
    operator: str = "Equal"
    value: str = ""
    effect: str = ""              # "": every effect

    def tolerates(self, t: Taint) -> bool:
        """v1.7 core/v1 `Toleration.ToleratesTaint`."""
        if self.effect and self.effect != t.effect:
            return False
        if self.key and self.key != t.key:
            return False
        return self.operator == "Exists" or self.value == t.value


@dataclass
class PodSpec:
    name: str
    namespace: str
    cpu_m: int
    mem: int                      # bytes
    labels: Pairs
    terms: Tuple[Term, ...] = ()  # required
    preferred: Tuple[Tuple[int, Term], ...] = ()
    extended: Tuple[Tuple[str, int], ...] = ()
    node_selector: Pairs = ()
    tolerations: Tuple[Toleration, ...] = ()

    @property
    def key(self) -> str:
        return f"{self.namespace}/{self.name}"

    @property
    def shape(self) -> tuple:
        """Everything but the pod's identity and labels."""
        return (self.namespace, self.cpu_m, self.mem, self.terms,
                self.preferred, self.extended, self.node_selector,
                self.tolerations)


@dataclass
class NodeSpec:
    name: str
    cpu_m: int
    mem: int
    pods: int
    labels: Dict[str, str]
    taints: Tuple[Taint, ...] = ()
    extended: Tuple[Tuple[str, int], ...] = ()


def load_json(kind: str, name: str) -> dict:
    """`configs/<name>.json` or `traffic/<name>.json`, found by name."""
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator per purpose, so one draw never shifts another. Seeds
    may exceed 32 bits; SeedSequence takes any non-negative int."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 63),
                                sum(map(ord, stream))]))


# ------------------------------------------------------------ the schema

_GROUP_KEYS = {"count", "cpu_m", "memory_mi", "pods", "labels", "zones",
               "taints", "extended"}
_TEMPLATE_KEYS = {"namespace", "cpu_m", "memory_mi", "extended", "labels",
                  "node_selector", "tolerations", "affinity", "apps"}
_TERM_KEYS = {"anti", "match_labels", "topology_key", "namespaces"}
# what a configuration states besides the deployment: its name, source
# and the notes on how it was cut
_CONFIG_KEYS = {"name", "source", "deployment", "assumed", "reduced",
                "guarantees", "priorities", "node_groups", "pod_templates",
                "setup", "mix"}


def _keys(what: str, d: dict, allowed: set, required=()) -> None:
    if not isinstance(d, dict):
        raise ValueError(f"{what}: not an object")
    extra = set(d) - allowed
    if extra:
        raise ValueError(f"{what}: unknown keys {sorted(extra)} (the "
                         "reference judges only what the schema states)")
    missing = [k for k in required if k not in d]
    if missing:
        raise ValueError(f"{what}: missing {missing}")


def _check_term(what: str, t: dict) -> None:
    _keys(what, t, _TERM_KEYS, ("match_labels", "topology_key"))
    if not t["match_labels"] or not t["topology_key"]:
        raise ValueError(f"{what}: needs match_labels and a topology_key")


def _check_template(name: str, t: dict) -> None:
    what = f"pod_templates.{name}"
    _keys(what, t, _TEMPLATE_KEYS, ("namespace", "cpu_m", "memory_mi"))
    for i, tol in enumerate(t.get("tolerations", ())):
        _keys(f"{what}.tolerations[{i}]", tol,
              {"key", "operator", "value", "effect"})
        op = tol.get("operator", "Equal")
        if op not in ("Equal", "Exists") or tol.get("effect", "") not in \
                ("",) + EFFECTS or (op == "Exists" and tol.get("value")) \
                or (not tol.get("key") and op != "Exists"):
            raise ValueError(f"{what}.tolerations[{i}]: not a valid "
                             "v1.7 toleration")
    aff = t.get("affinity", {})
    _keys(f"{what}.affinity", aff, {"required", "preferred"})
    for i, term in enumerate(aff.get("required", ())):
        _check_term(f"{what}.affinity.required[{i}]", term)
    for i, w in enumerate(aff.get("preferred", ())):
        _keys(f"{what}.affinity.preferred[{i}]", w, {"weight", "term"},
              ("weight", "term"))
        if not 1 <= int(w["weight"]) <= 100:
            raise ValueError(f"{what}.affinity.preferred[{i}]: weight "
                             "outside 1..100")
        _check_term(f"{what}.affinity.preferred[{i}].term", w["term"])
    if int(t.get("apps", 1)) < 1:
        raise ValueError(f"{what}: apps must be at least 1")


def _check(d: dict) -> dict:
    _keys("configuration", d, _CONFIG_KEYS,
          ("node_groups", "pod_templates", "setup", "mix"))
    for i, g in enumerate(d["node_groups"]):
        what = f"node_groups[{i}]"
        _keys(what, g, _GROUP_KEYS, ("count", "cpu_m", "memory_mi", "pods"))
        labels = g.get("labels", {})
        if HOSTNAME_KEY in labels or (g.get("zones") and ZONE_KEY in labels):
            raise ValueError(f"{what}: hostname and zone labels are made "
                             "per node")
        for x in g.get("taints", ()):
            _keys(f"{what}.taints", x, {"key", "value", "effect"},
                  ("key", "effect"))
            if x["effect"] not in EFFECTS:
                raise ValueError(f"{what}: taint effect {x['effect']!r}")
    for name, t in d["pod_templates"].items():
        _check_template(name, t)
    names = set(d["pod_templates"])
    s = d["setup"]
    for e in d["mix"] + s.get("mix", []):
        _keys("mix", e, {"share", "template"}, ("share", "template"))
        if e["template"] not in names:
            raise ValueError(f"mix: no template {e['template']!r}")
    if "per_node" in s:
        _keys("setup", s, {"per_node", "mix"}, ("per_node", "mix"))
        if any(x["effect"] != "PreferNoSchedule"
               for g in d["node_groups"] for x in g.get("taints", ())):
            raise ValueError("setup.per_node lays pods on every node, so "
                             "no node may carry a NoSchedule or NoExecute "
                             "taint: use {count, template}")
        for e in s["mix"]:
            _stride_rule(d["pod_templates"][e["template"]])
    else:
        _keys("setup", s, {"count", "template"}, ("count", "template"))
        if s["template"] not in names:
            raise ValueError(f"setup: no template {s['template']!r}")
    return d


def deployment(cfg: dict) -> dict:
    """The configuration in the deployment form, checked: a file in the
    older form is translated first. Keys outside the schema are refused,
    so nothing can make a priority the reference holds constant vary
    (no preferred node affinity, no Services or controllers)."""
    if "node_groups" in cfg:
        return _check(cfg)
    return _check(_from_legacy(cfg))


def _from_legacy(cfg: dict) -> dict:
    """`nodes` + `background_per_node` + a `mix` of kinds, as templates:
    each mix class becomes one template for the traffic and one for the
    set-up, whose labels carry `bg-` so no traffic pod's term selects a
    set-up pod."""
    n = cfg["nodes"]
    group = {k: n[k] for k in ("count", "cpu_m", "memory_mi", "pods",
                               "zones")}
    templates: Dict[str, dict] = {}
    mix, setup_mix = [], []
    for i, c in enumerate(cfg["mix"]):
        for prefix, out in (("", mix), ("bg-", setup_mix)):
            name = f"{prefix}{i}-{c['kind']}"
            templates[name] = _legacy_template(c, prefix)
            out.append({"share": c["share"], "template": name})
    rest = {k: v for k, v in cfg.items()
            if k in _CONFIG_KEYS and k != "mix"}
    return dict(rest, node_groups=[group], pod_templates=templates,
                setup={"per_node": cfg["background_per_node"],
                       "mix": setup_mix},
                mix=mix)


def _legacy_template(c: dict, prefix: str) -> dict:
    t = {"namespace": "bench", "cpu_m": c["cpu_m"],
         "memory_mi": c["memory_mi"]}
    keys = {"anti_host": (True, HOSTNAME_KEY), "aff_zone": (False, ZONE_KEY),
            "plain": None}
    if c["kind"] not in keys:
        raise ValueError(f"unknown pod kind {c['kind']!r}")
    app = c.get("app")
    if app:
        sel = {"app": f"{prefix}{app}-{{k}}"}
        t["labels"], t["apps"] = sel, c["apps"]
        if keys[c["kind"]]:
            anti, key = keys[c["kind"]]
            t["affinity"] = {"required": [{"anti": anti, "match_labels": sel,
                                           "topology_key": key}]}
    return t


# ---------------------------------------------------------------- nodes


def make_nodes(cfg: dict) -> List[NodeSpec]:
    out: List[NodeSpec] = []
    for g in deployment(cfg)["node_groups"]:
        zones = g.get("zones") or ()
        taints = tuple(Taint(x["key"], x.get("value", ""), x["effect"])
                       for x in g.get("taints", ()))
        ext = tuple(sorted(g.get("extended", {}).items()))
        for j in range(g["count"]):
            name = f"hollow-node-{len(out)}"
            labels = dict(g.get("labels", {}))
            labels[HOSTNAME_KEY] = name
            if zones:
                labels[ZONE_KEY] = zones[j % len(zones)]
            out.append(NodeSpec(name, g["cpu_m"], g["memory_mi"] * Mi,
                                g["pods"], labels, taints, ext))
    return out


# ------------------------------------------------------------- pod mixes


def _pairs(d: dict, k: int) -> Pairs:
    return tuple(sorted((a, str(b).replace("{k}", str(k)))
                        for a, b in d.items()))


def _term(t: dict, k: int) -> Term:
    return Term(bool(t.get("anti", False)), _pairs(t["match_labels"], k),
                t["topology_key"], tuple(t.get("namespaces", ())))


def _variants(t: dict) -> List[tuple]:
    """The template's fields after the pod's name, once per app."""
    aff = t.get("affinity", {})
    ext = tuple(sorted(t.get("extended", {}).items()))
    sel = tuple(sorted(t.get("node_selector", {}).items()))
    tols = tuple(Toleration(x.get("key", ""), x.get("operator", "Equal"),
                            x.get("value", ""), x.get("effect", ""))
                 for x in t.get("tolerations", ()))
    return [(t["namespace"], t["cpu_m"], t["memory_mi"] * Mi,
             _pairs(t.get("labels", {}), k),
             tuple(_term(x, k) for x in aff.get("required", ())),
             tuple((int(w["weight"]), _term(w["term"], k))
                   for w in aff.get("preferred", ())),
             ext, sel, tols)
            for k in range(int(t.get("apps", 1)))]


def _class_table(mix: List[dict]) -> List[str]:
    """Expand the mix's shares into 100 slots: slot r of every run of 100
    pods takes the template whose share range holds r (hollow.py's
    `i % 100` interleave)."""
    table = []
    for c in mix:
        table += [c["template"]] * int(c["share"])
    if len(table) != 100:
        raise ValueError(f"mix shares add to {len(table)}, not 100")
    return table


def mix_pods(cfg: dict, n: int, prefix: str) -> List[PodSpec]:
    """n pods of the config's mix in the hollow interleave: pod i takes
    slot i % 100, and each template cycles its apps in turn."""
    d = deployment(cfg)
    table = _class_table(d["mix"])
    variants = {t: _variants(d["pod_templates"][t]) for t in set(table)}
    used = dict.fromkeys(variants, 0)
    out = []
    for i in range(n):
        t = table[i % 100]
        k = used[t]
        used[t] = k + 1
        v = variants[t]
        out.append(PodSpec(f"{prefix}{i}", *v[k % len(v)]))
    return out


# ------------------------------------------------------------ background


def background(cfg: dict, nodes: List[NodeSpec], seed: int
               ) -> List[Tuple[PodSpec, int]]:
    """The set-up pods, each with its node, bound at set-up."""
    d = deployment(cfg)
    if "per_node" in d["setup"]:
        return _stride(d, nodes, seed)
    return _walk(d, nodes, seed)


def _stride_rule(t: dict) -> str:
    """How the stride picks a template's app on a node so that its
    required term holds: `plain` (none), `anti_host` (one per host of its
    own app) or `aff_zone` (its own app in one zone)."""
    req = t.get("affinity", {}).get("required", ())
    if t.get("extended") or t.get("node_selector") or t.get("tolerations") \
            or t.get("affinity", {}).get("preferred"):
        raise ValueError("setup.per_node takes no extended requests, "
                         "selectors, tolerations or preferred terms: use "
                         "{count, template}")
    if not req:
        return "plain"
    if len(req) == 1 and req[0]["match_labels"] == t.get("labels") \
            and not req[0].get("namespaces"):
        anti = bool(req[0].get("anti", False))
        if anti and req[0]["topology_key"] == HOSTNAME_KEY:
            return "anti_host"
        if not anti and req[0]["topology_key"] == ZONE_KEY:
            return "aff_zone"
    raise ValueError("setup.per_node takes plain templates and those whose "
                     "one required term is on their own labels (host "
                     "anti-affinity or zone affinity): use {count, template}")


def _stride(d: dict, nodes: List[NodeSpec], seed: int
            ) -> List[Tuple[PodSpec, int]]:
    """`per_node` pods on every node.

    The mix's templates go to nodes by a stride over the 100 slots, so
    each node holds the mix's shares; the apps are picked per node so that
    the required terms hold: one pod per app on a node that holds that
    app's one-per-host pod, and each one-zone app only in its zone. The
    seed permutes which node gets which composition."""
    per = d["setup"]["per_node"]
    mix = d["setup"]["mix"]
    table = _class_table(mix)
    tpl = d["pod_templates"]
    variants = {t: _variants(tpl[t]) for t in set(table)}
    rule = {t: _stride_rule(tpl[t]) for t in variants}
    # the selector of the first one-per-host template: the templates whose
    # labels hold it form the app family that template keeps apart
    family = next((tpl[e["template"]]["labels"].items() for e in mix
                   if rule[e["template"]] == "anti_host"), None)
    in_family = {t: family is not None
                 and family <= tpl[t].get("labels", {}).items()
                 for t in variants}
    zone_names = list(dict.fromkeys(n.labels[ZONE_KEY] for n in nodes
                                    if ZONE_KEY in n.labels))
    perm = rng_for(seed, "background").permutation(len(nodes))
    out: List[Tuple[PodSpec, int]] = []
    counters: Dict[str, int] = {}
    q = 0
    for slot_node in range(len(nodes)):
        node = int(perm[slot_node])
        zone = nodes[node].labels.get(ZONE_KEY)
        anti_apps = set()   # apps with a one-per-host pod on this node
        label_apps = set()  # apps of that family labelling any pod here
        for _ in range(per):
            t = table[(q * 37) % 100]
            q += 1
            k = counters.get(t, 0)
            counters[t] = k + 1
            v = variants[t]
            n_apps = len(v)
            turn = [x % n_apps for x in range(k, k + n_apps)]
            if rule[t] == "aff_zone":
                if zone is None:
                    raise ValueError(f"{nodes[node].name} has no zone for "
                                     f"template {t!r}")
                # apps z, z + zones, ... live in zone z only
                z = zone_names.index(zone)
                own = [a for a in range(n_apps) if a % len(zone_names) == z]
                a = own[k % len(own)]
            elif rule[t] == "anti_host":
                a = _first(turn, label_apps, t)
                anti_apps.add(a)
            elif in_family[t]:
                a = _first(turn, anti_apps, t)
            else:
                a = turn[0]
            if in_family[t]:
                label_apps.add(a)
            out.append((PodSpec(f"bg-{len(out)}", *v[a]), node))
    return out


def _first(turn: List[int], taken: set, t: str) -> int:
    for x in turn:
        if x not in taken:
            return x
    raise ValueError(f"setup.per_node: template {t!r} has too few apps for "
                     "one node")


def _walk(d: dict, nodes: List[NodeSpec], seed: int
          ) -> List[Tuple[PodSpec, int]]:
    """`count` pods of one template (scheduler_perf's init pods), each on
    the first node, along a walk over the nodes in an order drawn from
    the seed, where the reference's own predicates hold; the walk goes on
    from the node after the last one taken."""
    from reference import ClusterState
    s = d["setup"]
    v = _variants(d["pod_templates"][s["template"]])
    st = ClusterState(nodes)
    order = rng_for(seed, "setup-walk").permutation(len(nodes))
    out: List[Tuple[PodSpec, int]] = []
    pos = 0
    for j in range(int(s["count"])):
        pod = PodSpec(f"bg-{j}", *v[j % len(v)])
        node: Optional[int] = None
        for step in range(len(nodes)):
            at = int(order[(pos + step) % len(nodes)])
            if st.fits_at(pod, at):
                node = at
                pos += step + 1
                break
        if node is None:
            raise ValueError(f"set-up pod {pod.key} fits no node")
        st.add(pod, node)
        out.append((pod, node))
    return out


# ------------------------------------------------------------- arrivals


@dataclass
class Arrival:
    due: float          # seconds after the window opens
    spec: PodSpec
    group: int          # burst number, or -1 for a stream


def arrivals(cfg: dict, traffic: dict, seed: int, seconds: float
             ) -> List[Arrival]:
    """The open-loop schedule: every pod with its due time.

    Bursts: `pods` pods all due at each `period_s` instant inside the
    window; every burst is the same multiset of pods, in an order drawn
    from the seed."""
    a = traffic["arrivals"]
    out: List[Arrival] = []
    if a["kind"] == "burst":
        n = int(a["pods"])
        rng = rng_for(seed, "burst-order")
        k = 0
        t = float(a.get("first_s", 0.0))
        while t < seconds:
            pods = mix_pods(cfg, n, f"b{k}-")
            for i in rng.permutation(n):
                out.append(Arrival(t, pods[int(i)], k))
            k += 1
            t += float(a["period_s"])
        return out
    raise ValueError(f"unknown arrivals kind {a['kind']!r}")
