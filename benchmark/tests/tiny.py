"""The benchmark cut to a few seconds of CPU, for its own tests.

`tiny_root` copies a checkout's benchmark and cuts every cell of its
BENCHMARK.json with `tiny_cut`, whatever form the cell's configuration
takes, and adds a cell of each fixture configuration (FIXTURE_CELLS). A
cell added as files only is cut like the others, so its tiny runs and
broken-path controls need no edit of a test.
"""

from __future__ import annotations

import copy
import json
import math
import os
import shutil
from fractions import Fraction

HERE = os.path.dirname(os.path.abspath(__file__))
FIX = os.path.join(HERE, "fixtures")
REPO = os.path.dirname(os.path.dirname(HERE))

TINY_NODES = 60
TINY_BURST = 300
TINY_PERIOD_S = 2.0
TINY_SECONDS = {"stream": 4.0, "extender": 2.0}
# the seeds of test_harness.py's tiny runs, by what each run is for
SEED_RUN = 2 ** 35 + 11     # a cell's correct run
SEED_TRACED = 3             # its traced run
SEED_CONTROL = 4            # the controls (control.py)
SEED_FAULT = 5              # a broken-path case
SEED_BREACH = 6             # a fixture's infeasible bind
TINY_SEEDS = (SEED_TRACED, SEED_CONTROL, SEED_FAULT, SEED_BREACH, SEED_RUN)

# cells of fixture configurations in the tiny root: (cell, config file,
# traffic, limits). `affinity-burst` keeps the older form's kinds under
# test. `schedperf-anti` and `schedperf-pref` are scheduler_perf's
# anti-affinity and preferred-affinity templates as published (one label
# shared by set-up and measured pods, both namespaces in the term, a
# set-up count over the cluster); `gpu-taints` has node groups with
# taints and GPUs, and pods with tolerations, GPU requests and a
# nodeSelector. Through the extender every sampled node score is
# compared exactly; a stream cell judges placement, which under
# preferred terms a wave places against its start state (PERF.md
# section 7), so preferred terms go through the extender only. The
# fixtures are small already and are not cut.
FIXTURE_CELLS = [
    ("affinity-burst", "affinity_mix.json", "burst30k",
     {"score_gap_max": 6}),
    ("schedperf-anti", "schedperf_anti.json", "burst30",
     {"score_gap_max": 6}),
    ("gpu-taints", "gpu_taints.json", "burst30k", {"score_gap_max": 6}),
    ("schedperf-pref-extender", "schedperf_pref.json", "extender-all", {}),
    ("gpu-taints-extender", "gpu_taints.json", "extender-all", {}),
]


def tiny_cut(cfg: dict, traffic: dict):
    """`cfg` and its cell's `traffic`, cut to about TINY_NODES nodes.

    The older form (`nodes`) gets exactly 60 nodes. The deployment form
    scales every node group's count by one factor f = 60 / its nodes (a
    group keeps one node at least), and a `{count, template}` set-up's
    count by the same f, rounded down; `per_node` stays. A stream's
    bursts scale by f too, at most 300 pods, every 2 s: a deployment
    whose bursts fit at full size still fits when cut."""
    cfg = copy.deepcopy(cfg)
    if "nodes" in cfg:
        f = Fraction(TINY_NODES, cfg["nodes"]["count"])
        cfg["nodes"]["count"] = TINY_NODES
    else:
        f = Fraction(TINY_NODES, sum(g["count"] for g in cfg["node_groups"]))
        for g in cfg["node_groups"]:
            g["count"] = max(1, round(g["count"] * f))
        if "count" in cfg["setup"]:
            cfg["setup"]["count"] = math.floor(cfg["setup"]["count"] * f)
    return cfg, _cut_traffic(traffic, f)


def _cut_traffic(traffic: dict, f: Fraction) -> dict:
    if traffic["driver"] != "stream":
        return traffic
    a = dict(traffic["arrivals"],
             pods=min(TINY_BURST, max(1, math.floor(traffic["arrivals"]
                                                     ["pods"] * f))),
             period_s=TINY_PERIOD_S)
    return dict(traffic, arrivals=a)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _dump(obj: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(dst: str, src: str = REPO) -> str:
    """A copy of `src`'s benchmark under `dst`, every cell cut by
    `tiny_cut`, plus a cell of each fixture configuration, so the
    generator's and the reference's rules for every field of the schema
    run end to end.

    Each mix keeps its name cut as a fixture cell reads it (f = 1); a
    cell whose own cut differs (cells of different configurations may
    share a mix) reads `<mix>.<cell>` instead."""
    root = str(dst)
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(src, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    tdir = os.path.join(bench, "traffic")
    mixes = {n[:-len(".json")]: _load(os.path.join(tdir, n))
             for n in os.listdir(tdir) if n.endswith(".json")}
    written = {}
    for name, t in mixes.items():
        written[name] = _cut_traffic(t, Fraction(1))
        _dump(written[name], os.path.join(tdir, name + ".json"))
    b = _load(os.path.join(src, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in b["configs"]}
    for w in b["workloads"]:
        cfg, t = tiny_cut(_load(os.path.join(src, files[w["config"]])),
                          mixes[w["traffic"]])
        _dump(cfg, os.path.join(root, files[w["config"]]))
        if t != written[w["traffic"]]:
            w["traffic"] = f"{w['traffic']}.{w['name']}"
            _dump(t, os.path.join(tdir, w["traffic"] + ".json"))
    _dump({"driver": "stream", "arrivals": {
        "kind": "burst", "pods": 30, "period_s": 2.0, "first_s": 0.0},
        "grace_s": 60, "score_sample": 256},
        os.path.join(tdir, "burst30.json"))
    _dump(dict(mixes["extender"], sample_every=1),
          os.path.join(tdir, "extender-all.json"))
    for cell, cfile, traffic, lim in FIXTURE_CELLS:
        name = _load(os.path.join(FIX, cfile))["name"]
        if name not in {c["name"] for c in b["configs"]}:
            shutil.copy(os.path.join(FIX, cfile),
                        os.path.join(bench, "configs", name + ".json"))
            b["configs"].append({"name": name, "source": "fixture",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "t"})
        b["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic, "chips": 1, "why": "t"})
        like = "density30-extender" if traffic.startswith("extender") \
            else "density30-burst30k"
        for m in b["end_to_end"] + b["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
        _dump(lim, os.path.join(bench, "limits", cell + ".json"))
    _dump(b, os.path.join(root, "BENCHMARK.json"))
    return root
