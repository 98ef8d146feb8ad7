"""The inputs the existing cells are given, pinned.

Every cell's nodes, set-up pods, arrival schedule, extender pool and
warm-up pods are made from its seed by `spec.py`. A digest of each, for
both cells and the older form's affinity fixture at three seeds, was
taken before the configuration schema grew node groups and pod
templates; the loader must reproduce it byte for byte, so the cells
measure the same work from the same seed. The tiny cells of the harness's
own tests (tiny.py) are pinned the same way, at every seed their runs
take, to digests of the tiny root that tiny.py replaced.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_inputs.py -q
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(BENCH, "tests"))

import drivers  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import tiny  # noqa: E402

SEEDS = (1, 2 ** 31 + 3, 2 ** 40 + 7)
WINDOW_S = 48.0
# the stream loop's own quantum ladder (engine/streaming.py defaults),
# which _warm_stream walks
LADDER = drivers._ladder(256, 16384)


def _pod_row(s) -> list:
    row = [s.name, s.namespace, s.cpu_m, s.mem, [list(p) for p in s.labels],
           [_term_row(t) for t in s.terms]]
    extra = {k: getattr(s, k) for k in ("extended", "node_selector",
                                         "tolerations", "preferred")
             if getattr(s, k, None)}
    if extra:
        row.append(repr(sorted(extra.items())))
    return row


def _term_row(t) -> list:
    row = [t.anti, [list(p) for p in t.match], t.key]
    if getattr(t, "namespaces", ()):
        row.append(list(t.namespaces))
    return row


def _node_row(n) -> list:
    row = [n.name, n.cpu_m, n.mem, n.pods, sorted(n.labels.items())]
    extra = {k: getattr(n, k) for k in ("taints", "extended")
             if getattr(n, k, None)}
    if extra:
        row.append(repr(sorted(extra.items())))
    return row


class _Digest:
    def __init__(self):
        self.h = hashlib.sha256()

    def add(self, tag: str, rows) -> None:
        self.h.update(tag.encode())
        for r in rows:
            self.h.update(json.dumps(r, separators=(",", ":")).encode())
            self.h.update(b"\n")


def _digest(cfg: dict, traffic: dict, seed: int) -> str:
    d = _Digest()
    nodes = spec.make_nodes(cfg)
    d.add("nodes", map(_node_row, nodes))
    d.add("background", ([_pod_row(s), i]
                         for s, i in spec.background(cfg, nodes, seed)))
    if traffic["driver"] == "stream":
        d.add("arrivals", ([a.due, a.group, _pod_row(a.spec)] for a in
                           spec.arrivals(cfg, traffic, seed, WINDOW_S)))
        for s in LADDER:
            d.add(f"warm{s}", map(_pod_row,
                                  spec.mix_pods(cfg, s, f"warm{s}-")))
            if 2 * s <= LADDER[-1]:
                m = int(1.4 * s)
                d.add(f"warm{m}", map(_pod_row, spec.mix_pods(
                    cfg, m, f"warm{m}-")))
        d.add("rehearsal", map(_pod_row, spec.mix_pods(
            cfg, int(traffic["arrivals"]["pods"]), "rehearsal-")))
    else:
        d.add("pool", map(_pod_row, drivers._extender_pool(cfg, seed)))
        d.add("warm", map(_pod_row, spec.mix_pods(cfg, 200, "warm-")))
    return d.h.hexdigest()


def _cell_digest(cell: str, seed: int) -> str:
    """A cell of BENCHMARK.json, or `affinity-burst`: the fixture mix of
    the older form's three kinds (fixtures/affinity_mix.json) in bursts."""
    if cell == "affinity-burst":
        cfg = json.load(open(os.path.join(BENCH, "tests", "fixtures",
                                          "affinity_mix.json")))
        return _digest(cfg, spec.load_json("traffic", "burst30k"), seed)
    bench = json.load(open(os.path.join(os.path.dirname(BENCH),
                                        "BENCHMARK.json")))
    w = next(x for x in bench["workloads"] if x["name"] == cell)
    return _digest(spec.load_json("configs", w["config"]),
                   spec.load_json("traffic", w["traffic"]), seed)


# taken on the parent commit of the schema change, with this file's dump
PINNED = {
    ("density30-burst30k", 1):
        "e0782c2449a22a5d2730ea767543d4832651daea1c064626c56693b0a8bd4b72",
    ("density30-burst30k", 2147483651):
        "76664f3eb8c249c537646a7d494878a256d75d46ecd747d17670e0becb8c76bc",
    ("density30-burst30k", 1099511627783):
        "54e8bad10068cf88bffe763cf00b3b58c59da4f99aeb1461f2a6071df8a0cf19",
    ("density30-extender", 1):
        "32c0d11becf045eb31e9a234b56c95469e718a79a6d974898dacf5b943930db4",
    ("density30-extender", 2147483651):
        "59bca8fb22e6feb399e9979d0db353dfd42bdcc3545a750dc9e25e8038c3ffb6",
    ("density30-extender", 1099511627783):
        "fdb0eb4209cc56de6aa0329e59163061f6d985be4458ac153c66add86248ba83",
    ("affinity-burst", 1):
        "76887dde0e59dc729385190ca4866367d9fd364cfdd79d599390035a873cb287",
    ("affinity-burst", 2147483651):
        "7dc451880d4c7ae06576cc2cf6d98ea547744c16b4d39c9b1b8b2ca954d9134d",
    ("affinity-burst", 1099511627783):
        "5c8dcf45ccb13657a04aef0d3395fd7141cb542390cefa56b906e3c4e58d1d1c",
}


@pytest.mark.parametrize("cell,seed", sorted(PINNED))
def test_cell_inputs_are_pinned(cell, seed):
    assert _cell_digest(cell, seed) == PINNED[(cell, seed)]


def tiny_digest(root: str, cell: str) -> str:
    """A cell of the harness's tiny root (tiny.py): its traffic, its
    nodes, and at every seed its tiny runs take, its set-up, and its
    arrivals over the tiny window or its extender pool."""
    r = run.resolve(run.load_benchmark(root), cell, root)
    cfg, traffic = r["cfg"], r["traffic"]
    d = _Digest()
    d.add("traffic", [json.dumps(traffic, sort_keys=True)])
    nodes = spec.make_nodes(cfg)
    d.add("nodes", map(_node_row, nodes))
    for seed in tiny.TINY_SEEDS:
        d.add(f"background{seed}", ([_pod_row(s), i] for s, i in
                                    spec.background(cfg, nodes, seed)))
        if traffic["driver"] == "stream":
            d.add(f"arrivals{seed}", (
                [a.due, a.group, _pod_row(a.spec)] for a in spec.arrivals(
                    cfg, traffic, seed, tiny.TINY_SECONDS["stream"])))
        else:
            d.add(f"pool{seed}", map(_pod_row,
                                     drivers._extender_pool(cfg, seed)))
    return d.h.hexdigest()


# taken on the parent commit of tiny.py, from the tiny root it replaced
TINY_PINNED = {
    "density30-burst30k":
        "50eef9813a0bc88a6f569d2df82328d198de0d548d7fcd3a4fd255ea87fa3ceb",
    "density30-extender":
        "3a6bf70e9ec5254a29a75138178d576731c2e9e52eb4d61c1c98cde236b495bb",
    "affinity-burst":
        "ac8c70b6bac70ccd84aa815ba4603bccd26bbf5435bab03990a0bcdc234ca482",
    "schedperf-anti":
        "37d47f793b58968b7918fa0f743806843ae74cecf2adbe7ad3f4dd77efd66dbe",
    "gpu-taints":
        "aa92c9f2fc8b5d40d2d341df35613cde13d524c9fc252848f3267e752ba88cd5",
    "schedperf-pref-extender":
        "526dcd2b05b1cbd603126a50b63795b41e37b779a52e57d9e8a265e11e9c8e96",
    "gpu-taints-extender":
        "94b75bc253a5d15119693be82e435bf7de964f609bb7ca0ac131542642ffd62e",
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", sorted(TINY_PINNED))
def test_tiny_inputs_are_pinned(tiny_root, cell):
    assert tiny_digest(tiny_root, cell) == TINY_PINNED[cell]


if __name__ == "__main__":
    for cell in ("density30-burst30k", "density30-extender",
                 "affinity-burst"):
        for seed in SEEDS:
            print(f'    ("{cell}", {seed}): "{_cell_digest(cell, seed)}",')
