"""The benchmark's own tests, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- every cell in BENCHMARK.json resolves to its config, traffic and
  metric files, and the file keeps the contract's shape;
- adding a cell, a config, a mix or a metric needs only new files and
  new entries;
- a run that finds no TPU, or no program, exits non-zero with no result;
- the arithmetic and the trace reduction, on fixed inputs and a trace
  recorded on the CPU (fixtures/);
- each cell end to end at a tiny size (the TPU look skipped only here),
  with `correct` true;
- the timed path broken underneath (state unchanged, half of each batch
  left out, an answer altered where it is produced): `correct` false;
- the controls (control.py) fail the limits the program passes.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, REPO)

import check  # noqa: E402
import control  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402
from reference import ClusterState, place_background  # noqa: E402

FIX = os.path.join(BENCH, "tests", "fixtures")
BENCHMARK = run.load_benchmark(REPO)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
TINY_SECONDS = {"stream": 4.0, "extender": 2.0}


AFFINITY = os.path.join(FIX, "affinity_mix.json")
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
# section 7), so preferred terms go through the extender only.
FIXTURE_CELLS = [
    ("affinity-burst", "affinity_mix.json", "burst30k",
     {"score_gap_max": 6}),
    ("schedperf-anti", "schedperf_anti.json", "burst30",
     {"score_gap_max": 6}),
    ("gpu-taints", "gpu_taints.json", "burst30k", {"score_gap_max": 6}),
    ("schedperf-pref-extender", "schedperf_pref.json", "extender-all", {}),
    ("gpu-taints-extender", "gpu_taints.json", "extender-all", {}),
]


@pytest.fixture(autouse=True)
def small_quanta(monkeypatch):
    """The stream loop's quantum ladder cut to the tiny bursts (the
    benchmark itself takes the loop's own defaults)."""
    from kubernetes_tpu.engine.scheduler import Scheduler
    orig = Scheduler.stream

    def stream(self, **kw):
        kw.setdefault("min_quantum", 64)
        kw.setdefault("max_quantum", 256)
        return orig(self, **kw)
    monkeypatch.setattr(Scheduler, "stream", stream)


def _tiny_root(tmp_path) -> str:
    """A copy of the benchmark whose configs and mixes are cut to a few
    seconds of CPU: 60 nodes, 300-pod bursts every 2 s; plus a cell of
    each fixture configuration (FIXTURE_CELLS), so the generator's and
    the reference's rules for every field of the schema run end to
    end."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for c in BENCHMARK["configs"]:
        path = os.path.join(root, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["nodes"]["count"] = 60
        with open(path, "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(root, "benchmark", "traffic")
    for name in os.listdir(tdir):
        path = os.path.join(tdir, name)
        with open(path) as f:
            t = json.load(f)
        if t["driver"] == "stream":
            t["arrivals"]["pods"], t["arrivals"]["period_s"] = 300, 2.0
        with open(path, "w") as f:
            json.dump(t, f)
    with open(os.path.join(tdir, "burst30.json"), "w") as f:
        json.dump({"driver": "stream", "arrivals": {
            "kind": "burst", "pods": 30, "period_s": 2.0, "first_s": 0.0},
            "grace_s": 60, "score_sample": 256}, f)
    ext = spec.load_json("traffic", "extender")
    with open(os.path.join(tdir, "extender-all.json"), "w") as f:
        json.dump(dict(ext, sample_every=1), f)
    b = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for cell, cfile, traffic, lim in FIXTURE_CELLS:
        name = json.load(open(os.path.join(FIX, cfile)))["name"]
        if name not in {c["name"] for c in b["configs"]}:
            shutil.copy(os.path.join(FIX, cfile), os.path.join(
                root, "benchmark", "configs", name + ".json"))
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
        with open(os.path.join(root, "benchmark", "limits",
                               cell + ".json"), "w") as f:
            json.dump(lim, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed, trace, capsys):
    seconds = TINY_SECONDS[run.resolve(
        run.load_benchmark(root), cell, root)["traffic"]["driver"]]
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


# ------------------------------------------------------------- resolving


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    import drivers
    r = run.resolve(BENCHMARK, cell)
    assert r["cfg"]["name"] == r["cell"]["config"]
    assert r["traffic"]["driver"] in drivers.DRIVERS
    assert r["cell"]["chips"] == 1
    names = {m["name"] for m in r["e2e"]}
    assert "setup_s" in names and len(names) >= 2
    assert r["per_layer"], "every cell reports a per-layer metric"
    for m in r["per_layer"]:
        assert callable(r["readers"][m["name"]])


def test_benchmark_file_shape():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"]
    e2e = {m["name"] for m in b["end_to_end"]}
    assert e2e == {"pods_bound_s", "create_to_bound_p50_ms",
                   "create_to_bound_p99_ms", "setup_s"}
    for w in b["workloads"]:
        # no traffic file sets the program's own tuning
        t = spec.load_json("traffic", w["traffic"])
        assert not {"budget_ms", "min_quantum", "max_quantum"} & set(t)
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in b["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for c in b["configs"]:
        cfg = json.load(open(os.path.join(REPO, c["file"])))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert set(cfg["priorities"]) <= set(
            __import__("reference").KNOWN_PRIORITIES)


def test_adding_needs_only_new_files(tmp_path):
    """A new config, mix, metric and cell (with its limits): new files
    and new entries in BENCHMARK.json, and no file that was there
    edited."""
    root = _tiny_root(tmp_path)
    before = {p: open(p, "rb").read() for p in _files(root)}
    cfg = json.load(open(os.path.join(
        root, "benchmark/configs/kubemark5k-density30.json")))
    cfg["name"] = "new-config"
    with open(os.path.join(root, "benchmark/configs/new-config.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "benchmark/traffic/new-mix.json"),
              "w") as f:
        json.dump({"driver": "stream", "arrivals": {"kind": "burst",
                                                    "pods": 100,
                                                    "period_s": 3.0},
                   "grace_s": 10, "score_sample": 8}, f)
    with open(os.path.join(root, "benchmark/metrics/new_metric.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 1.0\n")
    with open(os.path.join(root, "benchmark/limits/new-cell.json"),
              "w") as f:
        json.dump({"score_gap_max": 5}, f)
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b["configs"].append({"name": "new-config", "source": "x",
                         "file": "benchmark/configs/new-config.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "new-cell", "config": "new-config",
                           "traffic": "new-mix", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "new_metric", "unit": "%",
                           "better": "lower", "source": "program_span",
                           "layer": "engine", "moves": "pods_bound_s",
                           "workloads": ["new-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    r = run.resolve(b, "new-cell", root)
    assert r["cfg"]["name"] == "new-config"
    assert r["traffic"]["arrivals"]["pods"] == 100
    assert r["readers"]["new_metric"](None) == 1.0
    after = {p: open(p, "rb").read() for p in _files(root)}
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [os.path.join(root, "BENCHMARK.json")]


def _files(root):
    for d, _s, fs in os.walk(root):
        for f in fs:
            yield os.path.join(d, f)


# ------------------------------------------------------------ refusing


def test_no_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_no_program_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


# ---------------------------------------------------------- arithmetic


def test_percentiles_rates_and_shares():
    xs = [0.001 * i for i in range(1, 101)]           # 1..100 ms
    assert stats.percentile_ms(xs, 50) == pytest.approx(50.5)
    assert stats.percentile_ms(xs, 99) == pytest.approx(99.01)
    assert stats.percentile_ms([], 99) is None
    assert stats.rate(150_000, 30.0) == 5000.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    assert stats.per_kpod_ms(0.25, 5000) == pytest.approx(50.0)
    assert stats.per_kpod_ms(1.0, 0) is None
    assert stats.per_pod_us(0.003, 1000) == pytest.approx(3.0)
    assert stats.share_pct(1.0, 4.0) == 25.0
    assert stats.union_length([(0, 2), (1, 3), (5, 6), (6, 6)]) == 4


def test_trace_reduction_on_recorded_trace():
    from trace_reduce import DeviceTrace
    meta = json.load(open(os.path.join(FIX, "cpu_trace.json")))
    tr = DeviceTrace(os.path.join(FIX, "cpu_trace.xplane.pb"),
                     anchor_mono=meta["anchor_mono"], cpu_ops=True)
    assert tr.platform == "cpu" and tr.anchor_ns is not None
    a, b = tr.to_ns(meta["anchor_mono"]), tr.to_ns(meta["end_mono"])
    assert a == tr.anchor_ns
    busy = tr.busy_s([(a, b)])
    assert 0 < busy < (b - a) / 1e9
    waves = tr.program_s(["waves_loop"], [(a, b)])
    both = tr.program_s(["jit_"], [(a, b)])
    assert 0 < waves < both
    # the busy union never exceeds the summed program time
    assert busy <= both + 1e-9
    bd = tr.breakdown(a, b)
    assert [n for n, _s in bd["device_ops"]] == ["jit__lambda",
                                                 "jit_waves_loop"]
    assert len(bd["idle_gaps"]) <= 10
    assert {n for n, _s in bd["idle_gaps"]} <= {"create", "stream.step",
                                                "bench.anchor", "none"}
    idle = sum(s for _n, s in bd["idle_by_host"])
    assert idle == pytest.approx((b - a) / 1e9 - busy, rel=1e-6)
    # the host sleeps inside "create": the longest gap is named for it
    assert bd["idle_gaps"][0][0] == "create"


def test_peaks_table():
    from trace_reduce import peaks
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


@pytest.mark.parametrize("path", [os.path.join(REPO, c["file"])
                                  for c in BENCHMARK["configs"]]
                         + sorted({os.path.join(FIX, c[1])
                                   for c in FIXTURE_CELLS}))
def test_background_keeps_the_rules(path):
    cfg = json.load(open(path))
    nodes = spec.make_nodes(cfg)
    placed = spec.background(cfg, nodes, 2 ** 40 + 7)
    setup = spec.deployment(cfg)["setup"]
    per = setup.get("per_node")
    assert len(placed) == (per * len(nodes) if per else setup["count"])
    st = ClusterState(nodes)
    assert place_background(st, placed, check=True) == 0
    if per:
        assert (st.count == per).all()


def test_seeds_change_order_not_load():
    cfg = json.load(open(AFFINITY))
    tr = spec.load_json("traffic", "burst30k")
    a = spec.arrivals(cfg, tr, 1, 12.0)
    b = spec.arrivals(cfg, tr, 2 ** 33 + 5, 12.0)
    assert [x.spec.key for x in a] != [x.spec.key for x in b]
    assert sorted((x.due, x.spec.key) for x in a) == \
        sorted((x.due, x.spec.key) for x in b)
    assert sorted({x.due for x in a}) == [0.0, 6.0]


# ------------------------------------------------------------ end to end


@pytest.mark.parametrize("cell", CELLS + [c[0] for c in FIXTURE_CELLS])
def test_tiny_run_is_correct(tiny_root, cell, capsys):
    rc, res = _run(tiny_root, cell, 2 ** 35 + 11, 0, capsys)
    assert rc == 0
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    want = {m["name"] for m in run.resolve(
        run.load_benchmark(tiny_root), cell, tiny_root)["e2e"]}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_traced_run(tiny_root, cell, capsys):
    rc, res = _run(tiny_root, cell, 3, 1, capsys)
    assert rc == 0 and res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res
    # on the CPU no device plane exists: the device metrics read nothing
    # and are left out, the span metrics are there
    for name in res["metrics"]:
        assert not name.startswith(("device_idle", "waves_device",
                                    "extender_device"))
    if cell == "density30-extender":
        assert res["metrics"]["scheduleone_p99_ms"]["value"] > 0


# ------------------------------------------------------------- faults


def _store_drops_binds(mp):
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite
    mp.setattr(ApiServerLite, "bind_pods_bulk",
               lambda self, pods: [None] * len(pods))


def _half_batch(mp):
    from kubernetes_tpu.engine.scheduler_engine import SchedulingEngine
    orig = SchedulingEngine.harvest_waves

    def half(self, handle):
        res = orig(self, handle)
        res.bound = res.bound[::2]
        return res
    mp.setattr(SchedulingEngine, "harvest_waves", half)


def _altered_bind(mp):
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite
    orig = ApiServerLite.bind_pods_bulk

    def alter(self, pods):
        import copy
        out = []
        for i, p in enumerate(pods):
            if i % 5 == 0:
                p = copy.copy(p)
                p.node_name = "hollow-node-0"
            out.append(p)
        return orig(self, out)
    mp.setattr(ApiServerLite, "bind_pods_bulk", alter)


def _rebind_one(node: str, tolerating: bool):
    """The first pod of each bulk bind that has tolerations (or, with
    `tolerating` false, none) is written to `node` instead."""
    def fault(mp):
        from kubernetes_tpu.server.apiserver_lite import ApiServerLite
        orig = ApiServerLite.bind_pods_bulk

        def alter(self, pods):
            import copy
            out, done = [], False
            for p in pods:
                if not done and bool(p.tolerations) == tolerating:
                    p = copy.copy(p)
                    p.node_name = node
                    done = True
                out.append(p)
            return orig(self, out)
        mp.setattr(ApiServerLite, "bind_pods_bulk", alter)
    fault.__name__ = f"rebind_{'tolerating' if tolerating else 'plain'}"
    return fault


def _altered_score(mp):
    from kubernetes_tpu.server.extender import ExtenderHTTPServer
    orig = ExtenderHTTPServer.handle_prioritize

    def alter(self, payload):
        out = orig(self, payload)
        if out:
            out[0] = {"Host": out[0]["Host"], "Score": out[0]["Score"] + 1}
        return out
    mp.setattr(ExtenderHTTPServer, "handle_prioritize", alter)


def _extender_binds_lost(mp):
    from kubernetes_tpu.testing import churn

    def binder(api):
        return lambda *a: None      # acknowledges, never writes
    mp.setattr(churn, "extender_store_binder", binder)


@pytest.mark.parametrize("cell,fault", [
    ("density30-burst30k", _store_drops_binds),
    ("density30-burst30k", _half_batch),
    ("density30-burst30k", _altered_bind),
    ("affinity-burst", _altered_bind),
    ("density30-extender", _extender_binds_lost),
    ("density30-extender", _altered_score),
])
def test_broken_path_is_not_correct(tiny_root, cell, fault, capsys,
                                    monkeypatch):
    fault(monkeypatch)
    rc, res = _run(tiny_root, cell, 5, 0, capsys)
    assert rc == 0
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell,fault", [
    # one-per-host pods piled on one node, across both namespaces
    ("schedperf-anti", _altered_bind),
    # a pod that tolerates nothing on the NoSchedule-tainted GPU group
    ("gpu-taints", _rebind_one("hollow-node-59", tolerating=False)),
    # a GPU pod on a CPU node: no GPUs there, and its nodeSelector fails
    ("gpu-taints", _rebind_one("hollow-node-0", tolerating=True)),
])
def test_fixture_breach_is_an_infeasible_bind(tiny_root, cell, fault,
                                              capsys, monkeypatch):
    fault(monkeypatch)
    rc, res = _run(tiny_root, cell, 6, 0, capsys)
    assert rc == 0
    assert not res["correct"]
    assert res["checks"]["infeasible_binds"]["value"] > 0, res["checks"]


# ------------------------------------------------------------- controls


def test_controls_fail_where_the_program_passes(tiny_root):
    """The stream control (first fit, priorities skipped) reads a score
    gap over the limit; the 32-bit extender control a score mismatch."""
    import drivers
    r = run.resolve(run.load_benchmark(tiny_root), "density30-burst30k",
                    tiny_root)
    ctl = control.stream_control(r["cfg"], r["traffic"], 4, 4.0,
                                 "first_fit", r["limits"])
    assert not check.verdict(ctl)
    assert ctl["score_gap_max"][0] > ctl["score_gap_max"][1]
    r = run.resolve(run.load_benchmark(tiny_root), "density30-extender",
                    tiny_root)
    rec = drivers.extender(r["cfg"], r["traffic"], 4, 2.0, False)
    assert check.verdict(check.extender_checks(rec, r["cfg"], r["traffic"],
                                               4, r["limits"]))
    ctl = control.extender_control(rec, r["cfg"], r["traffic"], 4,
                                   r["limits"])
    assert ctl["score_mismatch_nodes"][0] > 0
