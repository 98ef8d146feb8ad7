"""The benchmark's own tests, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- every cell in BENCHMARK.json resolves to its config, traffic and
  metric files, and the file keeps the contract's shape;
- adding a cell, a config, a mix or a metric needs only new files and
  new entries; a deployment-form configuration so added at full size is
  cut like any other (tiny.py) and runs end to end;
- a run that finds no TPU, or no program, exits non-zero with no result;
- the arithmetic and the trace reduction, on fixed inputs and a trace
  recorded on the CPU (fixtures/);
- each cell end to end at a tiny size (the TPU look skipped only here),
  with `correct` true;
- the timed path broken underneath, for every cell by its driver (binds
  dropped, half of each batch left out, an answer altered where it is
  produced): `correct` false;
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
sys.path.insert(2, os.path.join(BENCH, "tests"))

import check  # noqa: E402
import control  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402
import tiny  # noqa: E402
from reference import ClusterState, place_background  # noqa: E402
from tiny import (FIXTURE_CELLS, SEED_BREACH, SEED_CONTROL,  # noqa: E402
                  SEED_FAULT, SEED_RUN, SEED_TRACED, TINY_SECONDS)

FIX = os.path.join(BENCH, "tests", "fixtures")
BENCHMARK = run.load_benchmark(REPO)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
AFFINITY = os.path.join(FIX, "affinity_mix.json")


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


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("bench"))


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
    assert r["cell"]["chips"] in (1, 4)
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
    root = tiny.tiny_root(tmp_path)
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


# the deployment that test adds goes under names of its own, which no
# committed cell, configuration or mix takes
PROBE = "probe-podanti"
PROBE_CONFIG = "probe-schedperf5k"
PROBE_MIX = "probe-burst4k"


def _full_copy(dst):
    """The benchmark as committed, at full size, under `dst`."""
    shutil.copytree(BENCH, dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    return dst


def _add_probe_cell(root: str) -> None:
    """scheduler_perf's SchedulingPodAntiAffinity at 5,000 nodes and
    1,000 init pods, as a deployment-form configuration (the fixture's
    templates), in 4,000-pod bursts every 6 s, which fill every free
    node, the tightest fit for the cut: its traffic, limits and
    entries."""
    cfg = json.load(open(os.path.join(FIX, "schedperf_anti.json")))
    cfg.update(name=PROBE_CONFIG, source=(
        "https://github.com/kubernetes/kubernetes/tree/master/test/"
        "integration/scheduler_perf"))
    cfg["node_groups"][0]["count"] = 5000
    cfg["setup"]["count"] = 1000
    with open(os.path.join(root, "benchmark/configs",
                           cfg["name"] + ".json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "benchmark/traffic", PROBE_MIX + ".json"),
              "w") as f:
        json.dump({"driver": "stream", "arrivals": {
            "kind": "burst", "pods": 4000, "period_s": 6.0, "first_s": 0.0},
            "grace_s": 60, "score_sample": 256}, f)
    with open(os.path.join(root, "benchmark/limits", PROBE + ".json"),
              "w") as f:
        json.dump({"score_gap_max": 7}, f)
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": f"benchmark/configs/{cfg['name']}.json",
                         "reduced": [], "why": "one replica per host"})
    b["workloads"].append({"name": PROBE, "config": cfg["name"],
                           "traffic": PROBE_MIX, "chips": 1, "why": "t"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "density30-burst30k" in m.get("workloads", ()):
            m["workloads"].append(PROBE)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)


def test_adding_a_deployment_needs_only_new_files(tmp_path, capsys,
                                                  monkeypatch):
    """A deployment-form configuration at full size, added as
    new files and entries, is cut by the tiny root like any other: its
    tiny run is `correct`, and its broken-bind control is not."""
    full = _full_copy(tmp_path / "full")
    before = {p: open(p, "rb").read() for p in _files(full)}
    _add_probe_cell(str(full))
    after = {p: open(p, "rb").read() for p in _files(full)}
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [str(full / "BENCHMARK.json")]
    root = tiny.tiny_root(tmp_path / "tiny", src=str(full))
    rc, res = _run(root, PROBE, SEED_RUN, 0, capsys)
    assert rc == 0
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    _altered_bind(monkeypatch)
    rc, res = _run(root, PROBE, SEED_FAULT, 0, capsys)
    assert rc == 0
    assert not res["correct"]
    assert res["checks"]["infeasible_binds"]["value"] > 0, res["checks"]


def test_tiny_cut_of_each_form():
    burst = spec.load_json("traffic", "burst30k")
    ext = spec.load_json("traffic", "extender")
    # the older form: exactly 60 nodes; 30,000 x 60 / 5,000 = 360 pods,
    # capped at 300
    cfg = spec.load_json("configs", "kubemark5k-density30")
    cut, t = tiny.tiny_cut(cfg, burst)
    assert cut == dict(cfg, nodes=dict(cfg["nodes"], count=60))
    assert t == dict(burst, arrivals=dict(burst["arrivals"], pods=300,
                                          period_s=2.0))
    assert tiny.tiny_cut(cfg, ext)[1] == ext
    # node groups keep their proportions, and {count, template} set-up
    # pods scale with them: 3,000 + 1,000 + 2,000 nodes and 2,000 GPU
    # pods cut by 1/100
    gpu = json.load(open(os.path.join(FIX, "gpu_taints.json")))
    for g in gpu["node_groups"]:
        g["count"] *= 100
    gpu["setup"]["count"] *= 100
    cut, t = tiny.tiny_cut(gpu, burst)
    assert [g["count"] for g in cut["node_groups"]] == [30, 10, 20]
    assert cut["setup"] == {"count": 20, "template": "gpu"}
    assert t["arrivals"]["pods"] == 300
    # a group too small for its share keeps one node
    gpu["node_groups"][2]["count"] = 20
    cut, _t = tiny.tiny_cut(gpu, burst)
    assert [g["count"] for g in cut["node_groups"]] == [45, 15, 1]
    # scheduler_perf's 5000Nodes anti-affinity: every free node filled
    anti = json.load(open(os.path.join(FIX, "schedperf_anti.json")))
    anti["node_groups"][0]["count"], anti["setup"]["count"] = 5000, 1000
    four = dict(burst, arrivals=dict(burst["arrivals"], pods=4000))
    cut, t = tiny.tiny_cut(anti, four)
    assert (cut["node_groups"][0]["count"], cut["setup"]["count"],
            t["arrivals"]["pods"]) == (60, 12, 48)


def test_tiny_root_cuts_a_shared_mix_per_cell(tmp_path):
    """Cells whose cut of a mix differs from the mix's own (the fixtures'
    cut, f = 1) each read theirs as `<mix>.<cell>`."""
    full = _full_copy(tmp_path / "full")
    _add_probe_cell(str(full))
    b = json.load(open(full / "BENCHMARK.json"))
    b["workloads"].append(dict(b["workloads"][0], name="probe-density30",
                               traffic=PROBE_MIX))
    shutil.copy(full / "benchmark/limits/density30-burst30k.json",
                full / "benchmark/limits/probe-density30.json")
    with open(full / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    root = tiny.tiny_root(tmp_path / "tiny", src=str(full))
    tb = run.load_benchmark(root)
    cut = {w["name"]: (w["traffic"], run.resolve(tb, w["name"], root)[
        "traffic"]["arrivals"]["pods"]) for w in tb["workloads"]
        if w["name"] in ("probe-density30", PROBE, "density30-burst30k")}
    assert cut == {"probe-density30": (PROBE_MIX + ".probe-density30", 48),
                   PROBE: (PROBE_MIX + "." + PROBE, 48),
                   "density30-burst30k": ("burst30k", 300)}
    t = json.load(open(os.path.join(root, "benchmark/traffic",
                                    PROBE_MIX + ".json")))
    assert t["arrivals"]["pods"] == 300


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
    rc, res = _run(tiny_root, cell, SEED_RUN, 0, capsys)
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
    rc, res = _run(tiny_root, cell, SEED_TRACED, 1, capsys)
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


# the store entries the scheduler binds through: the waves' bulk write,
# and the classic round's Bindings (the loop's fallback)
BIND_ENTRIES = ("bind_pods_bulk", "bind_many")


def _in_window(fault):
    """A stream cell's fault, planted once its warm-up is over, so the
    window's path is the one broken: a warm-up drains until every pod
    is placed, which a fault that loses pods can keep from ever
    happening."""
    def plant(mp):
        import drivers
        warm = drivers._warm_stream

        def warm_then_break(*a, **kw):
            warm(*a, **kw)
            fault(mp)
        mp.setattr(drivers, "_warm_stream", warm_then_break)
    plant.__name__ = fault.__name__
    return plant


@_in_window
def _store_drops_binds(mp):
    """Every bind acknowledged and dropped, whichever entry it takes."""
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite
    for entry in BIND_ENTRIES:
        mp.setattr(ApiServerLite, entry, lambda self, xs: [None] * len(xs))


@_in_window
def _half_batch(mp):
    from kubernetes_tpu.engine.scheduler_engine import SchedulingEngine
    orig = SchedulingEngine.harvest_waves

    def half(self, handle):
        res = orig(self, handle)
        res.bound = res.bound[::2]
        return res
    mp.setattr(SchedulingEngine, "harvest_waves", half)


@_in_window
def _altered_bind(mp):
    """Every fifth bind of each write goes to hollow-node-0, whichever
    entry it takes (its Pods and Bindings both carry `node_name`)."""
    import copy
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite
    for entry in BIND_ENTRIES:
        def alter(self, xs, orig=getattr(ApiServerLite, entry)):
            out = []
            for i, x in enumerate(xs):
                if i % 5 == 0:
                    x = copy.copy(x)
                    x.node_name = "hollow-node-0"
                out.append(x)
            return orig(self, out)
        mp.setattr(ApiServerLite, entry, alter)


def _bound_pod(store, binding):
    """The stored Pod a Binding names, or None."""
    from kubernetes_tpu.server.apiserver_lite import NotFound
    try:
        return store.get("Pod", binding.pod_namespace, binding.pod_name)
    except NotFound:
        return None


def _rebind_one(node: str, tolerating: bool):
    """The first pod of each bind write that has tolerations (or, with
    `tolerating` false, none) is written to `node` instead, whichever
    entry it takes (a Binding's pod is looked up in the store)."""
    def fault(mp):
        import copy
        from kubernetes_tpu.server.apiserver_lite import ApiServerLite
        pod_of = {"bind_pods_bulk": lambda store, pod: pod,
                  "bind_many": _bound_pod}
        for entry in BIND_ENTRIES:
            def alter(self, xs, orig=getattr(ApiServerLite, entry),
                      pod_of=pod_of[entry]):
                out, done = [], False
                for x in xs:
                    p = pod_of(self, x)
                    if not done and p is not None and \
                            bool(p.tolerations) == tolerating:
                        x = copy.copy(x)
                        x.node_name = node
                        done = True
                    out.append(x)
                return orig(self, out)
            mp.setattr(ApiServerLite, entry, alter)
    fault.__name__ = f"rebind_{'tolerating' if tolerating else 'plain'}"
    return _in_window(fault)


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


# the broken paths of each cell, by its driver: every cell of
# BENCHMARK.json gets its driver's, and the older form's affinity kinds
# (a fixture cell) a bind altered
FAULTS = {"stream": (_store_drops_binds, _half_batch, _altered_bind),
          "extender": (_extender_binds_lost, _altered_score)}
BROKEN = [(cell, fault) for cell in CELLS for fault in FAULTS[
    run.resolve(BENCHMARK, cell)["traffic"]["driver"]]]
BROKEN.append(("affinity-burst", _altered_bind))


@pytest.mark.parametrize("cell,fault", BROKEN)
def test_broken_path_is_not_correct(tiny_root, cell, fault, capsys,
                                    monkeypatch):
    fault(monkeypatch)
    rc, res = _run(tiny_root, cell, SEED_FAULT, 0, capsys)
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
    rc, res = _run(tiny_root, cell, SEED_BREACH, 0, capsys)
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
    ctl = control.stream_control(r["cfg"], r["traffic"], SEED_CONTROL,
                                 TINY_SECONDS["stream"], "first_fit",
                                 r["limits"])
    assert not check.verdict(ctl)
    assert ctl["score_gap_max"][0] > ctl["score_gap_max"][1]
    r = run.resolve(run.load_benchmark(tiny_root), "density30-extender",
                    tiny_root)
    rec = drivers.extender(r["cfg"], r["traffic"], SEED_CONTROL,
                           TINY_SECONDS["extender"], False)
    assert check.verdict(check.extender_checks(
        rec, r["cfg"], r["traffic"], SEED_CONTROL, r["limits"]))
    ctl = control.extender_control(rec, r["cfg"], r["traffic"],
                                   SEED_CONTROL, r["limits"])
    assert ctl["score_mismatch_nodes"][0] > 0
