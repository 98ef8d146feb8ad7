"""Profiling rig for the headline bench: times each phase of the drain.

Not part of the framework; dev-only.

  python profile_bench.py             # pipelined drain attribution: spans
                                      # per phase + measured device-idle
                                      # fraction (overlap vs sequential)
  PROFILE_CLASSIC=1 python profile_bench.py
                                      # classic synchronous rounds, the
                                      # r06-era per-phase attribution
  PROFILE_EXTENDER=1 python profile_bench.py
                                      # warm extender round attribution:
                                      # where does a /filter+/prioritize
                                      # round spend its time (refresh,
                                      # pairs, encode, kernel, HTTP), from
                                      # the utils.trace.COUNTERS spans the
                                      # fast lane emits
"""
from __future__ import annotations

import os
import time

from bench import build


def profile_extender():
    """Attribute the warm extender round: in-process span times from the
    fast lane (utils/trace.py COUNTERS) vs the HTTP wall clock, over
    result-memo hits (repeat class), kernel re-evals (bind between
    requests), and encode misses (fresh class per request)."""
    import json
    import http.client

    from bench import _build_extender
    from kubernetes_tpu.api import serde
    from kubernetes_tpu.api.types import make_pod
    from kubernetes_tpu.utils.trace import COUNTERS

    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    rounds = int(os.environ.get("PROFILE_ROUNDS", 50))
    backend, srv = _build_extender(n_nodes)
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)

    def post(path, obj):
        body = json.dumps(obj)
        conn.request("POST", f"/scheduler/{path}", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return json.loads(resp.read())

    def run(label, make, bind_between):
        COUNTERS.reset()
        t0 = time.perf_counter()
        for i in range(rounds):
            pod = make(i)
            enc = serde.encode_pod(pod)
            post("filter", {"Pod": enc, "NodeNames": None, "Nodes": None})
            post("prioritize", {"Pod": enc, "NodeNames": None,
                                "Nodes": None})
            if bind_between:
                backend.bind(pod.name, pod.namespace, pod.uid,
                             backend.engine.snapshot.node_names[i % n_nodes])
        wall = time.perf_counter() - t0
        print(f"\n{label}: {rounds} rounds, "
              f"{wall / rounds * 1e3:.3f} ms/round wall (HTTP incl.)")
        for name, (count, secs) in sorted(COUNTERS.snapshot().items()):
            per = secs / rounds * 1e3
            print(f"    {name:32s} x{count:<6d} {secs * 1e3:8.1f}ms total"
                  f"  {per:7.3f} ms/round")

    run("steady (repeat class, result-memo hits)",
        lambda i: make_pod(f"steady-{i}", cpu=100, memory=256 << 20),
        bind_between=False)
    run("scheduleOne (bind between rounds -> kernel re-eval)",
        lambda i: make_pod(f"so-{i}", cpu=100, memory=256 << 20),
        bind_between=True)
    run("fresh class per round (encode misses)",
        lambda i: make_pod(f"fresh-{i}", cpu=100 + i, memory=256 << 20),
        bind_between=False)
    conn.close()
    srv.stop()


def profile_pipeline():
    """Attribute the PIPELINED drain (ISSUE 2): per-phase wall from the
    engine's spans + scheduler wrappers, then the measured device-idle
    story — sequential mode exposes raw device time (pipeline.device_sync:
    no host work runs inside that window), overlapped mode exposes the
    residual un-hidden wait (pipeline.device_block), and hidden fraction =
    1 - residual/raw."""
    import gc
    import time as _time

    from kubernetes_tpu.utils.trace import COUNTERS

    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    n_pods = int(os.environ.get("BENCH_PODS", 30000))
    profile = os.environ.get("BENCH_PROFILE", "density")
    api, sched = build(n_nodes, n_pods, profile)
    sched.run_until_drained()  # warm compile

    def run(overlap):
        api, sched = build(n_nodes, n_pods, profile)
        phases = {}

        def timed(name, fn):
            def wrap(*a, **k):
                t0 = _time.perf_counter()
                r = fn(*a, **k)
                phases[name] = phases.get(name, 0.0) \
                    + _time.perf_counter() - t0
                return r
            return wrap

        sched.sync = timed("sync(columnar)", sched.sync)
        sched.queue.pop_batch = timed("pop_batch", sched.queue.pop_batch)
        sched.api.bind_pods_bulk = timed("bind_bulk",
                                         sched.api.bind_pods_bulk)
        sched.cache.finish_bindings_bulk = timed(
            "finish_bulk", sched.cache.finish_bindings_bulk)
        COUNTERS.reset()
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            t0 = _time.perf_counter()
            totals = sched.run_until_drained(overlap=overlap)
            elapsed = _time.perf_counter() - t0
        finally:
            gc.enable()
            gc.unfreeze()
        for name, (_c, secs) in COUNTERS.snapshot().items():
            if name.startswith("pipeline."):
                phases["  " + name] = secs
        return elapsed, totals, phases

    seq_device = []
    for trial in range(3):
        elapsed, totals, phases = run(overlap=True)
        print(f"overlap trial {trial}: elapsed={elapsed:.3f}s "
              f"bound={totals['bound']} "
              f"fence_requeued={totals['fence_requeued']}")
        for k, v in sorted(phases.items(), key=lambda kv: -kv[1]):
            print(f"    {k:28s} {v * 1e3:7.1f}ms")
        residual = phases.get("  pipeline.device_block", 0.0)
        print(f"    {'(residual device wait)':28s} {residual * 1e3:7.1f}ms")
    for trial in range(2):
        elapsed, totals, phases = run(overlap=False)
        dev = phases.get("  pipeline.device_sync", 0.0)
        seq_device.append((elapsed, dev))
        print(f"sequential trial {trial}: elapsed={elapsed:.3f}s raw "
              f"device={dev * 1e3:.0f}ms "
              f"(idle-if-serial={dev / elapsed * 100:.0f}% of wall)")
    if seq_device:
        el, dev = min(seq_device)
        print(f"device-idle story: sequential wall {el:.3f}s carries "
              f"{dev * 1e3:.0f}ms of exposed device wait; the overlapped "
              f"runs above show the residual (pipeline.device_block) the "
              f"pipeline failed to hide — hidden fraction = "
              f"1 - residual/raw.")


def main():
    from kubernetes_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("PROFILE_EXTENDER") == "1":
        profile_extender()
        return
    if os.environ.get("PROFILE_CLASSIC") != "1":
        profile_pipeline()
        return
    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    n_pods = int(os.environ.get("BENCH_PODS", 30000))
    profile = os.environ.get("BENCH_PROFILE", "density")

    # warmup (compile) run
    api, sched = build(n_nodes, n_pods, profile)
    sched.run_until_drained(pipeline=False)

    for trial in range(3):
        api, sched = build(n_nodes, n_pods, profile)
        phases = {}

        def timed(name, fn):
            def wrap(*a, **k):
                t0 = time.perf_counter()
                r = fn(*a, **k)
                phases[name] = phases.get(name, 0.0) + time.perf_counter() - t0
                return r
            return wrap

        import kubernetes_tpu.engine.scheduler_engine as SE
        import kubernetes_tpu.engine.waves as W
        import kubernetes_tpu.state.classes as CL
        from kubernetes_tpu.ops import affinity as AF

        eng = sched.engine
        sched.sync = timed("sync", sched.sync)
        sched.queue.pop_batch = timed("pop_batch", sched.queue.pop_batch)
        eng.schedule = timed("engine.schedule", eng.schedule)
        sched.api.bind_many = timed("bind_many", sched.api.bind_many)
        sched.cache.finish_bindings_bulk = timed("finish_bulk",
                                                 sched.cache.finish_bindings_bulk)
        eng.snapshot.refresh = timed("  snapshot.refresh", eng.snapshot.refresh)
        eng._nodes_on_device = timed("  nodes_on_device", eng._nodes_on_device)
        eng._run_wave = timed("  run_wave(device)", eng._run_wave)
        sched.cache.assume_pods_bulk = timed("  assume_bulk",
                                             sched.cache.assume_pods_bulk)
        orig_cb = CL.ClassBatch
        class TimedCB(orig_cb):
            def __init__(self, *a, **k):
                t0 = time.perf_counter()
                super().__init__(*a, **k)
                phases["  ClassBatch"] = phases.get("  ClassBatch", 0.0) \
                    + time.perf_counter() - t0
        SE.ClassBatch = TimedCB
        orig_ad = AF.AffinityData
        class TimedAD(orig_ad):
            def __init__(self, *a, **k):
                t0 = time.perf_counter()
                super().__init__(*a, **k)
                phases["  AffinityData"] = phases.get("  AffinityData", 0.0) \
                    + time.perf_counter() - t0
        AF.AffinityData = TimedAD

        import gc
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            t0 = time.perf_counter()
            totals = sched.run_until_drained(pipeline=False)
            elapsed = time.perf_counter() - t0
        finally:
            gc.enable()
            gc.unfreeze()
            SE.ClassBatch = orig_cb
            AF.AffinityData = orig_ad
        print(f"trial {trial}: elapsed={elapsed:.3f}s bound={totals['bound']}")
        top = phases.pop("engine.schedule", 0.0)
        inner = sum(v for k, v in phases.items() if k.startswith("  "))
        outer = sum(v for k, v in phases.items() if not k.startswith("  "))
        for k, v in sorted(phases.items(), key=lambda kv: -kv[1]):
            print(f"    {k:24s} {v*1e3:7.1f}ms")
        print(f"    {'schedule other':24s} {(top-inner)*1e3:7.1f}ms")
        print(f"    {'(unaccounted)':24s} {(elapsed-outer-top)*1e3:7.1f}ms")


if __name__ == "__main__":
    main()
