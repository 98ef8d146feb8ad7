"""The scheduler micro-benchmark matrix — scheduler_bench_test.go parity.

Reference: test/integration/scheduler_perf/scheduler_bench_test.go:32-52
runs BenchmarkScheduling{100,1000}Nodes{0,1000}Pods — measure scheduling
`measured` fresh pods onto a cluster of N nodes that already carries P
scheduled pods, reporting per-pod cost (the Go bench's ns/op).

Prints one JSON line per cell:
  {"cell": "100Nodes/0Pods", "nodes": 100, "preexisting": 0,
   "measured": 1000, "s_per_pod": ..., "pods_per_s": ...}
plus a trailing summary line with the full matrix, so the driver's
one-line-JSON readers and humans both get what they need.

Env knobs: MATRIX_CELLS="100:0,100:1000,1000:0,1000:1000" (nodes:pre),
MATRIX_MEASURED (default 1000, the upstream bench's fixed measurement
batch).
"""

from __future__ import annotations

import json
import os
import sys
import time



def run_cell(n_nodes: int, n_pre: int, n_measured: int):
    """setupScheduler + the measured loop of benchmarkScheduling
    (scheduler_bench_test.go:57-95): preexisting pods are scheduled first
    and excluded from timing; the clock runs over the measured batch
    create -> all bound."""
    from kubernetes_tpu.engine.scheduler import Scheduler
    from kubernetes_tpu.models.hollow import PROFILES, hollow_nodes, \
        load_cluster
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite

    api = ApiServerLite(max_log=max(200_000,
                                    3 * (n_nodes + n_pre + n_measured)))
    load_cluster(api, hollow_nodes(n_nodes), [])
    sched = Scheduler(api, record_events=False)
    sched.start()
    if n_pre:
        for p in PROFILES["density"](n_pre):
            api.create("Pod", p)
        totals = sched.run_until_drained()
        assert totals["bound"] == n_pre, totals
    measured = PROFILES["density"](n_measured)
    for p in measured:
        p.name = "measured-" + p.name
        api.create("Pod", p)
    t0 = time.monotonic()
    totals = sched.run_until_drained()
    elapsed = time.monotonic() - t0
    assert totals["bound"] == n_measured, totals
    return elapsed


def main() -> int:
    from kubernetes_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cells = os.environ.get("MATRIX_CELLS",
                           "100:0,100:1000,1000:0,1000:1000")
    n_measured = int(os.environ.get("MATRIX_MEASURED", "1000"))
    matrix = []
    for spec in cells.split(","):
        n_nodes, n_pre = (int(x) for x in spec.strip().split(":"))
        # warmup pass compiles the kernels for this cell's exact shape
        # bucket — a smaller warmup batch lands in a different bucket and
        # the measured run pays the compile (observed: 68 vs 3700 pods/s)
        run_cell(n_nodes, n_pre, n_measured)
        elapsed = run_cell(n_nodes, n_pre, n_measured)
        cell = {
            "cell": f"{n_nodes}Nodes/{n_pre}Pods",
            "nodes": n_nodes,
            "preexisting": n_pre,
            "measured": n_measured,
            "s_per_pod": round(elapsed / n_measured, 9),
            "pods_per_s": round(n_measured / elapsed, 1),
        }
        matrix.append(cell)
        print(json.dumps(cell), flush=True)
    print(json.dumps({"metric": "scheduler micro-bench matrix "
                                "(scheduler_bench_test.go:32-52 shape)",
                      "unit": "s/pod", "matrix": matrix}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
