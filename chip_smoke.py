"""Bring-up smoke: the scheduler's main path once, on the chip, at real size.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # the node-axis mesh over four chips

One process, through the entry points a user calls, on the BASELINE north
star (the kubemark 5k-node rig, BASELINE.json configs 2 and 3):

  drain     `Scheduler(api).run_until_drained()` over an `ApiServerLite`
            holding `hollow_nodes(5000)`: a 30,000-pod density drain
            (warm-up, then a fresh cluster) and a 30,000-pod
            mixed_affinity drain. Checked by the store: bound counts, zero
            duplicate binds, an empty cache-vs-store audit, no node over
            its allocatable, and every required (anti-)affinity term held.
  parity    both Pallas kernels (`force=True`) against the jnp reference
            (`force=False`) on the device, at the drains' shapes.
  extender  `TPUExtenderBackend` synced to the same nodes and the
            mixed_affinity drain's pods, served by `ExtenderHTTPServer`;
            plain and affinity pods over real HTTP /filter + /prioritize,
            each verdict checked exactly against `ops/oracle.py`.

With `--chips 4` it runs only the 30k/5k density drain with
`mesh=make_mesh(4)` and the same drain unsharded, and requires the
placements to be identical.

Exits non-zero, with no result line, when JAX's first device is not a TPU
or any check fails. Times printed here are bring-up observations (compile
included where said), not benchmark numbers. The last line of standard
output is the one JSON result object.
"""

from __future__ import annotations

import argparse
import http.client
import json
import sys
import time

N_NODES = 5000
N_PODS = 30_000
N_EXTENDER_PODS = 32


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    """A failed check ends the run: nothing is caught to carry on."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX's first device is on platform "
            f"{devs[0].platform!r}); this smoke runs only on the chip")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips asked for, "
                         f"{len(devs)} found")
    return devs


def kernel_branches() -> dict:
    """The Pallas dispatchers' trace-time record: branch per shape."""
    from kubernetes_tpu.utils.trace import COUNTERS
    return {k: v[0] for k, v in sorted(COUNTERS.snapshot().items())
            if k.startswith("kernel.")}


# ------------------------------------------------------------------ drain


def drain(n_nodes: int, n_pods: int, profile: str, mesh=None):
    """One pipelined drain of a fresh cluster through the Scheduler.
    Returns (api, scheduler, totals, seconds)."""
    from kubernetes_tpu.engine.scheduler import Scheduler
    from kubernetes_tpu.models.hollow import (
        PROFILES,
        hollow_nodes,
        load_cluster,
    )
    from kubernetes_tpu.server.apiserver_lite import ApiServerLite

    api = ApiServerLite(max_log=max(200_000, 3 * (n_nodes + n_pods)))
    load_cluster(api, hollow_nodes(n_nodes), PROFILES[profile](n_pods))
    sched = Scheduler(api, record_events=False, mesh=mesh)
    sched.start()
    t0 = time.perf_counter()
    totals = sched.run_until_drained()
    return api, sched, totals, time.perf_counter() - t0


def placements(api) -> dict:
    return {p.key(): p.node_name for p in api.list("Pod")[0]}


def audit_drain(api, sched, n_pods: int, label: str) -> dict:
    """The store's verdict on a drain; fails the run on any breach."""
    from kubernetes_tpu.parallel.multiproc import audit_duplicate_binds
    from kubernetes_tpu.state.node_info import node_info_map
    from kubernetes_tpu.testing.churn import audit_cache_vs_store

    sched.sync()
    nodes = api.list("Node")[0]
    pods = api.list("Pod")[0]
    bound = [p for p in pods if p.node_name]
    dups = audit_duplicate_binds(api)
    ghost = audit_cache_vs_store(sched, api)
    over = []
    for name, info in node_info_map(nodes, bound).items():
        a, r = info.node.allocatable, info.requested
        if (r.milli_cpu > a.milli_cpu or r.memory > a.memory
                or r.nvidia_gpu > a.nvidia_gpu
                or len(info.pods) > info.node.allowed_pod_number):
            over.append(name)
    broken = affinity_breaches(nodes, bound)
    log(f"{label}: bound {len(bound)}/{n_pods} (unbound "
        f"{n_pods - len(bound)}), duplicate binds {dups}, cache-vs-store "
        f"problems {len(ghost)}, over-committed nodes {len(over)}, "
        f"required-affinity breaches {len(broken)}")
    check(len(pods) == n_pods, f"{label}: store holds {len(pods)} pods")
    check(len(bound) > 0, f"{label}: nothing bound")
    check(dups == 0, f"{label}: {dups} duplicate binds")
    check(not ghost, f"{label}: cache vs store: {ghost[:5]}")
    check(not over, f"{label}: over-committed nodes {over[:5]}")
    check(not broken, f"{label}: affinity breaches {broken[:5]}")
    return {"bound": len(bound), "unbound": n_pods - len(bound)}


def affinity_breaches(nodes, bound) -> list:
    """Every bound pod's REQUIRED pod (anti-)affinity terms, checked
    directly against the final placements (a plain reading of
    predicates.go's inter-pod rules, independent of the engine): an anti
    term's domain holds no other matching pod, an affinity term's domain
    holds another matching pod unless the pod is its term's only match."""
    from collections import Counter

    node_labels = {n.name: n.labels for n in nodes}
    per_term = {}  # (term, namespaces) -> (matches per domain, total)
    out = []
    for pod in bound:
        aff = pod.affinity
        if aff is None:
            continue
        for anti, group in ((False, aff.pod_affinity),
                            (True, aff.pod_anti_affinity)):
            for term in (group.required_terms if group else ()):
                key = term.topology_key
                spaces = frozenset(term.namespaces) or {pod.namespace}
                tk = (id(term), tuple(sorted(spaces)))
                if tk not in per_term:
                    doms = Counter(
                        node_labels[q.node_name].get(key) for q in bound
                        if q.namespace in spaces
                        and term.label_selector.matches(q.labels))
                    per_term[tk] = (doms, sum(doms.values()))
                doms, total = per_term[tk]
                dom = node_labels[pod.node_name].get(key)
                me = int(pod.namespace in spaces
                         and term.label_selector.matches(pod.labels))
                here, others = doms[dom] - me, total - me
                if anti and here:
                    out.append(f"{pod.key()} shares {key}={dom} with "
                               f"{here} matching pods")
                if not anti and others and not here:
                    out.append(f"{pod.key()} has no match in {key}={dom}")
    return out


# ------------------------------------------------------------------ parity


def kernel_parity(sched, n_pods: int) -> None:
    """Pallas (force=True) vs the jnp reference (force=False) on the
    device, at a drain's node arrays: the capacity fit at the wave's class
    shape and at one row per pending pod, against the drained occupancy
    and against a seeded random one (so both verdicts occur), and the
    incidence matmul over the mixed_affinity pods' affinity classes."""
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.models.hollow import mixed_affinity_pods
    from kubernetes_tpu.ops.affinity import (
        AffinityData,
        collect_pod_pairs,
        intern_topology_pairs,
    )
    from kubernetes_tpu.ops.pallas_kernels import (
        precompute_static_fast,
        resources_fit_fast,
    )
    from kubernetes_tpu.ops.predicates import (
        bucket,
        node_arrays,
        pod_arrays_padded,
    )
    from kubernetes_tpu.state.classes import ClassBatch
    from kubernetes_tpu.state.snapshot import ClusterSnapshot

    infos = sched.cache.node_infos()
    snap = ClusterSnapshot()
    snap.refresh(infos)
    pods = mixed_affinity_pods(n_pods, namespace="parity")
    all_pairs, aff_pairs = collect_pod_pairs(infos)
    intern_topology_pairs(snap, pods, aff_pairs)
    batch = ClassBatch(pods, snap)
    nodes = {k: jnp.asarray(v) for k, v in node_arrays(snap).items()}
    cls = pod_arrays_padded(batch.reps_batch, bucket(batch.num_classes + 1))
    per_pod_req = jnp.asarray(np.asarray(cls["req"])[batch.pod_class])
    per_pod_zero = jnp.asarray(np.asarray(cls["zero_req"])[batch.pod_class])
    alloc = np.asarray(nodes["alloc"])
    rng = np.random.default_rng(0)
    loaded = jnp.asarray((alloc * rng.random(alloc.shape)).astype(np.int32))
    for label, req, zero, used in (
            ("class rows, drained", cls["req"], cls["zero_req"],
             nodes["requested"]),
            ("class rows, random load", cls["req"], cls["zero_req"], loaded),
            ("pod rows, drained", per_pod_req, per_pod_zero,
             nodes["requested"]),
            ("pod rows, random load", per_pod_req, per_pod_zero, loaded)):
        args = (req, zero, nodes["alloc"], used)
        got = np.asarray(resources_fit_fast(*args, force=True))
        want = np.asarray(resources_fit_fast(*args, force=False))
        log(f"parity resources_fit {label} {got.shape}: "
            f"{int((got == want).sum())}/{want.size} equal, "
            f"{int(want.sum())} fits")
        check(np.array_equal(got, want), f"resources_fit parity ({label})")
    adata = AffinityData(batch.reps, snap, all_pairs, aff_pairs, (), 1)
    aff = adata.device_arrays()
    got = precompute_static_fast(aff, nodes["labels"], force=True)
    want = precompute_static_fast(aff, nodes["labels"], force=False)
    for k in ("allow_hit", "forbid_hit", "prio_counts"):
        g, w = np.asarray(got[k]), np.asarray(want[k])
        log(f"parity precompute_static {k} {g.shape}: "
            f"{int((g == w).sum())}/{w.size} equal")
        check(np.array_equal(g, w), f"precompute_static parity ({k})")


# ---------------------------------------------------------------- extender


def _post(port: int, path: str, obj) -> object:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", path, json.dumps(obj),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        check(resp.status == 200,
              f"POST {path}: HTTP {resp.status} {body[:200]!r}")
        return json.loads(body)
    finally:
        conn.close()


def extender_requests(n: int) -> list:
    """Plain and affinity pods from the mixed_affinity mix: one-per-host
    anti-affinity (and its symmetric targets), pack-into-one-zone
    affinity, and plain pods."""
    from kubernetes_tpu.models.hollow import density_pods, mixed_affinity_pods
    mix = mixed_affinity_pods(100, namespace="bench")
    picks = [0, 1, 2, 15, 16, 17, 18, 22, 23, 3, 4, 5, 19, 20, 21, 24]
    pods = [mix[i] for i in picks] + density_pods(n, seed=1)
    out = []
    for i, p in enumerate(pods[:n]):
        p.name = f"ext-{i}-{p.name}"
        out.append(p)
    return out


def extender_phase(nodes, bound_pods, n_requests: int) -> dict:
    """Serve the drained cluster and check every verdict against the
    oracle: the fits set exactly, and every score the /prioritize answer
    carries for the fitting nodes. Two passes over the same pods: the
    first sight of an affinity pod's topology pairs takes the exact host
    route while the pairs queue; the periodic cache sync interns them, so
    the second pass must reach the device for every pod."""
    from kubernetes_tpu.api import serde
    from kubernetes_tpu.ops import oracle
    from kubernetes_tpu.ops.oracle_ext import AffinityMeta, SchedulingContext
    from kubernetes_tpu.server.extender import (
        ExtenderHTTPServer,
        TPUExtenderBackend,
    )
    from kubernetes_tpu.state.node_info import node_info_map
    from kubernetes_tpu.utils.trace import COUNTERS

    def device_evals():
        return COUNTERS.count("extender.fused_eval") \
            + COUNTERS.count("extender.fused_eval_batch")

    infos = node_info_map(nodes, bound_pods)
    ctx = SchedulingContext(infos, [], hard_pod_affinity_weight=1)
    names = sorted(infos)
    pods = extender_requests(n_requests)
    backend = TPUExtenderBackend()
    prios = backend.engine.priorities
    want = {}
    for pod in pods:
        meta = AffinityMeta(pod, ctx)
        fit = [nm for nm in names
               if oracle.pod_fits(pod, infos[nm], ctx, meta)]
        score = oracle.prioritize(pod, [infos[nm] for nm in fit], prios,
                                  ctx)
        want[pod.key()] = (fit, dict(zip(fit, score)))
    out = {}
    srv = ExtenderHTTPServer(backend, prefix="/scheduler")
    srv.start()
    try:
        for pass_no in (1, 2):
            backend.sync_nodes(nodes)
            backend.sync_pods(bound_pods)
            dev0, routes0 = device_evals(), backend.eval_cache.oracle_routes
            fit_pairs = 0
            t0 = time.perf_counter()
            for pod in pods:
                wire = serde.encode_pod(pod)
                res = _post(srv.port, "/scheduler/filter",
                            {"Pod": wire, "NodeNames": names})
                check(not res.get("Error"), f"/filter {pod.key()}: {res}")
                passed = res["NodeNames"] or []
                scores = _post(srv.port, "/scheduler/prioritize",
                               {"Pod": wire, "NodeNames": passed})
                want_fit, want_score = want[pod.key()]
                check(passed == want_fit,
                      f"{pod.key()}: /filter passed {len(passed)} nodes, "
                      f"the oracle {len(want_fit)}")
                check({e["Host"]: e["Score"] for e in scores} == want_score,
                      f"{pod.key()}: /prioritize scores differ from the "
                      f"oracle")
                fit_pairs += len(passed)
            wall = time.perf_counter() - t0
            dev = device_evals() - dev0
            routes = backend.eval_cache.oracle_routes - routes0
            log(f"extender pass {pass_no}: {len(pods)}/{len(pods)} pods "
                f"agree with ops/oracle.py on fits and scores ({fit_pairs} "
                f"fitting (pod, node) pairs; {dev} device evaluations, "
                f"{routes} exact host routes); {wall:.3f} s over HTTP")
            out[pass_no] = {"device_evals": dev, "host_routes": routes}
    finally:
        srv.stop()
    check(out[2]["host_routes"] == 0,
          "extender: pods still take the host route after the sync")
    check(out[2]["device_evals"] > 0, "extender: no device evaluation")
    return out


# ------------------------------------------------------------------ phases


def one_chip(n_nodes: int = N_NODES, n_pods: int = N_PODS,
             n_requests: int = N_EXTENDER_PODS) -> None:
    from kubernetes_tpu import native
    from kubernetes_tpu.models.hollow import hollow_nodes

    log(f"native hostops loaded: {native.available()} "
        f"({native.so_path()})")
    for profile in ("density", "mixed_affinity"):
        _api, _s, totals, cold = drain(n_nodes, n_pods, profile)
        log(f"{profile} warm-up drain (compiles included, set-up): "
            f"{cold:.3f} s, totals {totals}")
        api, sched, totals, warm = drain(n_nodes, n_pods, profile)
        log(f"{profile} drain on a fresh cluster: {warm:.3f} s (bring-up "
            f"observation, not a benchmark; compile ~{cold - warm:.3f} s), "
            f"totals {totals}")
        audit_drain(api, sched, n_pods, profile)
        log(f"kernel branches after the {profile} drains: "
            f"{kernel_branches()}")

    t0 = time.perf_counter()
    kernel_parity(sched, n_pods)
    log(f"kernel parity: {time.perf_counter() - t0:.3f} s (compiles "
        f"included)")

    bound = [p for p in api.list("Pod")[0] if p.node_name]
    t0 = time.perf_counter()
    extender_phase(hollow_nodes(n_nodes), bound, n_requests)
    log(f"extender phase: {time.perf_counter() - t0:.3f} s (sync, compiles "
        f"and oracle included)")
    log(f"kernel branches, whole run: {kernel_branches()}")


def four_chips(n_nodes: int = N_NODES, n_pods: int = N_PODS,
               n_chips: int = 4) -> None:
    import jax

    from kubernetes_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_chips)
    log(f"mesh devices: {[str(d) for d in mesh.devices.reshape(-1)]}")
    drain(n_nodes, n_pods, "density", mesh=mesh)  # compiles
    api, sched, totals, t_mesh = drain(n_nodes, n_pods, "density", mesh=mesh)
    log(f"density drain, node axis over {n_chips} chips: {t_mesh:.3f} s "
        f"(bring-up observation), totals {totals}")
    audit_drain(api, sched, n_pods, f"mesh({n_chips})")
    alloc = sched.engine._device_nodes["alloc"]
    spread = sorted(str(s.device) for s in alloc.addressable_shards)
    log(f"node arrays: sharding {alloc.sharding}, shards on {spread}")
    check(len(set(spread)) == n_chips,
          f"node shards sit on {len(set(spread))} devices, not {n_chips}")
    sharded = placements(api)

    drain(n_nodes, n_pods, "density")  # compiles
    api, sched, totals, t_one = drain(n_nodes, n_pods, "density")
    log(f"density drain unsharded on one chip: {t_one:.3f} s, totals "
        f"{totals}")
    audit_drain(api, sched, n_pods, "unsharded")
    single = placements(api)
    differ = sum(1 for k in single if single[k] != sharded.get(k))
    log(f"placements: {len(single) - differ}/{len(single)} identical "
        f"between the {n_chips}-chip mesh and one chip")
    check(differ == 0, f"{differ} placements differ between mesh and one chip")
    for d in jax.devices()[:n_chips]:
        log(f"memory_stats {d}: {d.memory_stats()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the node-axis mesh drain and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)

    from kubernetes_tpu.utils.compile_cache import enable_compile_cache
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    stats = devs[0].memory_stats() or {}
    log(f"peak device memory {stats.get('peak_bytes_in_use')} bytes; "
        f"whole run {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
