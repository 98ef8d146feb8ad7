"""Extender wire-protocol tests: a scheduler-side fake client POSTs
ExtenderArgs JSON (capitalized Go-style keys, like the reference's internal
structs marshal) and asserts on the filter/prioritize/bind results — the
shape of test/integration/scheduler/extender_test.go:71-126 with the roles
flipped (there the extender is fake; here the scheduler is)."""

import http.client
import json

import pytest

from kubernetes_tpu.api import serde
from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.server.extender import ExtenderHTTPServer


class FakeBackend:
    """machine1/2/3-style predicate backend (extender_test.go FakeExtender)."""

    def __init__(self):
        self.bound = []
        self.synced_nodes = []
        self.synced_pods = []

    def filter(self, pod, nodes, node_names):
        cands = node_names if node_names is not None else [n.name for n in nodes]
        passed = [n for n in cands if not n.endswith("1")]
        failed = {n: "ends with 1" for n in cands if n.endswith("1")}
        return passed, failed

    def prioritize(self, pod, nodes, node_names):
        cands = node_names if node_names is not None else [n.name for n in nodes]
        return [(n, 10 if n.endswith("2") else 1) for n in cands]

    def bind(self, pod_name, pod_namespace, pod_uid, node):
        self.bound.append((pod_namespace, pod_name, node))
        return ""

    def sync_nodes(self, nodes):
        self.synced_nodes = nodes

    def sync_pods(self, pods):
        self.synced_pods = pods

    def metrics_text(self):
        return "# fake"


@pytest.fixture()
def server():
    backend = FakeBackend()
    srv = ExtenderHTTPServer(backend, prefix="/scheduler")
    srv.start()
    yield srv, backend
    srv.stop()


def post(port, path, obj):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    body = json.dumps(obj)
    conn.request("POST", path, body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read())
    conn.close()
    return resp.status, data


def get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _args_cache_capable():
    pod = make_pod("p1", cpu=100)
    return {"Pod": serde.encode_pod(pod),
            "NodeNames": ["machine1", "machine2", "machine3"]}


def test_filter_node_cache_capable(server):
    srv, _ = server
    status, out = post(srv.port, "/scheduler/filter", _args_cache_capable())
    assert status == 200
    assert out["NodeNames"] == ["machine2", "machine3"]
    assert out["FailedNodes"] == {"machine1": "ends with 1"}
    assert out["Error"] == ""


def test_filter_with_full_nodes():
    backend = FakeBackend()
    srv = ExtenderHTTPServer(backend)
    srv.start()
    try:
        nodes = [make_node("machine1"), make_node("machine2")]
        args = {"Pod": serde.encode_pod(make_pod("p", cpu=100)),
                "Nodes": {"Items": [serde.encode_node(n) for n in nodes]}}
        status, out = post(srv.port, "/filter", args)
        assert status == 200
        names = [n["metadata"]["name"] for n in out["Nodes"]["Items"]]
        assert names == ["machine2"]
    finally:
        srv.stop()


def test_prioritize(server):
    srv, _ = server
    status, out = post(srv.port, "/scheduler/prioritize", _args_cache_capable())
    assert status == 200
    assert out == [{"Host": "machine1", "Score": 1},
                   {"Host": "machine2", "Score": 10},
                   {"Host": "machine3", "Score": 1}]


def test_bind(server):
    srv, backend = server
    status, out = post(srv.port, "/scheduler/bind", {
        "PodName": "p1", "PodNamespace": "default", "PodUID": "u1",
        "Node": "machine2"})
    assert status == 200
    assert out == {"Error": ""}
    assert backend.bound == [("default", "p1", "machine2")]


def test_lowercase_keys_accepted(server):
    # v1 wire mirror uses lowercase tags (api/v1/types.go) — accept both
    srv, _ = server
    pod = make_pod("p1", cpu=100)
    status, out = post(srv.port, "/scheduler/filter",
                       {"pod": serde.encode_pod(pod),
                        "nodenames": ["machine1", "machine2"]})
    assert status == 200
    assert out["NodeNames"] == ["machine2"]


def test_cache_sync_endpoints(server):
    srv, backend = server
    nodes = [serde.encode_node(make_node("n1")), serde.encode_node(make_node("n2"))]
    status, out = post(srv.port, "/scheduler/cache/nodes", {"items": nodes})
    assert status == 200 and out["synced"] == 2
    assert [n.name for n in backend.synced_nodes] == ["n1", "n2"]
    p = make_pod("bp", cpu=100)
    p.node_name = "n1"
    status, out = post(srv.port, "/scheduler/cache/pods",
                       {"items": [serde.encode_pod(p)]})
    assert status == 200 and out["synced"] == 1
    assert backend.synced_pods[0].node_name == "n1"


def test_healthz_and_metrics(server):
    srv, _ = server
    assert get(srv.port, "/healthz") == (200, b"ok")
    status, body = get(srv.port, "/metrics")
    assert status == 200 and b"fake" in body


def test_malformed_json_yields_in_band_error(server):
    srv, _ = server
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
    conn.request("POST", "/scheduler/filter", "{not json",
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = json.loads(resp.read())
    conn.close()
    assert resp.status == 500
    assert "Error" in data


def test_tpu_backend_sync_pods_removes_deleted_pods():
    from kubernetes_tpu.server.extender import TPUExtenderBackend
    backend = TPUExtenderBackend()
    backend.sync_nodes([make_node("n1")])
    p = make_pod("gone", cpu=500)
    p.node_name = "n1"
    backend.sync_pods([p])
    assert backend.cache.node_infos()["n1"].requested.milli_cpu == 500
    # next full sync omits the pod -> its capacity is released
    backend.sync_pods([])
    assert backend.cache.node_infos()["n1"].requested.milli_cpu == 0
    assert backend._known_pods == {}


def test_tpu_backend_stale_node_labels_not_served_in_args_mode():
    # non-cache-capable: node state ships per request; a label change between
    # requests must be honored (regression: shared-snapshot generation diffing)
    from kubernetes_tpu.server.extender import TPUExtenderBackend
    backend = TPUExtenderBackend()
    pod = make_pod("p", cpu=100, node_selector={"zone": "b"})
    n = make_node("n2", labels={"zone": "b"})
    passed, _ = backend.filter(pod, [n], None)
    assert passed == ["n2"]
    n_changed = make_node("n2", labels={"zone": "c"})
    passed, failed = backend.filter(pod, [n_changed], None)
    assert passed == [] and "n2" in failed


def test_compat_scheduleone_over_http_binds_everything():
    """A stock scheduler's serial scheduleOne against the TPU backend over
    real HTTP (core/extender.go Filter, Prioritize, Bind): /filter with
    every node name (nodeCacheCapable), /prioritize over the survivors,
    /bind on the top score. Every pod of a small stream binds."""
    from kubernetes_tpu.models.hollow import hollow_nodes
    from kubernetes_tpu.server.extender import TPUExtenderBackend

    backend = TPUExtenderBackend()
    nodes = hollow_nodes(200)
    for i, n in enumerate(nodes):
        n.labels["zone"] = f"z{i % 16}"
    backend.sync_nodes(nodes)
    names = [n.name for n in nodes]
    srv = ExtenderHTTPServer(backend, prefix="/scheduler")
    srv.start()
    bound, unschedulable = [], []
    try:
        for i in range(60):
            pod = make_pod(f"compat-{i}", cpu=100, memory=256 << 20)
            enc = serde.encode_pod(pod)
            status, out = post(srv.port, "/scheduler/filter",
                               {"Pod": enc, "NodeNames": names})
            assert status == 200 and not out.get("Error"), out
            passed = out.get("NodeNames") or []
            if not passed:
                unschedulable.append(pod.name)
                continue
            status, scores = post(srv.port, "/scheduler/prioritize",
                                  {"Pod": enc, "NodeNames": passed})
            assert status == 200, scores
            host = max(scores, key=lambda e: e["Score"])["Host"]
            assert host in passed
            status, out = post(srv.port, "/scheduler/bind",
                               {"PodName": pod.name,
                                "PodNamespace": pod.namespace,
                                "PodUID": pod.uid, "Node": host})
            assert status == 200 and not out.get("Error"), out
            bound.append(pod.name)
    finally:
        srv.stop()
    assert len(bound) == 60 and unschedulable == []
