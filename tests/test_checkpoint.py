"""Node-local checkpoint store (dockershim checkpoint analog).

Reference: pkg/kubelet/dockershim/checkpoint_store.go (FileStore atomic
writes, key validation, idempotent delete) + docker_checkpoint.go
(versioned, checksummed sandbox records) + the e2e
dockershim_checkpoint_test.go shape: state written before a kubelet
restart is visible after it.
"""

import os

import pytest

from kubernetes_tpu.api.types import Probe, make_node, make_pod
from kubernetes_tpu.nodes.checkpoint import (
    CorruptCheckpointError,
    FileStore,
    MemStore,
    PodSandboxCheckpointer,
    validate_key,
)
from kubernetes_tpu.nodes.kubelet import HollowKubelet
from kubernetes_tpu.server.apiserver_lite import ApiServerLite

Mi = 1 << 20
Gi = 1 << 30


# ---------------------------------------------------------------- FileStore


def test_filestore_roundtrip_and_idempotent_delete(tmp_path):
    st = FileStore(str(tmp_path / "ckpt"))
    st.write("sandbox-a", b"one")
    st.write("sandbox-b", b"two")
    assert st.read("sandbox-a") == b"one"
    assert st.list() == ["sandbox-a", "sandbox-b"]
    st.write("sandbox-a", b"three")  # overwrite is atomic replace
    assert st.read("sandbox-a") == b"three"
    st.delete("sandbox-a")
    st.delete("sandbox-a")  # missing key is NOT an error
    with pytest.raises(KeyError):
        st.read("sandbox-a")
    assert st.list() == ["sandbox-b"]


def test_key_validation_blocks_traversal(tmp_path):
    st = FileStore(str(tmp_path / "ckpt"))
    for bad in ("", "..", "a/b", "../evil", "/abs", ".hidden" * 50):
        with pytest.raises(ValueError):
            validate_key(bad)
        with pytest.raises(ValueError):
            st.write(bad, b"x")


def test_checkpointer_checksum_rejects_corruption(tmp_path):
    st = FileStore(str(tmp_path / "ckpt"))
    ck = PodSandboxCheckpointer(st)
    ck.checkpoint("default/web", {"restarts": 3, "node": "n1"})
    assert ck.restore("default/web") == {"restarts": 3, "node": "n1"}
    assert ck.pod_keys() == ["default/web"]
    # flip bytes on disk: restore must refuse, not return garbage
    path = os.path.join(st.directory, "default_web")
    with open(path, "r+b") as f:
        data = f.read().replace(b'"restarts": 3', b'"restarts": 9')
        f.seek(0)
        f.write(data)
        f.truncate()
    with pytest.raises(CorruptCheckpointError):
        ck.restore("default/web")


def test_memstore_matches_filestore_contract():
    st = MemStore()
    st.write("k", b"v")
    assert st.read("k") == b"v"
    st.delete("k")
    st.delete("k")
    with pytest.raises(KeyError):
        st.read("k")


# ------------------------------------------------- kubelet restart recovery


def _live_pod(name):
    p = make_pod(name, cpu=50, memory=Mi)
    p.containers[0].liveness_probe = Probe(
        initial_delay_s=0, period_s=1, failure_threshold=1)
    p.annotations["bench/liveness-fail-at"] = "5"
    return p


def test_kubelet_restart_resumes_restart_counters(tmp_path):
    t = [1000.0]
    api = ApiServerLite()
    node = make_node("n1", cpu=4000, memory=8 * Gi)
    api.create("Node", node)
    ck = PodSandboxCheckpointer(FileStore(str(tmp_path / "ckpt")))
    kubelet = HollowKubelet(api, node, now=lambda: t[0], checkpointer=ck)
    pod = _live_pod("web")
    pod.node_name = "n1"
    api.create("Pod", pod)
    kubelet.handle_pod(pod)
    kubelet.workers.drain()
    # run past the liveness-failure point a few times -> restarts accrue
    for _ in range(3):
        t[0] += 6.0
        kubelet.step()
    restarts = kubelet._restarts.get(pod.key(), 0)
    assert restarts >= 2
    # kubelet process dies; a NEW kubelet on the same node + checkpoint
    # dir resumes the counter instead of resetting to zero
    kubelet2 = HollowKubelet(api, node, now=lambda: t[0], checkpointer=ck)
    kubelet2.handle_pod(api.get("Pod", "default", "web"))
    kubelet2.workers.drain()
    assert kubelet2._restarts.get(pod.key()) == restarts
    # pod deletion cleans the checkpoint up
    kubelet2.forget_pod(pod)
    kubelet2.workers.drain()
    assert ck.pod_keys() == []


def test_corrupt_checkpoint_dropped_on_restart(tmp_path):
    api = ApiServerLite()
    node = make_node("n1", cpu=4000, memory=8 * Gi)
    api.create("Node", node)
    store = FileStore(str(tmp_path / "ckpt"))
    store.write("default_web", b"{not json")
    ck = PodSandboxCheckpointer(store)
    kubelet = HollowKubelet(api, node, checkpointer=ck)
    # the invalid checkpoint was removed, kubelet starts clean
    assert store.list() == []
    assert kubelet._restored == {}


def test_restore_all_survives_any_blob_shape(tmp_path):
    store = FileStore(str(tmp_path / "ckpt"))
    store.write("arr", b"[1, 2]")          # valid JSON, wrong shape
    store.write("num", b"42")              # valid JSON, wrong shape
    store.write("badpod", b'{"pod": 7, "version": "v1", "record": {}}')
    ck = PodSandboxCheckpointer(store)
    ck.checkpoint("default/ok", {"restarts": 1})
    assert ck.restore_all() == {"default/ok": {"restarts": 1}}
    # all malformed blobs pruned, the valid one kept
    assert store.list() == ["default_ok"]


def test_long_pod_keys_checkpoint_safely(tmp_path):
    ck = PodSandboxCheckpointer(FileStore(str(tmp_path / "ckpt")))
    long_key = ("n" * 250) + "/" + ("p" * 250)
    ck.checkpoint(long_key, {"restarts": 5})
    assert ck.restore(long_key) == {"restarts": 5}
    assert ck.restore_all() == {long_key: {"restarts": 5}}
    ck.remove(long_key)
    assert ck.pod_keys() == []


def test_orphaned_checkpoint_gc(tmp_path):
    """A checkpoint for a pod deleted while the kubelet was down is
    removed by the sync-loop sweep, not inherited by a future pod."""
    api = ApiServerLite()
    node = make_node("n1", cpu=4000, memory=8 * Gi)
    api.create("Node", node)
    ck = PodSandboxCheckpointer(FileStore(str(tmp_path / "ckpt")))
    ck.checkpoint("default/ghost", {"restarts": 7, "node": "n1"})
    kubelet = HollowKubelet(api, node, checkpointer=ck)
    assert "default/ghost" in kubelet._restored
    kubelet.step()
    assert kubelet._restored == {}
    assert ck.pod_keys() == []
