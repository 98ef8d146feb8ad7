"""Native layer: C++ hostops kernels, the pause binary, the make build.

The native seam of SURVEY §2 ("C++ host-side tensor snapshot encoder" +
the pause.c equivalent, reference build/pause/pause.c). Every kernel must
be bit-identical to its pure-Python fallback; the toolchain is baked into
the image, so the build paths are exercised for real here.
"""

from __future__ import annotations

import os
import random
import shutil
import signal
import subprocess
import time

import numpy as np
import pytest

from kubernetes_tpu import native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HAVE_GXX = shutil.which("g++") is not None


@pytest.mark.skipif(not HAVE_GXX, reason="no g++ in image")
def test_hostops_builds_and_loads():
    assert native.available(), "hostops must build on demand with g++"


def _python_only(monkeypatch):
    """Force the fallback path regardless of the loaded library."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)


@pytest.mark.skipif(not HAVE_GXX, reason="no g++ in image")
def test_port_bitmaps_native_matches_python(monkeypatch):
    rng = random.Random(7)
    pairs = np.array([[rng.randrange(0, 64),
                       rng.randrange(-5, 70000)]  # incl. out-of-range
                      for _ in range(500)], dtype=np.int64)
    a = np.zeros((64, 2048), dtype=np.uint32)
    native.fill_port_bitmaps(pairs, a)
    b = np.zeros((64, 2048), dtype=np.uint32)
    with pytest.MonkeyPatch.context() as mp:
        _python_only(mp)
        native.fill_port_bitmaps(pairs, b)
    np.testing.assert_array_equal(a, b)
    assert a.any()


@pytest.mark.skipif(not HAVE_GXX, reason="no g++ in image")
def test_multi_hot_native_matches_python(monkeypatch):
    rng = random.Random(11)
    pairs = np.array([[rng.randrange(-2, 40), rng.randrange(-2, 70)]
                      for _ in range(400)], dtype=np.int64)
    a = np.zeros((32, 64), dtype=np.int8)
    native.fill_multi_hot(pairs, a)
    b = np.zeros((32, 64), dtype=np.int8)
    with pytest.MonkeyPatch.context() as mp:
        _python_only(mp)
        native.fill_multi_hot(pairs, b)
    np.testing.assert_array_equal(a, b)
    assert a.any()


@pytest.mark.skipif(not HAVE_GXX, reason="no g++ in image")
def test_fnv1a64_native_matches_python():
    for data in (b"", b"x", b"kubernetes-tpu", bytes(range(256)) * 3):
        got = native.fnv1a64(data)
        with pytest.MonkeyPatch.context() as mp:
            _python_only(mp)
            want = native.fnv1a64(data)
        assert got == want


def test_snapshot_label_rebuild_uses_batch_scatter():
    """The wiring point: finalize_labels' full-matrix rebuild goes through
    fill_multi_hot and stays correct (vs the logical per-row content)."""
    from kubernetes_tpu.api.types import make_node, make_pod
    from kubernetes_tpu.state.node_info import node_info_map
    from kubernetes_tpu.state.snapshot import ClusterSnapshot, PodBatch

    nodes = [make_node(f"n{i}", labels={"zone": f"z{i % 3}",
                                        "disk": "ssd" if i % 2 else "hdd"})
             for i in range(16)]
    snap = ClusterSnapshot()
    snap.refresh(node_info_map(nodes, []))
    # grow the demand-driven vocab -> full rebuild through the batch scatter
    pod = make_pod("p", node_selector={"zone": "z1", "disk": "ssd"})
    PodBatch([pod], snap)
    # every INTERNED pair's column carries exactly its nodes' bits (the
    # vocab is selector-demand-driven; un-referenced labels have no column)
    for key, val in (("zone", "z1"), ("disk", "ssd")):
        col = snap.label_vocab.get(key, val)
        assert col >= 0
        for n in nodes:
            row = snap.node_index[n.name]  # rows are sorted-name order
            want = 1 if n.labels.get(key) == val else 0
            assert snap.labels[row, col] == want, (n.name, key, val)


# ------------------------------------------------------------------ pause


@pytest.mark.skipif(not HAVE_GXX, reason="no g++ in image")
def test_pause_builds_and_terminates_cleanly(tmp_path):
    binary = tmp_path / "pause"
    subprocess.run(
        ["g++", "-O2", "-std=c++17", "-o", str(binary),
         os.path.join(ROOT, "build", "pause", "pause.cc")],
        check=True, capture_output=True, timeout=120)
    proc = subprocess.Popen([str(binary)], stderr=subprocess.PIPE)
    try:
        time.sleep(0.2)
        assert proc.poll() is None  # pausing, not exiting
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=10) == 0  # clean shutdown on TERM
        assert b"signal" in proc.stderr.read()
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.skipif(not HAVE_GXX or shutil.which("make") is None,
                    reason="no toolchain")
def test_make_builds_everything(tmp_path):
    env = dict(os.environ)
    subprocess.run(["make", "-C", os.path.join(ROOT, "build"), "clean"],
                   check=True, capture_output=True, env=env, timeout=120)
    subprocess.run(["make", "-C", os.path.join(ROOT, "build"), "all"],
                   check=True, capture_output=True, env=env, timeout=300)
    assert os.path.exists(os.path.join(ROOT, "build", "bin", "pause"))
    assert os.path.exists(native.so_path())


def test_library_name_follows_source_content(tmp_path, monkeypatch):
    """A library built from other source (a stale file left on disk) must
    never be the one loaded: the name is keyed on the source's bytes."""
    import hashlib
    src = tmp_path / "hostops.cc"
    src.write_bytes(b"int a;")
    monkeypatch.setattr(native, "_SRC", str(src))
    first = native.so_path()
    want = hashlib.sha256(b"int a;").hexdigest()[:16]
    assert os.path.basename(first) == f"libhostops-{want}.so"
    src.write_bytes(b"int b;")
    assert native.so_path() != first
