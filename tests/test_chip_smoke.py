"""chip_smoke.py off the chip: it must refuse to run without a TPU, and its
phases must pass at a small size on the CPU backend (Pallas kernels in
interpret mode), so the script the driver runs on the chip cannot rot."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from kubernetes_tpu.ops import pallas_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "script_alone"])
def test_refuses_without_a_tpu(tmp_path, alone):
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if alone:
        cwd = str(tmp_path)
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU found" in proc.stderr


@pytest.fixture()
def interpret_kernels(monkeypatch):
    """The CPU backend runs Pallas only in interpret mode."""
    for name in ("capacity_fits_pallas", "incidence_matmul_pallas"):
        fn = getattr(pallas_kernels, name)
        monkeypatch.setattr(
            pallas_kernels, name,
            lambda *a, _fn=fn, **k: _fn(*a, **{**k, "interpret": True}))


def test_one_chip_phases_pass_at_small_size(interpret_kernels, capsys):
    chip_smoke.one_chip(n_nodes=64, n_pods=320, n_requests=12)
    out = capsys.readouterr().out
    assert "density: bound 320/320" in out
    assert "mixed_affinity: bound 320/320" in out
    assert "extender pass 2: 12/12 pods agree" in out


def test_mesh_phase_matches_one_device(capsys):
    chip_smoke.four_chips(n_nodes=64, n_pods=320, n_chips=4)
    assert "320/320 identical" in capsys.readouterr().out


def test_affinity_audit_catches_a_breach():
    """The placement audit is independent of the engine: two pods of one
    'one replica per host' app on one node must be reported."""
    from kubernetes_tpu.models.hollow import hollow_nodes, mixed_affinity_pods
    nodes = hollow_nodes(4)
    pods = mixed_affinity_pods(100)
    iso = [p for p in pods if p.labels["app"] == "iso-0"
           and p.affinity is not None][:2]
    for i, p in enumerate(iso):
        p.node_name = nodes[i].name
    assert chip_smoke.affinity_breaches(nodes, iso) == []
    iso[1].node_name = nodes[0].name
    assert len(chip_smoke.affinity_breaches(nodes, iso)) == 2
