"""Repo hygiene — the cmd/importverifier + cmd/clicheck analog.

The reference ships small verifier binaries run in CI (importverifier:
no forbidden import edges; clicheck: every CLI command documented).
Equivalents here:
- every module under kubernetes_tpu imports cleanly (dead imports and
  circular-import regressions fail fast, not at first use in prod);
- no module opens or reads the read-only reference tree at runtime
  (file:line strings in docstrings are parity citations, not code);
- every ktctl cmd_* verb is reachable through run()'s dispatch;
- the wire KIND_REGISTRY and the apiserver KIND_INFO agree on the kinds
  both layers must serve;
- one helper (utils/compile_cache.py) places JAX's compilation cache.
"""

import importlib
import pathlib
import pkgutil

import kubernetes_tpu

ROOT = pathlib.Path(kubernetes_tpu.__file__).parent


def test_every_module_imports():
    failures = []
    for mod in pkgutil.walk_packages(kubernetes_tpu.__path__,
                                     prefix="kubernetes_tpu."):
        if mod.name.endswith("__main__"):
            continue
        try:
            importlib.import_module(mod.name)
        except Exception as e:  # noqa: BLE001 - collecting all failures
            failures.append(f"{mod.name}: {type(e).__name__}: {e}")
    assert not failures, failures


def test_no_runtime_reads_of_the_reference_tree():
    offenders = []
    for path in ROOT.rglob("*.py"):
        for i, line in enumerate(path.read_text().splitlines(), 1):
            stripped = line.strip()
            if stripped.startswith("#"):
                continue
            if "/root/reference" in stripped:
                offenders.append(f"{path}:{i}: {stripped[:80]}")
    assert not offenders, offenders


def test_ktctl_verbs_dispatchable():
    import io

    from kubernetes_tpu.cli.ktctl import Ktctl
    from kubernetes_tpu.server.apiserver import ApiServer

    kt = Ktctl(ApiServer(), out=io.StringIO())
    verbs = [m[len("cmd_"):].replace("_", "-") for m in dir(kt)
             if m.startswith("cmd_")]
    assert len(verbs) >= 20
    for verb in verbs:
        assert getattr(kt, "cmd_" + verb.replace("-", "_"), None) \
            is not None


def test_wire_registry_covers_served_kinds():
    from kubernetes_tpu.api.wire import KIND_REGISTRY
    from kubernetes_tpu.server.apiserver import KIND_INFO

    # kinds the apiserver serves but the wire codec cannot carry would
    # break the REST facade on first touch
    missing = [k for k in KIND_INFO if k not in KIND_REGISTRY]
    assert not missing, missing


def test_one_helper_places_the_compile_cache(monkeypatch, tmp_path):
    """Only utils/compile_cache.py sets the cache directory; it follows
    $JAX_COMPILATION_CACHE_DIR when set, else the checkout's .jax_cache."""
    import jax

    from kubernetes_tpu.utils import compile_cache

    repo = ROOT.parent
    setters = [
        str(path.relative_to(repo))
        for path in [*ROOT.rglob("*.py"), *repo.glob("*.py")]
        if path.name != "compile_cache.py"
        and ("jax_compilation_cache_dir" in path.read_text()
             or "JAX_COMPILATION_CACHE_DIR" in path.read_text())]
    assert not setters, setters
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == str(
            repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
