"""graftlint: static + runtime hazard analysis for the JAX hot path.

PRs 2 and 3 each fixed a REAL data race of the identical class — the CPU
backend zero-copies aligned numpy uploads, so an in-place write to a
buffer a still-executing async wave reads corrupts placements silently
(`engine/scheduler_engine.py` `_nodes_on_device` / `committed_nodes`).
Both PRs also fought recompile storms and hidden device→host syncs by
hand. Those hazard classes are STRUCTURAL here: the whole design keeps
findNodesThatFit/PrioritizeNodes on-device as one fused async dispatch,
so host buffers alias device reads by default and every host touch of a
device value is a pipeline stall. Borg/Omega-lineage systems survive at
scale because invariants are checked by tooling, not reviewer vigilance
(PAPERS.md: Omega, Firmament) — this package is that tooling.

Two halves:

- `lint` + `rules/`: an AST rules engine over the package. Typed
  findings GL001–GL005 (aliasing upload, host-sync in hot path,
  recompile hazard, tracer leak, snapshot generation discipline), with
  `# graftlint:` pragmas for blessed sites and a JSON baseline for
  everything else. CLI: `python -m kubernetes_tpu.analysis <paths>`.
- `sanitize`: a runtime sanitizer. Under GRAFT_SANITIZE=1 the device-
  upload helpers freeze zero-copy sources (ndarray writeable=False) and
  assert copy seams really copied, so an aliasing violation crashes
  loudly at test time instead of corrupting a blind wave.

tests/test_graftlint.py pins the clean-tree gate (tier-1) and per-rule
fixtures, so no tree with unsuppressed hazards passes.
"""

from kubernetes_tpu.analysis.lint import (  # noqa: F401
    Finding,
    lint_gate,
    load_baseline,
    run_paths,
    write_baseline,
)
