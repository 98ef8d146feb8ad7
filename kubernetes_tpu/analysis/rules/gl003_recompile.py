"""GL003 — recompile hazards.

XLA specializes a jitted callable per (shape, dtype, static-arg) signature;
minting fresh signatures in a loop is a compile per iteration (the
arrival-stream ragged-pop storm wave_pad_floor
exists to kill: pops of 345, 589, 100 ... each compiled their own wave
shape). Two provable shapes fire:

1. `jax.jit(...)` (or `functools.partial(jax.jit, ...)`) constructed
   inside a function or loop body — every evaluation builds a NEW jitted
   callable with an empty compile cache. The blessed idiom is a
   module-level wrap (`_fused_eval_jit = jax.jit(...)`) or decorator.
2. a known-jitted callable invoked inside a for/while loop with an
   argument sliced to a DATA-DEPENDENT bound (`xs[:n]`, `xs[i:j]` with
   non-constant bounds) — each distinct length is a fresh compile. The
   blessed idiom pads to a power-of-2 bucket (`predicates.bucket`,
   `wave_pad_floor`) so the shape set is bounded at log2(max).
"""

from __future__ import annotations

import ast
from typing import List

from kubernetes_tpu.analysis.rules.base import (
    FileContext,
    Finding,
    ProjectIndex,
    _is_jit_expr,
    dotted,
    functions_of,
    last_component,
)

RULE = "GL003"


def _ragged_slice(expr: ast.AST) -> bool:
    """A subscript whose slice has a non-constant bound anywhere in expr."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Subscript) and isinstance(node.slice,
                                                          ast.Slice):
            for bound in (node.slice.lower, node.slice.upper):
                if bound is not None and not isinstance(bound, ast.Constant):
                    return True
    return False


def check(ctx: FileContext, index: ProjectIndex) -> List[Finding]:
    findings: List[Finding] = []

    # shape 1: jit construction inside a function/loop body
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _is_jit_expr(node):
            fn = ctx.enclosing_function(node)
            if fn is not None and any(
                    node is d or node in set(ast.walk(d))
                    for d in fn.decorator_list):
                # @jax.jit / @partial(jax.jit, ...) decorator: evaluated
                # once at DEF time — blessed for top-level defs (the AST
                # parents the decorator under the decorated function). A
                # decorated def NESTED in a function still re-jits per
                # enclosing call, so only hoist one level and re-judge.
                fn = ctx.enclosing_function(fn)
                if fn is None:
                    continue
            in_loop = any(isinstance(a, (ast.For, ast.While))
                          for a in ctx.ancestors(node))
            if fn is None and not in_loop:
                continue  # module-level wrap: the blessed idiom
            where = "a loop body" if in_loop else \
                f"function {ctx.qualname(fn)}"
            findings.append(Finding(
                RULE, ctx.path, node.lineno, node.col_offset,
                f"jax.jit constructed inside {where} — every evaluation "
                "mints a fresh callable with an empty compile cache; wrap "
                "once at module level (the _fused_eval_jit idiom)",
                context=ctx.qualname(fn) if fn is not None else "<module>"))

    # shape 2: jitted call with ragged slice operand inside a loop (one
    # pass over all calls; ancestor check finds the enclosing loop, so a
    # call can never be reported twice)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted(node.func)
        if fname is None or last_component(fname) not in index.jitted_names:
            continue
        if not any(isinstance(a, (ast.For, ast.While))
                   for a in ctx.ancestors(node)):
            continue
        ragged = [a for a in list(node.args)
                  + [k.value for k in node.keywords]
                  if _ragged_slice(a)]
        if ragged:
            efn = ctx.enclosing_function(node)
            findings.append(Finding(
                RULE, ctx.path, node.lineno, node.col_offset,
                f"jitted '{last_component(fname)}' called in a "
                "loop with a data-dependent slice operand — each "
                "distinct length compiles a fresh kernel (the "
                "ragged-pop storm); pad to a shape bucket "
                "(predicates.bucket / wave_pad_floor)",
                context=ctx.qualname(efn) if efn is not None
                else "<module>"))
    return findings
