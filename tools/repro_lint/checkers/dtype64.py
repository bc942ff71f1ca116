"""RL401 — the float32 kernel contract.

`kernels/placement_score/ops.py` *rejects* float64 inputs rather than
silently downcasting, so the kernel path can never drift bitwise from
the jnp oracle.  That only holds if kernel-reachable modules never mint
float64 arrays in the first place.  This checker flags float64
*creation* sites — `dtype=float64` keywords, `.astype(float64)`, and
`np.float64(...)`/`jnp.float64(...)` constructor calls — in
kernel-reachable modules (`src/repro/kernels/` plus the core modules
whose arrays flow into kernel calls).  Comparisons like
`x.dtype == jnp.float64` (the guard in ops.py itself) are creation-free
and are not flagged.
"""
from __future__ import annotations

import ast

from .. import registry
from ..pyast import dotted, resolve
from ..scopes import in_kernel_reachable

registry.rule(
    "RL401", "float64-in-kernel-path",
    "kernel-reachable modules must not create float64 arrays: the "
    "placement-score kernel computes in float32 and its ops wrapper "
    "rejects x64 inputs (feasible_rows contract)")

_F64 = {"numpy.float64", "jax.numpy.float64"}


def _is_float64(node: ast.AST, aliases) -> bool:
    if isinstance(node, ast.Constant):
        return node.value == "float64"
    q = resolve(dotted(node), aliases)
    return q in _F64


@registry.file_checker
def check_dtype64(ctx):
    if not in_kernel_reachable(ctx.scope_path):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        # x.astype(float64-ish)
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype":
            for arg in (*node.args, *(kw.value for kw in node.keywords)):
                if _is_float64(arg, ctx.aliases):
                    yield ctx.diag(node, "RL401",
                                   ".astype(float64) in kernel-reachable"
                                   " module (float32 kernel contract)")
        # np.float64(x) / jnp.float64(x)
        elif resolve(dotted(node.func), ctx.aliases) in _F64:
            yield ctx.diag(node, "RL401",
                           "float64 scalar/array constructor in "
                           "kernel-reachable module (float32 kernel "
                           "contract)")
        # any call carrying dtype=float64
        for kw in node.keywords:
            if kw.arg == "dtype" and _is_float64(kw.value, ctx.aliases):
                yield ctx.diag(node, "RL401",
                               "dtype=float64 in kernel-reachable "
                               "module (float32 kernel contract)")
