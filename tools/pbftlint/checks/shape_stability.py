"""PBL006 — jit dispatch must route through the recorded-signature
warm path.

Historical bug this encodes: the r5 qc256 wedge — a coalesced 8127-item
pile hit a jit signature warmup had never dispatched, and the mid-run
XLA compile (40-150 s under the process-wide device lock) stalled the
whole committee. The fix (ISSUE 3) records every dispatched signature
(``TpuVerifier._record_shape``) so ``post_warm_compiles == 0`` is an
enforceable invariant. This checker makes the *static* half hold:

- **no stray jit construction**: ``jax.jit(...)`` / ``shard_map`` may
  only be constructed in the registered engine modules (the kernels in
  ``ops/``, the verifier/bank in ``crypto/tpu_verifier.py``). A
  ``jax.jit`` in consensus/transport/telemetry code is a new unwarmed
  dispatch surface by definition.

- **dispatch implies recording**: inside the shape-tracked modules
  (``crypto/tpu_verifier.py``, ``crypto/coalesce.py``,
  ``consensus/qc.py``), any function that CALLS a jitted handle
  (``self._fn(...)``) must also call ``_record_shape`` in the same body — otherwise its dispatches
  escape the warm-set accounting and ``post_warm_compiles`` lies.
"""

from __future__ import annotations

import ast
from typing import List, Set

from .. import callgraph
from ..core import Finding, Module

CODE = "PBL006"

# modules allowed to construct jitted callables
JIT_CONSTRUCTION_ALLOWED = (
    "simple_pbft_tpu/ops/",
    "simple_pbft_tpu/crypto/tpu_verifier.py",
    "simple_pbft_tpu/native/",
)
# modules whose jit dispatches must route through shape recording
SHAPE_TRACKED = (
    "simple_pbft_tpu/crypto/tpu_verifier.py",
    "simple_pbft_tpu/crypto/coalesce.py",
    "simple_pbft_tpu/consensus/qc.py",
)
# attribute names that hold jitted callables in the tracked modules
JIT_HANDLES = {"_fn"}
RECORDERS = {"_record_shape"}


def _body_calls(node) -> List[ast.Call]:
    """Calls in ONE def body, stopping at nested defs: a _record_shape
    inside a nested callback must not satisfy the enclosing function's
    dispatch (and a nested def's dispatch is its own FuncInfo — walking
    into it here would double-report)."""
    out: List[ast.Call] = []
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop()
        if isinstance(
            child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            continue
        if isinstance(child, ast.Call):
            out.append(child)
        stack.extend(ast.iter_child_nodes(child))
    return out


def check(mods: List[Module], graph: callgraph.CallGraph) -> List[Finding]:
    out: List[Finding] = []
    for m in mods:
        tracked = m.path in SHAPE_TRACKED or _opted_in(m)
        construction_ok = m.path.startswith(
            JIT_CONSTRUCTION_ALLOWED
        ) or _opted_in(m)
        vis = graph.visitors.get(m.path)
        funcs = vis.funcs if vis is not None else {}

        # stray jit construction anywhere outside the engine modules
        if not construction_ok:
            for node in ast.walk(m.tree):
                if isinstance(node, ast.Call):
                    d = callgraph.dotted(node.func)
                    if d in ("jax.jit", "jit", "shard_map", "jax.pjit", "pjit"):
                        out.append(
                            Finding(
                                code=CODE,
                                path=m.path,
                                line=node.lineno,
                                scope="",
                                detail=f"stray-jit:{d}",
                                message=(
                                    f"{d}() constructed outside the "
                                    "registered engine modules — a new "
                                    "unwarmed dispatch surface; put the "
                                    "kernel behind TpuVerifier/_SHARED_JIT "
                                    "so warmup and shape recording see it"
                                ),
                            )
                        )

        if not tracked:
            continue
        for qual, info in funcs.items():
            calls = _body_calls(info.node)
            dispatches = []
            records = False
            for c in calls:
                d = callgraph.dotted(c.func)
                if d is None:
                    continue
                parts = d.split(".")
                if parts[-1] in JIT_HANDLES:
                    dispatches.append((c, d))
                if parts[-1] in RECORDERS:
                    records = True
            if dispatches and not records:
                for c, d in dispatches:
                    out.append(
                        Finding(
                            code=CODE,
                            path=m.path,
                            line=c.lineno,
                            scope=qual,
                            detail=f"unrecorded-dispatch:{d}",
                            message=(
                                f"jit dispatch {d}(...) in {qual} without "
                                "a _record_shape() call in the same body — "
                                "the dispatch escapes the warmed shape "
                                "set and post_warm_compiles accounting"
                            ),
                        )
                    )
    return out


def _opted_in(m: Module) -> bool:
    head = "\n".join(m.lines[:30])
    return "pbftlint: shape-tracked-module" in head
