"""PBL004/PBL005 — the "telemetry never raises into consensus" contract
and the production-assert ban.

PBL004: the consensus path (consensus/*.py) calls into the telemetry
plane constantly — spans, the request tracer, the safety auditor, the
stats histograms. The contract (docs/OBSERVABILITY.md, PR 2) is that
those surfaces swallow their own failures; consensus code therefore
calls them UNGUARDED, which is only sound for entry points that were
actually audited to be no-raise. The checker holds the audited list:

- a telemetry-surface call in a consensus module is OK when its
  (root, method) pair is in ``AUDITED_NO_RAISE`` or it is lexically
  inside a ``try`` with an ``except Exception``/bare handler;
- anything else flags — new observability code either goes through an
  audited entry point or wears an explicit guard;
- every audited entry is *verified to exist* in its owning module, so
  renaming ``RequestTracer.emit`` breaks the lint and forces re-audit
  instead of silently un-protecting every call site.

PBL005: ``assert`` compiles away under ``python -O`` — a production
control-flow assert is a check that vanishes exactly when the system
runs optimized (the PR 1 precedent: a table-layout guard in ``ops/comb``
that was an ``assert``).
Flagged in every product module; validation belongs to ``raise``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .. import callgraph
from ..core import Finding, Module

CODE_TELEM = "PBL004"
CODE_ASSERT = "PBL005"

CONSENSUS_PREFIX = "simple_pbft_tpu/consensus/"

# attribute roots that denote the telemetry plane from consensus code
TELEMETRY_ROOTS = {
    "spans",
    "tracer",
    "auditor",
    "stats",
    "telemetry",
    "flight",
    "watchdog",
    "recorder",
    "devledger",
    # cross-replica trace plane (ISSUE 20): wire-envelope stamping and
    # per-certificate quorum-arrival stats called from replica/viewchange
    "trace",
    "qstats",
}

# (root, terminal attr) -> (owning module path, class or None, def name)
# — the audited no-raise surface. Each target's existence is checked.
AUDITED_NO_RAISE: Dict[Tuple[str, str], Tuple[str, Optional[str], str]] = {
    ("spans", "record"): ("simple_pbft_tpu/spans.py", None, "record"),
    # loop-held stages (ISSUE 26): a section is two clock reads and a few
    # adds on the loop's own state; charge() the same without the clock
    ("spans", "held"): ("simple_pbft_tpu/spans.py", "held", "__init__"),
    ("spans", "begin"): ("simple_pbft_tpu/spans.py", None, "begin"),
    ("spans", "end"): ("simple_pbft_tpu/spans.py", None, "end"),
    ("spans", "parked"): ("simple_pbft_tpu/spans.py", "parked", "__enter__"),
    ("spans", "charge"): ("simple_pbft_tpu/spans.py", None, "charge"),
    ("tracer", "emit"): (
        "simple_pbft_tpu/telemetry.py", "RequestTracer", "emit"),
    ("tracer", "note_block"): (
        "simple_pbft_tpu/telemetry.py", "RequestTracer", "note_block"),
    ("tracer", "slot_event"): (
        "simple_pbft_tpu/telemetry.py", "RequestTracer", "slot_event"),
    ("tracer", "release_slot"): (
        "simple_pbft_tpu/telemetry.py", "RequestTracer", "release_slot"),
    ("tracer", "rid_if_sampled"): (
        "simple_pbft_tpu/telemetry.py", "RequestTracer", "rid_if_sampled"),
    ("auditor", "observe_message"): (
        "simple_pbft_tpu/audit.py", "SafetyAuditor", "observe_message"),
    ("auditor", "observe_qc"): (
        "simple_pbft_tpu/audit.py", "SafetyAuditor", "observe_qc"),
    ("auditor", "observe_commit"): (
        "simple_pbft_tpu/audit.py", "SafetyAuditor", "observe_commit"),
    ("auditor", "observe_rejected_new_view"): (
        "simple_pbft_tpu/audit.py",
        "SafetyAuditor",
        "observe_rejected_new_view",
    ),
    ("auditor", "on_epoch"): (
        "simple_pbft_tpu/audit.py", "SafetyAuditor", "on_epoch"),
    ("auditor", "gc"): ("simple_pbft_tpu/audit.py", "SafetyAuditor", "gc"),
    ("stats", "record"): ("simple_pbft_tpu/logutil.py", "Histogram", "record"),
    # device-plane event ledger (ISSUE 14): the dispatch-recording seam
    # in consensus/qc.py (and any future consensus-side device lane)
    # rides these module-level never-raise entries — record() broad-
    # guards its own body, annotate()/take_annotation() guard the
    # thread-local handoff
    ("devledger", "record"): ("simple_pbft_tpu/devledger.py", None, "record"),
    ("devledger", "annotate"): (
        "simple_pbft_tpu/devledger.py", None, "annotate"),
    ("devledger", "take_annotation"): (
        "simple_pbft_tpu/devledger.py", None, "take_annotation"),
    ("devledger", "snapshot"): (
        "simple_pbft_tpu/devledger.py", None, "snapshot"),
    # trace plane (ISSUE 20): stamp() returns the frame unchanged on any
    # internal failure; QuorumStats methods broad-guard their own bodies
    ("trace", "stamp"): ("simple_pbft_tpu/trace.py", None, "stamp"),
    # the replica's one-time construction of its stats surface: plain
    # attribute initialization, no I/O to fail
    ("trace", "QuorumStats"): (
        "simple_pbft_tpu/trace.py", "QuorumStats", "__init__"),
    ("qstats", "note_vote"): (
        "simple_pbft_tpu/trace.py", "QuorumStats", "note_vote"),
    ("qstats", "note_quorum"): (
        "simple_pbft_tpu/trace.py", "QuorumStats", "note_quorum"),
    ("qstats", "flush_upto"): (
        "simple_pbft_tpu/trace.py", "QuorumStats", "flush_upto"),
    ("qstats", "flush_all"): (
        "simple_pbft_tpu/trace.py", "QuorumStats", "flush_all"),
    ("qstats", "snapshot"): (
        "simple_pbft_tpu/trace.py", "QuorumStats", "snapshot"),
}


def _def_exists(mod: Module, cls: Optional[str], name: str) -> bool:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef) and cls is not None:
            if node.name == cls:
                return any(
                    isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and n.name == name
                    for n in node.body
                )
        elif cls is None and isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            if node.name == name:
                return True
    return False


def _chain_root_terminal(name: str) -> Optional[Tuple[str, str]]:
    parts = name.split(".")
    if len(parts) < 2:
        return None
    root = parts[1] if parts[0] in ("self", "cls") and len(parts) > 2 else (
        parts[0] if parts[0] not in ("self", "cls") else parts[1]
    )
    return root, parts[-1]


class _GuardVisitor(ast.NodeVisitor):
    """Telemetry calls + their guardedness in one consensus module."""

    def __init__(self, mod: Module) -> None:
        self.mod = mod
        self.scope: List[str] = []
        self.guard_depth = 0
        self.findings: List[Finding] = []

    def visit_FunctionDef(self, node) -> None:
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_ClassDef = visit_FunctionDef

    def visit_Try(self, node: ast.Try) -> None:
        def _broad_type(t: Optional[ast.AST]) -> bool:
            if t is None:  # bare except
                return True
            if isinstance(t, ast.Name):
                return t.id in ("Exception", "BaseException")
            if isinstance(t, ast.Tuple):  # except (A, Exception):
                return any(_broad_type(e) for e in t.elts)
            return False

        broad = any(_broad_type(h.type) for h in node.handlers)
        for stmt in node.body:
            if broad:
                self.guard_depth += 1
                self.visit(stmt)
                self.guard_depth -= 1
            else:
                self.visit(stmt)
        for part in (node.handlers, node.orelse, node.finalbody):
            for stmt in part:
                self.visit(stmt)

    def visit_Call(self, node: ast.Call) -> None:
        name = callgraph.dotted(node.func)
        if name is not None:
            rt = _chain_root_terminal(name)
            if rt is not None and rt[0] in TELEMETRY_ROOTS:
                if rt not in AUDITED_NO_RAISE and self.guard_depth == 0:
                    self.findings.append(
                        Finding(
                            code=CODE_TELEM,
                            path=self.mod.path,
                            line=node.lineno,
                            scope=".".join(self.scope),
                            detail=name,
                            message=(
                                f"unguarded telemetry-plane call {name}() "
                                "in a consensus path — route through an "
                                "audited no-raise entry point or wrap in "
                                "try/except Exception (telemetry never "
                                "raises into consensus)"
                            ),
                        )
                    )
        self.generic_visit(node)


def check(mods: List[Module], graph: callgraph.CallGraph) -> List[Finding]:
    out: List[Finding] = []
    by_path = {m.path: m for m in mods}

    # the audited list must stay bound to real definitions
    for (root, term), (owner, cls, name) in AUDITED_NO_RAISE.items():
        owner_mod = by_path.get(owner)
        if owner_mod is None:
            continue  # partial-scope run (fixtures): nothing to verify
        if not _def_exists(owner_mod, cls, name):
            out.append(
                Finding(
                    code=CODE_TELEM,
                    path=owner,
                    line=1,
                    scope="",
                    detail=f"audited-missing:{root}.{term}",
                    message=(
                        f"audited no-raise entry {cls or owner}.{name} no "
                        "longer exists — update pbftlint's "
                        "AUDITED_NO_RAISE after re-auditing call sites"
                    ),
                )
            )

    for m in mods:
        if m.path.startswith(CONSENSUS_PREFIX) or _consensus_opted_in(m):
            v = _GuardVisitor(m)
            v.visit(m.tree)
            out.extend(v.findings)
        # assert ban: every product module
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Assert):
                out.append(
                    Finding(
                        code=CODE_ASSERT,
                        path=m.path,
                        line=node.lineno,
                        scope="",
                        detail=f"assert@{_assert_detail(node)}",
                        message=(
                            "assert in production control flow — vanishes "
                            "under python -O; raise ValueError/RuntimeError "
                            "for validation, or baseline with a why for "
                            "internal invariants"
                        ),
                    )
                )
    return out


def _consensus_opted_in(m: Module) -> bool:
    head = "\n".join(m.lines[:30])
    return "pbftlint: consensus-module" in head


def _assert_detail(node: ast.Assert) -> str:
    """Line-stable-ish identity: the test expression's source text."""
    try:
        return ast.unparse(node.test)[:60]
    except Exception:
        return str(node.lineno)
