"""pbftlint — static analysis purpose-built for this codebase (ISSUE 8).

Every deep review pass of this repo has caught the same five
mechanically-detectable defect classes. This package codifies them as
CI gates so the speculative-execution and aggregation-overlay work the
ROADMAP queues next cannot re-introduce them:

  PBL001  loop-blocking      blocking call reachable on the event loop
                             (the PR 7 ``json.loads``-per-backoff-tick bug)
  PBL002  determinism        hash()/wall-clock/unseeded-random/set-order
                             in replay-deterministic modules (the
                             ShapedTransport PYTHONHASHSEED salt bug)
  PBL003  drift              duplicated literal tables across modules
                             (the _DEFERRABLE_KINDS vs SHED_DEFERRABLE
                             hand-mirroring)
  PBL004  exception-safety   unguarded telemetry/span/audit call inside a
                             consensus path ("telemetry never raises into
                             consensus")
  PBL005  assert-ban         ``assert`` in production control flow (the
                             PR 1 precedent: a layout guard that was an
                             assert)
  PBL006  shape-stability    jit construction/dispatch outside the
                             recorded-signature warm path (the r5 qc256
                             mid-run-compile wedge)

The runtime half of the plane — the event-loop blocking sanitizer and
the lock-discipline sanitizer (``PBFT_SANITIZE=loop,locks``) — lives in
``simple_pbft_tpu/sanitize.py`` because product modules import its
annotation helpers; see docs/STATIC_ANALYSIS.md.

Run: ``python -m tools.pbftlint [--json] [--changed] [paths...]``
"""

from .core import Finding, LintConfig, run_lint  # noqa: F401

__all__ = ["Finding", "LintConfig", "run_lint"]
