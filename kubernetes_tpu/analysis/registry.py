"""Shipped analyzer configuration: the audited whitelists and path
scopes for the kubernetes_tpu package.

SANCTIONED_SYNC_POINTS is the contract at the heart of the pipelined
solver (a blocking host<->device sync stalls the dispatch loop for the
whole in-flight solve): the hot path may read device values through
EXACTLY these three points —

- ``DeferredAssignments.get`` (solver/exact.py): the deferred
  assignment download whose async D2H copy was started at dispatch, so
  the blocking read lands after the transfer has been overlapped.
- ``DeferredAssignments.wait`` (solver/exact.py): the streaming
  dispatcher's completion thread parks here so the wait is paid
  OFF the driver thread — it only waits for the async D2H started at
  dispatch to land and never converts the value; the driver's ``get``
  stays the one read.
- ``_InFlightSolve.assignments`` (scheduler.py): the scheduler-side
  wrapper the apply path calls once per batch.

Adding an entry is a design decision, not a lint tweak: it must come
with the same overlap analysis these carry.
"""

from __future__ import annotations

from .core import AnalysisContext

SANCTIONED_SYNC_POINTS = frozenset(
    {
        ("kubernetes_tpu/solver/exact.py", "DeferredAssignments.get"),
        ("kubernetes_tpu/solver/exact.py", "DeferredAssignments.wait"),
        ("kubernetes_tpu/scheduler.py", "_InFlightSolve.assignments"),
    }
)

# TPU003 dtype discipline applies where tensors feed the solve pipeline
# (a weakly-typed float literal silently re-specializes the jit cache).
# The solver/ prefix covers every engine — exact, single_shot, and the
# convex-relaxation mega-planner (solver/relax.py, ISSUE 19) — so a new
# kernel file inherits the discipline without a registry edit.
DTYPE_PATHS = (
    "kubernetes_tpu/ops/",
    "kubernetes_tpu/solver/",
)

# MET001 scans these for metric usage against metrics/__init__.py.
METRIC_SCAN_PATHS = (
    "kubernetes_tpu/scheduler.py",
    "kubernetes_tpu/resilience.py",
    "kubernetes_tpu/server/",
    "kubernetes_tpu/solver/",
    "kubernetes_tpu/sim/",
    "kubernetes_tpu/obs/",
    "kubernetes_tpu/fleet/",
    "kubernetes_tpu/rebalance/",
    "kubernetes_tpu/tuning/",
)


def default_context() -> AnalysisContext:
    return AnalysisContext(
        sanctioned_sync=SANCTIONED_SYNC_POINTS,
        dtype_paths=DTYPE_PATHS,
        metric_scan_paths=METRIC_SCAN_PATHS,
        metric_attrs=None,  # resolved lazily from kubernetes_tpu/metrics
    )
