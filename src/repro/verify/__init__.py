"""repro.verify — static plan/kernel verification.

The planners hand the whole communication pattern to the runtime; this
package checks the whole pattern.  Three passes:

* :mod:`.invariants` — host-side structural checks over patterns, plans,
  partitions, device ELL layouts and MoE dispatch geometry (conservation,
  duality, round conflict-freedom).
* :mod:`.jaxpr_audit` — trace bound executors and prove the compiled
  collective sequence matches the frozen DevicePlan (SPMD uniformity; no
  collective under data-dependent control flow).
* :mod:`.kernel_budget` — the SpMV kernel's BlockSpec footprint fits one
  core's VMEM.

Entry points: :func:`verify_hierarchy` sweeps every operator of a
``DistributedHierarchy``; ``ServeEngine.verify()`` checks a serving
engine's MoE plans; ``PlanCache`` calls :func:`verify_cache_value` /
:func:`audit_executor` on insertion when :func:`verify_enabled` — i.e.
``REPRO_VERIFY=1`` (tests/CI default via ``test.sh``; unset in production
hot paths).  All failures raise :class:`VerifyError` with a diagnostic
naming the offending rank / slot.
"""
from __future__ import annotations

import os
from typing import Dict

from .invariants import (
    VerifyError,
    verify_cache_value,
    verify_collective,
    verify_dense_plan,
    verify_device_ell,
    verify_device_plan,
    verify_moe_dispatch,
    verify_moe_plan,
    verify_partition,
    verify_pattern,
    verify_plan,
    verify_round_schedule,
)
from .jaxpr_audit import (
    COLLECTIVE_PRIMITIVES,
    CollectiveRecord,
    audit_dense_executor,
    audit_executor,
    collective_signature,
    trace_collectives,
)
from .kernel_budget import (
    flat_kernel_actual_bytes,
    verify_kernel_budget,
    vmem_bytes_per_core,
)

__all__ = [
    "VerifyError",
    "verify_enabled",
    "verify_pattern",
    "verify_round_schedule",
    "verify_plan",
    "verify_device_plan",
    "verify_collective",
    "verify_partition",
    "verify_device_ell",
    "verify_moe_plan",
    "verify_moe_dispatch",
    "verify_dense_plan",
    "verify_cache_value",
    "COLLECTIVE_PRIMITIVES",
    "CollectiveRecord",
    "collective_signature",
    "trace_collectives",
    "audit_executor",
    "audit_dense_executor",
    "flat_kernel_actual_bytes",
    "verify_kernel_budget",
    "vmem_bytes_per_core",
    "verify_dist_op",
    "verify_hierarchy",
]


def verify_enabled() -> bool:
    """Whether plan-cache insertions verify (``REPRO_VERIFY``).

    Read per call, not at import, so tests and operators can flip it at
    runtime.  On by default in tests/CI (``test.sh`` exports it); leave it
    unset in production hot paths — verification is host-side numpy over
    plan metadata, cheap next to planning but not free.
    """
    return os.environ.get("REPRO_VERIFY", "0").lower() in ("1", "true", "on")


def verify_dist_op(op, *, value_bytes: int = 8) -> Dict[str, int]:
    """All static checks for one distributed operator (a ``DistOp``):
    partition, bound collective, device layout and kernel budget.

    Each pass runs under an obs span (``verify/<pass>``) so
    ``obs.report()`` breaks verification wall time out per pass.
    """
    from ..obs import default_obs

    obs = default_obs()
    counts: Dict[str, int] = {}

    def tick(k: str) -> None:
        counts[k] = counts.get(k, 0) + 1

    with obs.span("verify/partition"):
        verify_partition(op.part)
    tick("partitions")
    if op.coll is not None:
        with obs.span("verify/collective"):
            verify_collective(op.coll)
        tick("collectives")
    with obs.span("verify/layout"):
        verify_device_ell(op.ell, op.part)
    tick("layouts")
    with obs.span("verify/kernel_budget"):
        verify_kernel_budget(op.ell, value_bytes=value_bytes)
    tick("kernel_budgets")
    return counts


def verify_hierarchy(h) -> Dict[str, int]:
    """Sweep every operator (A, R, P per level) of a
    ``DistributedHierarchy``; returns check counts per category.  Raises
    :class:`VerifyError` on the first violated invariant."""
    from ..obs import default_obs

    counts: Dict[str, int] = {"levels": len(h.levels)}
    with default_obs().span("verify/hierarchy", levels=len(h.levels)):
        for lv in h.levels:
            for name, op in (("A", lv.A), ("R", lv.R), ("P", lv.P)):
                if op is None:
                    continue
                try:
                    for k, v in verify_dist_op(
                            op, value_bytes=h.value_bytes).items():
                        counts[k] = counts.get(k, 0) + v
                except VerifyError as e:
                    raise VerifyError(
                        f"level {lv.index} operator {name}: {e}"
                    ) from e
    return counts
