"""What one V-cycle step must move and exchange, counted from the host
hierarchy's sizes and the solver the configuration states.

A level is a dict: ``n`` rows and ``nnz`` of its operator A, and for every
level but the coarsest ``nc`` (coarse rows), ``nnz_r`` and ``nnz_p`` of its
restriction and prolongation.

Bytes follow one rule, whatever implements the step: an operator
application reads each stored nonzero once (a value and a column index),
reads its input vector once and writes its output once; an elementwise
update reads each input vector once and writes each output once.  A
padded or bucketed layout, a kernel that gathers more, or a vector read
twice moves more than this count, so time against it is a lower bound.
"""
from __future__ import annotations

from typing import List, Optional

INDEX_BYTES = 4


def spmv_bytes(nnz: int, n_in: int, n_out: int, vb: int) -> int:
    return nnz * (vb + INDEX_BYTES) + vb * (n_in + n_out)


def cheby_bytes(n: int, nnz: int, degree: int, vb: int) -> int:
    """``degree`` steps of x, p <- update(A x, b, D^-1, x, p): the first
    step has no p to read."""
    updates = 6 + 7 * (degree - 1)
    return degree * spmv_bytes(nnz, n, n, vb) + updates * n * vb


def vcycle_bytes(levels: List[dict], solver: dict, vb: int) -> int:
    """Bytes of one step: r = b - A x, ||r||, x + V(r)."""
    total = 0
    for k, lv in enumerate(levels):
        n, nnz = lv["n"], lv["nnz"]
        if k == len(levels) - 1:
            total += cheby_bytes(n, nnz, solver["coarse_degree"], vb)
            break
        nc = lv["nc"]
        total += cheby_bytes(n, nnz, solver["pre_degree"], vb)
        total += cheby_bytes(n, nnz, solver["post_degree"], vb)
        total += spmv_bytes(nnz, n, n, vb) + 3 * n * vb        # r = b - A x
        total += spmv_bytes(lv["nnz_r"], n, nc, vb)            # R r
        total += spmv_bytes(lv["nnz_p"], nc, n, vb) + 3 * n * vb  # x += P e
    n0 = levels[0]["n"]
    total += spmv_bytes(levels[0]["nnz"], n0, n0, vb) + 3 * n0 * vb
    total += n0 * vb + 3 * n0 * vb                             # ||r||, x + v
    return total


def applications(k: int, n_levels: int, solver: dict) -> int:
    """Applications of level ``k``'s operator A in one step."""
    if k == n_levels - 1:
        apps = solver["coarse_degree"]
    else:
        apps = solver["pre_degree"] + solver["post_degree"] + 1
    return apps + (1 if k == 0 else 0)


def halo_msgs(levels: List[dict], solver: dict) -> Optional[int]:
    """Messages of one step over every level's exchanges, from each
    operator's plan: ``msgs_a`` per application of A, ``msgs_r`` and
    ``msgs_p`` once each.  None where no level exchanges anything."""
    total = 0
    for k, lv in enumerate(levels):
        total += lv["msgs_a"] * applications(k, len(levels), solver)
        total += lv.get("msgs_r", 0) + lv.get("msgs_p", 0)
    return total or None
