"""The comparison that decides ``correct``.

Each compared solve is run again by the reference (``bench.reference``)
from the same right-hand side, for as many residual checks as the program's
history holds, and three numbers are taken, each the worst over the
compared solves:

- ``hist_gap``: the largest gap between a relative residual the program
  reported and the reference's at the same step;
- ``x_gap``: ||x - x_ref|| / ||x_ref|| of the iterate the call returned;
- ``resid``: ||b - A x|| / ||b|| of the returned iterate, with the
  benchmark's own A, over the solves that reported convergence.  Its limit
  is the configuration's tolerance.

A number that is not finite fails.  The limits are the configuration's
``limits``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


def _finite(v: float) -> float:
    return v if math.isfinite(v) else math.inf


def compare(ref, solves: List[dict], sample: List[int], cfg: dict
            ) -> Tuple[Dict[str, float], int]:
    """(worst number of each kind, compared solves that failed a limit)."""
    limits, tol = cfg["limits"], float(cfg["tol"])
    worst: Dict[str, float] = {"hist_gap": 0.0, "x_gap": 0.0}
    failed = 0
    for i in sample:
        s = solves[i]
        x = np.asarray(s["x"], dtype=np.float64)
        hist = np.asarray(s["hist"], dtype=np.float64)
        converged = bool(hist[-1] < tol)
        x_ref, h_ref = ref.solve(s["b"], len(hist), converged)
        got = {
            "hist_gap": _finite(float(np.max(np.abs(hist - h_ref)))),
            "x_gap": _finite(float(np.linalg.norm(x - x_ref)
                                   / max(np.linalg.norm(x_ref), 1e-300))),
        }
        if converged:
            got["resid"] = _finite(ref.resid(s["b"], x))
        if any(not v <= limits[k] for k, v in got.items()):
            failed += 1
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst, failed


def report(worst: Dict[str, float], cfg: dict) -> Dict[str, dict]:
    """Each number beside its limit, as the result line carries them."""
    return {k: {"value": v, "limit": cfg["limits"][k]}
            for k, v in worst.items()}
