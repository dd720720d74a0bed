"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``.

A mix of solves gives how right-hand sides are drawn (``rhs``), the most
V-cycles one call may run (``max_iters``) and how many of the window's
solves the check compares with the reference (``check_sample``).  Calls
run back to back from one caller; the last call of a window is bounded so
that it ends near the window's end, never cut and restarted.
"""
from __future__ import annotations

import numpy as np

#: the stream of the warm-up solve; the window's solves are 1, 2, ...
WARM_UP = 0


def rhs(traffic: dict, n: int, seed: int, index: int) -> np.ndarray:
    """Right-hand side ``index`` of the run with ``seed``: the mix's fixed
    base vector ``index`` plus a part drawn from the seed, ``seed_part``
    times as large.  Every seed solves the same set of problems, so the seed
    does not change the work, and no two seeds solve the same vector."""
    spec = traffic["rhs"]
    if spec["kind"] != "normal":
        raise ValueError(f"unknown right-hand side kind {spec['kind']!r}")
    base = np.random.default_rng([spec["base_seed"], index]).standard_normal(n)
    part = np.random.default_rng([seed % 2 ** 64, index]).standard_normal(n)
    return base + spec["seed_part"] * part


def call_cap(traffic: dict, remaining_s: float, vcycle_s: float) -> int:
    """V-cycles the next call may run: the mix's cap, or as many as fit in
    what is left of the window.  0 where fewer than two fit: a call of one
    V-cycle checks only the residual of x0 = 0 and so measures nothing."""
    cap = int(min(traffic["max_iters"], max(remaining_s, 0.0) // vcycle_s))
    return cap if cap >= 2 else 0


def check_sample(traffic: dict, lengths: list, seed: int) -> list:
    """Indices of the window's solves to compare: the longest, and others
    drawn from the seed, ``check_sample`` in all."""
    k = min(int(traffic["check_sample"]), len(lengths))
    if k == 0:
        return []
    longest = int(np.argmax(lengths))
    rest = [i for i in range(len(lengths)) if i != longest]
    rng = np.random.default_rng([seed % 2 ** 64, 2 ** 32])
    picked = rng.choice(len(rest), size=k - 1, replace=False) if k > 1 else []
    return sorted([longest] + [rest[int(j)] for j in picked])
