"""2-D diffusion -div(K grad u) on a regular grid with Dirichlet boundary.

K = Q(theta)^T diag(1, eps) Q(theta), discretised by the 7-point stencil
for operators with a mixed derivative: centre, E, W, N, S and the two
corners along the strong diagonal (NE/SW where the cross term is positive,
NW/SE where it is negative).  theta=45 deg, eps=1e-3 is the paper's rotated
anisotropic system; theta=0, eps=1 drops the corners and leaves the 5-point
Laplacian (diagonal 4, neighbours -1).  Neighbours outside the grid are
dropped (homogeneous Dirichlet).

The benchmark's own generator: it shares no code with the program.
"""
from __future__ import annotations

import math

import numpy as np


def stencil(theta_deg: float, eps: float) -> list:
    """[(dy, dx, coefficient)] of the 7-point stencil; zero entries dropped."""
    t = math.radians(theta_deg)
    c, s = math.cos(t), math.sin(t)
    kxx = c * c + eps * s * s
    kyy = s * s + eps * c * c
    kxy = (1.0 - eps) * c * s
    k = abs(kxy)
    corner = (1, 1) if kxy >= 0 else (1, -1)
    entries = [
        (0, 0, 2 * kxx + 2 * kyy - 2 * k),
        (0, 1, -kxx + k),
        (0, -1, -kxx + k),
        (1, 0, -kyy + k),
        (-1, 0, -kyy + k),
        (corner[0], corner[1], -k),
        (-corner[0], -corner[1], -k),
    ]
    return [e for e in entries if e[2] != 0.0]


def assemble(cfg: dict):
    """The operator of ``cfg`` as CSR arrays ``(indptr, indices, data)``,
    rows ordered y-major (row = y * nx + x), columns sorted in each row."""
    ny, nx = int(cfg["ny"]), int(cfg["nx"])
    n = ny * nx
    ys, xs = np.divmod(np.arange(n, dtype=np.int64), nx)
    rows, cols, vals = [], [], []
    for dy, dx, coeff in stencil(float(cfg["theta_deg"]), float(cfg["eps"])):
        yy, xx = ys + dy, xs + dx
        ok = (yy >= 0) & (yy < ny) & (xx >= 0) & (xx < nx)
        rows.append(np.flatnonzero(ok))
        cols.append((yy * nx + xx)[ok])
        vals.append(np.full(int(ok.sum()), coeff))
    rows, cols, vals = (np.concatenate(a) for a in (rows, cols, vals))
    order = np.lexsort((cols, rows))
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=n))])
    return indptr.astype(np.int64), cols[order].astype(np.int32), vals[order]
