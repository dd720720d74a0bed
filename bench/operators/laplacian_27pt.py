"""hypre's 3-D 27-point Laplacian, as its ``ij -27pt`` test driver builds
it (``GenerateLaplacian27pt``): 26 on the diagonal and -1 for each of the
26 neighbours in the 3 x 3 x 3 cube around a point, neighbours outside the
grid dropped (homogeneous Dirichlet).

The grid is ``procs = [px, py, pz]`` process blocks of ``m`` x ``m`` x
``m`` points, rows numbered block by block as hypre's ParCSR numbers them:
row = block * m^3 + (lz * m + ly) * m + lx, block = (bz * py + by) * px +
bx.  So equal contiguous row blocks are the processes' cubes.

The benchmark's own generator: it shares no code with the program.
"""
from __future__ import annotations

import numpy as np


def assemble(cfg: dict):
    """The operator of ``cfg`` as CSR arrays ``(indptr, indices, data)``,
    columns sorted in each row."""
    m = int(cfg["m"])
    px, py, pz = (int(p) for p in cfg["procs"])
    n = m ** 3 * px * py * pz
    block, local = np.divmod(np.arange(n, dtype=np.int64), m ** 3)
    bz, b_xy = np.divmod(block, px * py)
    by, bx = np.divmod(b_xy, px)
    lz, l_xy = np.divmod(local, m * m)
    ly, lx = np.divmod(l_xy, m)
    gx, gy, gz = bx * m + lx, by * m + ly, bz * m + lz
    rows, cols, vals = [], [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                x, y, z = gx + dx, gy + dy, gz + dz
                ok = ((x >= 0) & (x < px * m) & (y >= 0) & (y < py * m)
                      & (z >= 0) & (z < pz * m))
                x, y, z = x[ok], y[ok], z[ok]
                nb = ((z // m * py + y // m) * px + x // m) * m ** 3 + (
                    (z % m * m + y % m) * m + x % m)
                rows.append(np.flatnonzero(ok))
                cols.append(nb)
                centre = dx == 0 and dy == 0 and dz == 0
                vals.append(np.full(len(nb), 26.0 if centre else -1.0))
    rows, cols, vals = (np.concatenate(a) for a in (rows, cols, vals))
    order = np.lexsort((cols, rows))
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=n))])
    return indptr.astype(np.int64), cols[order].astype(np.int32), vals[order]
