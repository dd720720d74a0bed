"""The plain reference of a solve: classical AMG on the host, in f64.

Independent of the program: it imports nothing of it and takes nothing it
made.  It builds its own hierarchy from the benchmark's operator, by the
algorithm the configuration states (classical strength, PMIS, direct
interpolation, Galerkin products, Chebyshev smoothing), with scipy's
sparse matrices in place of the program's CSR and device layout.  The same
operations on the same data give the same hierarchy, so its residual
history and iterate track a correct solve to rounding.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np
import scipy.sparse as sp


def _rows(A: sp.csr_matrix) -> np.ndarray:
    return np.repeat(np.arange(A.shape[0], dtype=np.int64), np.diff(A.indptr))


def _csr(rows, cols, vals, shape) -> sp.csr_matrix:
    M = sp.csr_matrix((vals, (rows, cols)), shape=shape)
    M.sum_duplicates()
    M.sort_indices()
    return M


def inv_diag(A: sp.csr_matrix) -> np.ndarray:
    d = A.diagonal()
    return np.where(d != 0, 1.0 / np.where(d == 0, 1.0, d), 0.0)


def strength(A: sp.csr_matrix, theta: float) -> sp.csr_matrix:
    """j strongly influences i where -a_ij >= theta * max_k(-a_ik), k != i."""
    rows = _rows(A)
    offd = rows != A.indices
    neg = np.where(offd, -A.data, 0.0)
    row_max = np.zeros(A.shape[0])
    np.maximum.at(row_max, rows, neg)
    keep = offd & (neg >= theta * row_max[rows]) & (neg > 0)
    return _csr(rows[keep], A.indices[keep], np.ones(int(keep.sum())), A.shape)


def pmis(S: sp.csr_matrix, seed: int) -> np.ndarray:
    """PMIS on the symmetrised strength graph: +1 C-point, 0 F-point."""
    n = S.shape[0]
    G = _csr(np.concatenate([_rows(S), S.indices]),
             np.concatenate([S.indices, _rows(S)]),
             np.ones(2 * S.nnz), S.shape)
    w = np.diff(G.indptr).astype(np.float64) + np.random.default_rng(
        seed).random(n)
    undecided, cpt, fpt = 0, 1, 2
    state = np.full(n, undecided, dtype=np.int8)
    state[np.diff(G.indptr) == 0] = fpt
    g_rows, g_cols = _rows(G), G.indices.astype(np.int64)
    while np.any(state == undecided):
        active_w = np.where(state == undecided, w, -1.0)
        nbr_max = np.zeros(n)
        live = state[g_rows] == undecided
        np.maximum.at(nbr_max, g_rows[live], active_w[g_cols[live]])
        new_c = (state == undecided) & (active_w > nbr_max)
        if not np.any(new_c):
            new_c = np.zeros(n, dtype=bool)
            new_c[np.flatnonzero(state == undecided)[0]] = True
        state[new_c] = cpt
        hit = new_c[g_cols] & (state[g_rows] == undecided)
        state[g_rows[hit]] = fpt
    return (state == cpt).astype(np.int8)


def direct_interpolation(A: sp.csr_matrix, S: sp.csr_matrix,
                         split: np.ndarray):
    """Direct interpolation from strong C-neighbours with negative
    couplings; F-points with no strong C-neighbour become C."""
    n = A.shape[0]
    arows, acols, avals = _rows(A), A.indices.astype(np.int64), A.data
    skeys = np.sort(_rows(S) * n + S.indices)
    q = arows * n + acols
    pos = np.minimum(np.searchsorted(skeys, q), len(skeys) - 1)
    strong = skeys[pos] == q
    deg_strong = np.bincount(_rows(S), minlength=n)
    for _ in range(30):
        has_c = np.zeros(n, dtype=bool)
        has_c[arows[strong & (split[acols] == 1)]] = True
        bad = (split == 0) & ~has_c & (deg_strong > 0)
        if not np.any(bad):
            break
        split = split.copy()
        split[bad] = 1
    cpts = np.flatnonzero(split == 1)
    cmap = -np.ones(n, dtype=np.int64)
    cmap[cpts] = np.arange(len(cpts))
    diag = A.diagonal()
    neg = np.where((arows != acols) & (avals < 0), avals, 0.0)
    neg_sum = np.zeros(n)
    np.add.at(neg_sum, arows, neg)
    interp = strong & (split[acols] == 1) & (avals < 0)
    cneg_sum = np.zeros(n)
    np.add.at(cneg_sum, arows[interp], avals[interp])
    f = interp & (split[arows] == 0)
    ri, ci, vi = arows[f], acols[f], avals[f]
    alpha = np.where(cneg_sum[ri] != 0, neg_sum[ri] / cneg_sum[ri], 0.0)
    P = _csr(np.concatenate([ri, cpts]),
             np.concatenate([cmap[ci], cmap[cpts]]),
             np.concatenate([-alpha * vi / diag[ri], np.ones(len(cpts))]),
             (n, len(cpts)))
    return P, split


def estimate_rho(A: sp.csr_matrix, iters: int) -> float:
    """Power iteration on D^-1 A from a fixed start vector."""
    dinv = inv_diag(A)
    x = np.random.default_rng(0).normal(size=A.shape[0])
    x /= np.linalg.norm(x) + 1e-300
    rho = 1.0
    for _ in range(iters):
        y = dinv * (A @ x)
        nrm = np.linalg.norm(y)
        if nrm == 0:
            return 1.0
        rho = nrm
        x = y / nrm
    return float(rho)


@dataclass
class Level:
    A: sp.csr_matrix
    dinv: np.ndarray
    rho: float
    P: Optional[sp.csr_matrix] = None
    R: Optional[sp.csr_matrix] = None


class Reference:
    """The reference hierarchy of one operator and the solver it states."""

    def __init__(self, indptr, indices, data, solver: dict):
        n = len(indptr) - 1
        A = sp.csr_matrix((np.asarray(data, dtype=np.float64),
                           np.asarray(indices), np.asarray(indptr)),
                          shape=(n, n))
        self.solver = solver
        mats: List[list] = [[A, None, None]]
        while (mats[-1][0].shape[0] > solver["min_coarse"]
               and len(mats) < solver["max_levels"]):
            Ak = mats[-1][0]
            S = strength(Ak, solver["strength_theta"])
            if S.nnz == 0:
                break
            P, _ = direct_interpolation(Ak, S, pmis(S, seed=len(mats)))
            if P.shape[1] >= Ak.shape[0] or P.shape[1] == 0:
                break
            R = P.T.tocsr()
            R.sort_indices()
            Ac = R @ (Ak @ P)
            Ac.data[np.abs(Ac.data) <= solver["prune"]] = 0.0
            Ac.eliminate_zeros()
            Ac.sort_indices()
            mats[-1][1:] = [P, R]
            mats.append([Ac, None, None])
        self.levels = [
            Level(A=Ak, dinv=inv_diag(Ak),
                  rho=estimate_rho(Ak, solver["rho_iters"]) or 1.0, P=P, R=R)
            for Ak, P, R in mats
        ]
        self.A = A

    def _cheby(self, lv: Level, x, b, degree: int):
        upper = self.solver["cheby_upper"] * lv.rho
        lower = self.solver["cheby_lower"] * lv.rho
        theta = 0.5 * (upper + lower)
        delta = 0.5 * (upper - lower)
        sigma = theta / delta
        rho_k = 1.0 / sigma
        r = lv.dinv * (b - lv.A @ x)
        p = r / theta
        x = x + p
        for _ in range(degree - 1):
            rho_next = 1.0 / (2.0 * sigma - rho_k)
            r = lv.dinv * (b - lv.A @ x)
            p = rho_next * rho_k * p + 2.0 * rho_next / delta * r
            x = x + p
            rho_k = rho_next
        return x

    def vcycle(self, k: int, b: np.ndarray) -> np.ndarray:
        lv = self.levels[k]
        zero = np.zeros_like(b)
        if lv.R is None:
            return self._cheby(lv, zero, b, self.solver["coarse_degree"])
        x = self._cheby(lv, zero, b, self.solver["pre_degree"])
        rc = lv.R @ (b - lv.A @ x)
        x = x + lv.P @ self.vcycle(k + 1, rc)
        return self._cheby(lv, x, b, self.solver["post_degree"])

    def solve(self, b: np.ndarray, steps: int, converged: bool):
        """The stationary iteration from x0 = 0 for ``steps`` residual
        checks: (iterate, relative residual history).  The iterate is the
        one whose residual was checked last where the solve converged, and
        the one a V-cycle past it where it ran out of calls."""
        x = np.zeros_like(b)
        nb = max(float(np.linalg.norm(b)), 1e-300)
        hist = []
        for it in range(steps):
            r = b - self.A @ x
            hist.append(float(np.linalg.norm(r)) / nb)
            if converged and it == steps - 1:
                break
            x = x + self.vcycle(0, r)
        return x, hist

    def resid(self, b: np.ndarray, x: np.ndarray) -> float:
        """||b - A x|| / ||b|| in f64."""
        return float(np.linalg.norm(b - self.A @ x)
                     / max(float(np.linalg.norm(b)), 1e-300))
