"""Plain float32 ALS, independent of the code under test: per entity,
the normal equations of its interactions, solved by LAPACK.

Explicit: ALS-WR (Zhou et al. 2008), A = Σ y yᵀ + λ·n_e·I, b = Σ r·y.
Implicit: Hu, Koren & Volinsky 2008 as MLlib's ``trainImplicit`` runs
it, confidence c = 1 + α·r, preference 1 for every observed pair:
A = YᵀY + Σ α·r·y yᵀ + λ·n_e·I, b = Σ (1 + α·r)·y.
Entities with no interaction keep zero factors. (Explicit case copied
from ``chip_smoke.py numpy_als``.)
"""

from __future__ import annotations

import numpy as np


def _half(idx_self, idx_other, vals, n_self, F, reg, implicit, alpha):
    order = np.argsort(idx_self, kind="stable")
    s, o, v = idx_self[order], idx_other[order], vals[order]
    bounds = np.flatnonzero(np.diff(s)) + 1
    starts = np.concatenate([[0], bounds])
    stops = np.concatenate([bounds, [len(s)]])
    k = F.shape[1]
    X = np.zeros((n_self, k), np.float32)
    eye = np.eye(k, dtype=np.float32)
    G = (F.T @ F).astype(np.float32) if implicit else None
    for a, b in zip(starts, stops):
        Fe = F[o[a:b]]
        r = v[a:b]
        ridge = np.float32(reg * (b - a)) * eye
        if implicit:
            A = G + (Fe * (alpha * r)[:, None]).T @ Fe + ridge
            rhs = Fe.T @ (1.0 + alpha * r)
        else:
            A = Fe.T @ Fe + ridge
            rhs = Fe.T @ r
        X[s[a]] = np.linalg.solve(A.astype(np.float32),
                                  rhs.astype(np.float32))
    return X


def numpy_als(users, items, values, n_users, n_items, V0, iterations, reg,
              implicit=False, alpha=1.0):
    values = values.astype(np.float32)
    alpha = np.float32(alpha)
    V = V0.astype(np.float32)
    U = np.zeros((n_users, V.shape[1]), np.float32)
    for _ in range(iterations):
        U = _half(users, items, values, n_users, V, reg, implicit, alpha)
        V = _half(items, users, values, n_items, U, reg, implicit, alpha)
    return U, V


def rmse(U, V, users, items, values) -> float:
    se = 0.0
    for a in range(0, len(values), 1_000_000):
        b = a + 1_000_000
        p = np.einsum("nk,nk->n", U[users[a:b]], V[items[a:b]])
        se += float(np.sum((p - values[a:b]) ** 2, dtype=np.float64))
    return (se / len(values)) ** 0.5


def mean_percentile_rank(U, V, users, items) -> float:
    """Hu–Koren–Volinsky's ranking measure for pairs the model never
    saw: the percentile (0 = top, 100 = bottom) at which each user's
    held-out item stands among ALL items ranked by score, averaged.
    Random scores give 50."""
    ranks = []
    for a in range(0, len(users), 256):
        u, i = users[a:a + 256], items[a:a + 256]
        scores = U[u] @ V.T                              # (256, n_items)
        own = scores[np.arange(len(u)), i]
        ranks.append((scores > own[:, None]).sum(axis=1) / V.shape[0])
    return float(100.0 * np.mean(np.concatenate(ranks)))
