"""Exact PageRank vectors of a CSR graph, on the host in float64.

The walk the program samples: start where told, then at each of at most
``t`` steps stop with probability ``p_T`` or move to a uniform out-neighbour
(a vertex without out-edges keeps the walker). The stop vertex's
distribution is ``sum_{m<t} p_T (1-p_T)^m x P^m + (1-p_T)^t x P^t``;
global PageRank is the same with ``t`` large and ``x`` uniform.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def transition(row_ptr: np.ndarray, col_idx: np.ndarray):
    """``(P, stay)``: the sparse walk matrix, ``P[u, v]`` the share of
    ``u``'s out-arcs that lead to ``v``, and the vertices without out-arcs,
    whose mass stays put."""
    n = row_ptr.shape[0] - 1
    deg = np.diff(row_ptr.astype(np.int64))
    data = np.repeat(np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0), deg)
    P = sp.csr_matrix((data, col_idx, row_ptr), shape=(n, n))
    return P, (deg == 0).astype(np.float64)


def truncated_walk(row_ptr: np.ndarray, col_idx: np.ndarray, x0: np.ndarray,
                   t: int, p_T: float) -> np.ndarray:
    """Stop distribution of walks started from ``x0``, truncated at ``t``."""
    P, stay = transition(row_ptr, col_idx)
    PT = P.T                              # x P as PT @ x, with no copy
    x = np.asarray(x0, np.float64)
    acc = np.zeros_like(x)
    for m in range(t):
        acc += p_T * (1.0 - p_T) ** m * x
        x = PT @ x + x * stay
    return acc + (1.0 - p_T) ** t * x


def global_pagerank(row_ptr: np.ndarray, col_idx: np.ndarray, p_T: float,
                    tol: float = 1e-10) -> np.ndarray:
    """PageRank with uniform teleport ``p_T``: the truncated walk from the
    uniform start, carried until the mass left, ``(1-p_T)^t``, is under
    ``tol``."""
    n = row_ptr.shape[0] - 1
    t = int(np.ceil(np.log(tol) / np.log(1.0 - p_T)))
    pi = truncated_walk(row_ptr, col_idx, np.full(n, 1.0 / n), t, p_T)
    return pi / pi.sum()
