"""Graph500 Kronecker graph with LDBC Graphalytics' clean-up, as a CSR.

The generator is the Graph500 specification's (``kronecker_generator``):
``edge_factor · 2^scale`` edges, each placing its endpoints bit by bit in
the quadrants A/B/C/D, then a random permutation of the vertex labels.
Graphalytics' graph500-* datasets then keep it undirected and remove
self-loops, duplicate edges and isolated vertices; the remaining vertices
are numbered 0..n-1 in label order. Each undirected edge is stored as two
arcs, so ``nnz = 2 · edges``.

Parameters (``graph`` in the configuration file): ``scale``,
``edge_factor``, ``a``, ``b``, ``c``, ``seed``.
"""
from __future__ import annotations

import numpy as np


def edges(params: dict):
    """The spec's edge list before clean-up: ``(i, j)`` int64 arrays."""
    scale = int(params["scale"])
    m = int(params["edge_factor"]) << scale
    a, b, c = float(params["a"]), float(params["b"]), float(params["c"])
    rng = np.random.default_rng(int(params["seed"]))
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.random(m, dtype=np.float32) > ab
        j_thr = np.where(i_bit, np.float32(c_norm), np.float32(a_norm))
        j_bit = rng.random(m, dtype=np.float32) > j_thr
        i |= i_bit.astype(np.int64) << bit
        j |= j_bit.astype(np.int64) << bit
    perm = rng.permutation(1 << scale)
    return perm[i], perm[j]


def generate(params: dict):
    scale = int(params["scale"])
    i, j = edges(params)
    lo, hi = np.minimum(i, j), np.maximum(i, j)
    del i, j
    keep = lo != hi
    key = np.unique((lo[keep] << scale) | hi[keep])
    del lo, hi, keep
    lo, hi = key >> scale, key & ((1 << scale) - 1)
    del key
    used = np.zeros(1 << scale, bool)
    used[lo] = True
    used[hi] = True
    new_id = np.cumsum(used) - 1
    n = int(used.sum())
    lo, hi = new_id[lo], new_id[hi]
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    del lo, hi
    order = np.argsort(src, kind="stable")
    deg = np.bincount(src, minlength=n)
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=row_ptr[1:])
    return n, row_ptr.astype(np.int32), dst[order].astype(np.int32)
