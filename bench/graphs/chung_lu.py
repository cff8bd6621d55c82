"""Directed Chung–Lu graph with power-law in-degree, as a CSR.

A copy of the repository's ``chung_lu_powerlaw`` (its arithmetic, draw for
draw, so one seed gives the same graph): out-degree ``1 + Poisson(d - 1)``,
destinations drawn with probability proportional to ``(i + 1)^(-1/(θ-1))``
over a permutation of the vertices, self-loops moved one vertex on. Arcs
come out sorted by source, so the CSR needs no sort.

Parameters (``graph`` in the configuration file): ``n``,
``mean_out_degree``, ``theta``, ``seed``.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict):
    n = int(params["n"])
    d = float(params["mean_out_degree"])
    theta = float(params["theta"])
    rng = np.random.default_rng(int(params["seed"]))
    out_deg = 1 + rng.poisson(max(d - 1.0, 0.0), size=n)
    m = int(out_deg.sum())
    src = np.repeat(np.arange(n, dtype=np.int64), out_deg)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (theta - 1.0))
    perm = rng.permutation(n)
    w = w[perm.argsort()]
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    dst = np.searchsorted(cdf, rng.random(m), side="left").astype(np.int64)
    dst = np.minimum(dst, n - 1)
    loop = dst == src
    dst[loop] = (dst[loop] + 1) % n
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(out_deg, out=row_ptr[1:])
    return n, row_ptr.astype(np.int32), dst.astype(np.int32)
