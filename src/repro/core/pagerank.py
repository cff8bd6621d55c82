"""Exact PageRank via power iteration — the GraphLab-PR baseline.

``power_iteration`` is the continuous-water process the paper quantizes:
x ← (1 − p_T)·P·x + p_T/n. Each iteration touches every edge (O(E) work and,
distributed, O(E-cut) communication) — this is precisely the cost FrogWild
avoids. We use it (a) as ground truth π for accuracy metrics, (b) as the
reduced-iterations baseline (paper runs GraphLab PR for 1–2 iterations), and
(c) as the workload for the Pallas SpMV kernel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.graph.csr import CSRGraph, transition_edges


@functools.partial(jax.jit, static_argnames=("n", "num_iters"))
def _power_iter_coo(
    src: jnp.ndarray,
    dst: jnp.ndarray,
    w: jnp.ndarray,
    n: int,
    num_iters: int,
    p_T: float,
) -> jnp.ndarray:
    def step(x, _):
        contrib = x[src] * w
        px = jax.ops.segment_sum(contrib, dst, num_segments=n)
        x_new = (1.0 - p_T) * px + p_T / n
        return x_new, None

    x0 = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
    x, _ = jax.lax.scan(step, x0, None, length=num_iters)
    return x


def power_iteration(
    g: CSRGraph,
    num_iters: int = 50,
    p_T: float = 0.15,
    spmv: str = "coo",
) -> jnp.ndarray:
    """PageRank by power iteration.

    Args:
      g: the graph.
      num_iters: iterations. 50 ≈ machine-precision convergence at p_T=0.15
        (|λ2| ≤ 1 − p_T ⇒ error ≤ 0.85^50 ≈ 3e-4 of initial).
      p_T: teleport probability (paper uses 0.15 throughout).
      spmv: "coo" (segment-sum) or "ell" (the hybrid-ELL Pallas kernel,
        ``kernels.ops.spmv``: interpreted on CPU; on TPU it raises, since
        Mosaic refuses the kernel's gather — see ``ops.TPU_REFUSED``).
    """
    if spmv == "coo":
        src, dst, w = transition_edges(g)
        return _power_iter_coo(src, dst, w, g.n, num_iters, p_T)
    elif spmv == "ell":
        from repro.graph.partition import to_ell
        from repro.kernels import ops

        ell = to_ell(g, K=32)
        x = jnp.full((g.n,), 1.0 / g.n, dtype=jnp.float32)

        def step(x, _):
            px = ops.spmv(ell, x)[: g.n]
            return (1.0 - p_T) * px + p_T / g.n, None

        x, _ = jax.lax.scan(step, x, None, length=num_iters)
        return x
    raise ValueError(f"unknown spmv impl {spmv!r}")


def reduced_iteration_baseline(
    g: CSRGraph, num_iters: int, p_T: float = 0.15
) -> jnp.ndarray:
    """The paper's GraphLab-PR comparison point: run PR for 1–2 iterations
    only ("a good top-k approximation, much faster than convergence")."""
    return power_iteration(g, num_iters=num_iters, p_T=p_T)


def pagerank_residual(g: CSRGraph, x: jnp.ndarray, p_T: float = 0.15) -> jnp.ndarray:
    """‖Qx − x‖₁ — fixed-point residual, used by convergence tests."""
    src, dst, w = transition_edges(g)
    px = jax.ops.segment_sum(x[src] * w, dst, num_segments=g.n)
    qx = (1.0 - p_T) * px + p_T / g.n
    return jnp.abs(qx - x).sum()
