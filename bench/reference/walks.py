"""Plain random walks on the device: the Monte Carlo reference for
personalized PageRank.

Each walk starts at its source and takes ``τ = min(G, t)`` uniform
out-edge moves, ``P(G = m) = p_T (1 - p_T)^m`` (a vertex without out-edges
keeps the walker); its stop vertex is one sample of the source's truncated
PPR distribution. No index, no stitching, no tally: one gather per move.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("walks", "t", "p_T"))
def endpoints(row_ptr, col_idx, sources, key, *, walks: int, t: int,
              p_T: float):
    """int32[len(sources), walks]: stop vertices of ``walks`` walks per
    source."""
    B = sources.shape[0]
    deg = row_ptr[1:] - row_ptr[:-1]
    k_len, k_move = jax.random.split(key)
    pos = jnp.broadcast_to(sources[:, None], (B, walks)).astype(jnp.int32)
    u = jax.random.uniform(k_len, (B, walks), minval=1e-30, maxval=1.0)
    tau = jnp.minimum(jnp.floor(jnp.log(u) / jnp.log1p(-p_T)),
                      t).astype(jnp.int32)

    def move(m, pos):
        d = deg[pos]
        slot = jax.random.randint(jax.random.fold_in(k_move, m), pos.shape,
                                  0, jnp.maximum(d, 1))
        nxt = col_idx[row_ptr[pos] + slot]
        return jnp.where((m < tau) & (d > 0), nxt, pos)

    return jax.lax.fori_loop(0, t, move, pos)
