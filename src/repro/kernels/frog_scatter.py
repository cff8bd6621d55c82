"""Pallas TPU kernel: frog-count histogram (the apply() tally).

``counts[v] = #{f : dest[f] == v}`` — the scatter-add at the heart of both
the walker oracle (tallying stopped frogs) and the engine's frontier build.
Scatter is hostile to TPUs (no HBM atomics), so we restructure it as a
**compare-and-reduce over a 2-D grid**: vertex blocks × frog blocks, each
tile materializing a one-hot match matrix and reducing over the frog axis.
The frog axis is the innermost (sequential) grid dimension, accumulating into
the output tile that stays resident in VMEM — the classic TPU histogram
pattern (work O(N·n/BV·BF⁻¹·…) = O(N · num_vertex_blocks), worth it because
N ≪ E and the match matrix hits the VPU at full width).

Validated against ``ref.frog_count_ref`` over shapes and index skews.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_VERTEX_BLOCK = 1024
DEFAULT_FROG_BLOCK = 1024


def _frog_scatter_kernel(dest_ref, counts_ref, *, vertex_block: int):
    jf = pl.program_id(1)

    @pl.when(jf == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    iv = pl.program_id(0)
    v0 = iv * vertex_block
    dest = dest_ref[...]                                        # [BF]
    local = dest - v0                                           # [BF]
    onehot = local[:, None] == jnp.arange(vertex_block)[None, :]  # [BF, BV]
    counts_ref[...] += onehot.sum(axis=0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("n", "vertex_block", "frog_block", "interpret")
)
def frog_count(
    dest: jnp.ndarray,          # int32[N] — destination vertex per frog
    n: int,                     # number of vertices (padded multiple of vertex_block)
    vertex_block: int = DEFAULT_VERTEX_BLOCK,
    frog_block: int = DEFAULT_FROG_BLOCK,
    *,
    interpret: bool,
) -> jnp.ndarray:
    (N,) = dest.shape
    if n % vertex_block != 0:
        raise ValueError(f"n={n} must be a multiple of vertex_block={vertex_block}")
    if N % frog_block != 0:
        raise ValueError(f"N={N} must be a multiple of frog_block={frog_block}")
    grid = (n // vertex_block, N // frog_block)
    kernel = functools.partial(_frog_scatter_kernel, vertex_block=vertex_block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((frog_block,), lambda iv, jf: (jf,))],
        out_specs=pl.BlockSpec((vertex_block,), lambda iv, jf: (iv,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(dest)
