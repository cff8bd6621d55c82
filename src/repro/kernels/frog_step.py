"""Pallas TPU kernel: fused plain walker superstep (apply + scatter).

One superstep of the p_s = 1 walk is four XLA ops with an HBM round-trip
between each: gather ``deg[pos]``, draw a slot, gather ``col_idx[row_ptr[pos]
+ slot]``, scatter-add the deaths.  This kernel fuses them into a single
VMEM-resident pass:

  per (vertex-block, frog-block) tile:
    deg/row_ptr/col_idx stay resident in VMEM (the whole graph block — this
    kernel targets CPU-bench-sized shards; the engine's per-shard CSR blocks
    are exactly that),
    gather degree → slot = bits % deg → gather successor → one-hot-reduce
    the died frogs into the counts tile (the frog axis is the innermost
    sequential grid dimension, so the counts tile never leaves VMEM).

Random bits default to the caller (``jax.random`` outside) — the kernel is
deterministic and byte-for-byte testable against ``ref.frog_step_ref``, the
interpret-mode determinism contract. On real TPU pass
``use_device_rng=True`` (the bits operand becomes an ``int32[1]`` seed) and
the slot draw comes from the in-kernel ``pltpu.prng_random_bits`` —
deleting the HBM bits stream without touching the step semantics.

Dangling guard: ``d_out == 0`` ⇒ the frog stays put (the self-loop
convention, see graph/csr.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_VERTEX_BLOCK = 512
DEFAULT_FROG_BLOCK = 1024


def _frog_step_kernel(
    pos_ref, die_ref, bits_ref, row_ptr_ref, col_idx_ref, deg_ref,
    counts_ref, next_ref, *, vertex_block: int, use_device_rng: bool,
):
    iv, jf = pl.program_id(0), pl.program_id(1)

    @pl.when(jf == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    pos = pos_ref[...]                                          # [BF]
    die = die_ref[...]                                          # [BF] 0/1
    # --- scatter(): draw slot, gather successor (graph VMEM-resident) ---
    deg = jnp.take(deg_ref[...], pos, axis=0)                   # [BF]
    if use_device_rng:
        # A frog block is revisited once per vertex block and next_ref is
        # rewritten each time; seeding on (seed, iv, jf) makes every visit
        # an independent uniform draw, so the surviving (last-iv) write is
        # still exactly one uniform slot per frog. The large odd multiplier
        # keeps consecutive caller seeds (superstep indices) off each
        # other's tile streams.
        pltpu.prng_seed(
            bits_ref[0] * 1000003 + iv * pl.num_programs(1) + jf)
        raw = pltpu.bitcast(pltpu.prng_random_bits(pos.shape), jnp.uint32)
        bits = (raw >> 1).astype(jnp.int32)
    else:
        bits = bits_ref[...]
    slot = bits % jnp.maximum(deg, 1)
    edge = jnp.take(row_ptr_ref[...], pos, axis=0) + slot
    nxt = jnp.take(col_idx_ref[...], edge, axis=0)
    nxt = jnp.where(deg > 0, nxt, pos)                          # dangling guard
    next_ref[...] = nxt.astype(jnp.int32)
    # --- apply() tally: died frogs accumulate into the resident tile ---
    v0 = iv * vertex_block
    local = jnp.where(die > 0, pos - v0, -1)
    onehot = local[:, None] == jnp.arange(vertex_block)[None, :]  # [BF, BV]
    counts_ref[...] += onehot.sum(axis=0).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("n_pad", "vertex_block", "frog_block", "interpret",
                     "use_device_rng"),
)
def frog_step(
    pos: jnp.ndarray,        # int32[N] — current vertex per frog
    die: jnp.ndarray,        # int32[N] — 1 where the frog dies this step
    bits: jnp.ndarray,       # int32[N] — slot bits; int32[1] seed in device-rng mode
    row_ptr: jnp.ndarray,    # int32[n + 1]
    col_idx: jnp.ndarray,    # int32[nnz]
    deg: jnp.ndarray,        # int32[n]
    n_pad: int,              # counts bins, multiple of vertex_block
    vertex_block: int = DEFAULT_VERTEX_BLOCK,
    frog_block: int = DEFAULT_FROG_BLOCK,
    *,
    interpret: bool,
    use_device_rng: bool = False,
):
    """Returns ``(next_pos int32[N], death_counts int32[n_pad])``."""
    (N,) = pos.shape
    if n_pad % vertex_block != 0:
        raise ValueError(f"n_pad={n_pad} not a multiple of {vertex_block}")
    if N % frog_block != 0:
        raise ValueError(f"N={N} not a multiple of {frog_block}")
    n1 = row_ptr.shape[0]
    nnz = col_idx.shape[0]
    nv = deg.shape[0]
    grid = (n_pad // vertex_block, N // frog_block)
    kernel = functools.partial(_frog_step_kernel, vertex_block=vertex_block,
                               use_device_rng=use_device_rng)
    whole = lambda shape: pl.BlockSpec(shape, lambda iv, jf: (0,) * len(shape))
    bits_spec = (pl.BlockSpec((1,), lambda iv, jf: (0,)) if use_device_rng
                 else pl.BlockSpec((frog_block,), lambda iv, jf: (jf,)))
    counts, nxt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((frog_block,), lambda iv, jf: (jf,)),   # pos
            pl.BlockSpec((frog_block,), lambda iv, jf: (jf,)),   # die
            bits_spec,                                           # bits | seed
            whole((n1,)),                                        # row_ptr
            whole((nnz,)),                                       # col_idx
            whole((nv,)),                                        # deg
        ],
        out_specs=(
            pl.BlockSpec((vertex_block,), lambda iv, jf: (iv,)),
            pl.BlockSpec((frog_block,), lambda iv, jf: (jf,)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.int32),
        ),
        interpret=interpret,
    )(pos, die, bits, row_ptr, col_idx, deg)
    return nxt, counts
