"""Pallas TPU kernels: fused walk-segment gather-and-tally (query stitch).

The online query engine (``repro/query``) composes precomputed length-L walk
segments: one stitch round replaces L walker supersteps with a single gather
from the dense endpoint slab ``endpoints[n, R]`` — ``next = endpoints[pos,
slot]`` for a uniform segment slot — and walks whose step budget is exhausted
are tallied into the per-vertex counter. Written as separate XLA ops that is
a gather, a modulo, and a scatter-add with an HBM round-trip between each;
these kernels fuse them into one VMEM-resident pass, structurally the twin
of ``frog_step.py``.

Two variants share the tile schedule:

* :func:`stitch_step` — the **global** kernel: the whole flat slab is
  resident (bench-/single-device-sized slabs, same budget assumption as
  ``frog_step``'s graph block).
* :func:`stitch_step_local` — the **local-index** kernel for sharded
  serving: the resident slab is one shard's ``[shard_size, R]`` block and a
  ``base`` vertex offset rebases the gather. Walks the shard does not own
  (``pos ∉ [base, base + shard_size)``) contribute ``0`` to ``next`` and
  nothing to the tally, so per-shard outputs compose across shards by a
  plain ``psum`` (mesh) or host-side sum (single device): each walk is
  owned by exactly one shard. Per-device slab VMEM drops from ``4nR`` to
  ``4nR/S`` — the Twitter-scale serving answer.

Random bits default to the caller (``jax.random`` outside the kernel), so
the kernels are deterministic and byte-for-byte testable against the
``ref.py`` oracles — the interpret-mode determinism contract. On real TPU
pass ``use_device_rng=True`` (third operand becomes a seed) and the slot
draw comes from the in-kernel ``pltpu.prng_random_bits``, eliminating the
HBM bits stream without touching the stitch semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_VERTEX_BLOCK = 512
DEFAULT_WALK_BLOCK = 1024


def _slot_bits(bits_ref, jw: int, shape, use_device_rng: bool):
    """Uniform nonnegative int32 bits for the slot draw.

    Caller mode reads the precomputed bits tile; device mode seeds the
    per-core PRNG on (seed, walk-block) — the gather runs once per walk
    block (``iv == 0``), so one draw per block keeps the walk's slot
    consistent across the whole grid. The seed is spread by a large odd
    multiplier so consecutive caller seeds (round indices) never share a
    block's stream.
    """
    if not use_device_rng:
        return bits_ref[...]
    pltpu.prng_seed(bits_ref[0] * 1000003 + jw)
    raw = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return (raw >> 1).astype(jnp.int32)


def _stitch_kernel(
    pos_ref, stop_ref, bits_ref, endpoints_ref,
    counts_ref, next_ref, *, vertex_block: int, R: int, use_device_rng: bool,
):
    iv, jw = pl.program_id(0), pl.program_id(1)

    @pl.when(jw == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    pos = pos_ref[...]                                          # [BW]
    stop = stop_ref[...]                                        # [BW] 0/1

    # --- stitch: draw a segment slot, gather its endpoint (slab resident).
    # Only the tally below depends on the vertex-block index; the gather is
    # done once per walk block (its tile is first visited at iv == 0 and the
    # written block round-trips through HBM across later iv revisits, the
    # same read-modify-write contract the counts accumulation relies on).
    @pl.when(iv == 0)
    def _gather():
        slot = _slot_bits(bits_ref, jw, pos.shape, use_device_rng) % R
        nxt = jnp.take(endpoints_ref[...], pos * R + slot, axis=0)
        next_ref[...] = nxt.astype(jnp.int32)
    # --- tally: stopped walks accumulate into the resident counts tile ---
    v0 = iv * vertex_block
    local = jnp.where(stop > 0, pos - v0, -1)
    onehot = local[:, None] == jnp.arange(vertex_block)[None, :]  # [BW, BV]
    counts_ref[...] += onehot.sum(axis=0).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("R", "n_pad", "vertex_block", "walk_block", "interpret",
                     "use_device_rng"),
)
def stitch_step(
    pos: jnp.ndarray,        # int32[W] — current vertex per walk
    stop: jnp.ndarray,       # int32[W] — 1 where the walk halts this round
    bits: jnp.ndarray,       # int32[W] — slot bits; int32[1] seed in device-rng mode
    endpoints: jnp.ndarray,  # int32[n · R] — flat walk-segment endpoint slab
    R: int,                  # segments per vertex
    n_pad: int,              # counts bins, multiple of vertex_block
    vertex_block: int = DEFAULT_VERTEX_BLOCK,
    walk_block: int = DEFAULT_WALK_BLOCK,
    *,
    interpret: bool,
    use_device_rng: bool = False,
):
    """Returns ``(next_pos int32[W], stop_counts int32[n_pad])``."""
    (W,) = pos.shape
    if n_pad % vertex_block != 0:
        raise ValueError(f"n_pad={n_pad} not a multiple of {vertex_block}")
    if W % walk_block != 0:
        raise ValueError(f"W={W} not a multiple of {walk_block}")
    nR = endpoints.shape[0]
    grid = (n_pad // vertex_block, W // walk_block)
    kernel = functools.partial(
        _stitch_kernel, vertex_block=vertex_block, R=R,
        use_device_rng=use_device_rng)
    bits_spec = (pl.BlockSpec((1,), lambda iv, jw: (0,)) if use_device_rng
                 else pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)))
    counts, nxt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)),   # pos
            pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)),   # stop
            bits_spec,                                           # bits | seed
            pl.BlockSpec((nR,), lambda iv, jw: (0,)),            # endpoints
        ],
        out_specs=(
            pl.BlockSpec((vertex_block,), lambda iv, jw: (iv,)),
            pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((W,), jnp.int32),
        ),
        interpret=interpret,
    )(pos, stop, bits, endpoints)
    return nxt, counts


def _stitch_gather_kernel(
    pos_ref, bits_ref, endpoints_ref, next_ref, *, R: int,
    use_device_rng: bool,
):
    jw = pl.program_id(0)
    pos = pos_ref[...]
    slot = _slot_bits(bits_ref, jw, pos.shape, use_device_rng) % R
    nxt = jnp.take(endpoints_ref[...], pos * R + slot, axis=0)
    next_ref[...] = nxt.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("R", "walk_block", "interpret", "use_device_rng"),
)
def stitch_gather(
    pos: jnp.ndarray,        # int32[W] — current vertex per walk
    bits: jnp.ndarray,       # int32[W] — slot bits; int32[1] seed in device-rng mode
    endpoints: jnp.ndarray,  # int32[n · R] — flat walk-segment endpoint slab
    R: int,
    walk_block: int = DEFAULT_WALK_BLOCK,
    *,
    interpret: bool,
    use_device_rng: bool = False,
):
    """Gather-only stitch round → ``next_pos int32[W]``.

    The tally-free twin of :func:`stitch_step` for callers that defer the
    histogram to one final pass over the wave's end positions (the
    scheduler's fused ``lax.scan`` wave): no per-round counts output means
    a lean scan carry and a 1-D grid (walk blocks only). The slot draw is
    identical to :func:`stitch_step`'s (same ``_slot_bits`` per walk
    block), so the gathered positions are byte-identical.
    """
    (W,) = pos.shape
    if W % walk_block != 0:
        raise ValueError(f"W={W} not a multiple of {walk_block}")
    nR = endpoints.shape[0]
    grid = (W // walk_block,)
    kernel = functools.partial(
        _stitch_gather_kernel, R=R, use_device_rng=use_device_rng)
    bits_spec = (pl.BlockSpec((1,), lambda jw: (0,)) if use_device_rng
                 else pl.BlockSpec((walk_block,), lambda jw: (jw,)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((walk_block,), lambda jw: (jw,)),        # pos
            bits_spec,                                            # bits | seed
            pl.BlockSpec((nR,), lambda jw: (0,)),                 # endpoints
        ],
        out_specs=pl.BlockSpec((walk_block,), lambda jw: (jw,)),
        out_shape=jax.ShapeDtypeStruct((W,), jnp.int32),
        interpret=interpret,
    )(pos, bits, endpoints)


def _stitch_local_kernel(
    pos_ref, stop_ref, bits_ref, base_ref, block_ref,
    counts_ref, next_ref, *, vertex_block: int, R: int, shard_size: int,
    use_device_rng: bool,
):
    iv, jw = pl.program_id(0), pl.program_id(1)

    @pl.when(jw == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    pos = pos_ref[...]                                          # [BW] global
    stop = stop_ref[...]                                        # [BW] 0/1
    local = pos - base_ref[0]                                   # shard-local
    owned = (local >= 0) & (local < shard_size)

    # --- stitch: gather from this shard's slab block only; walks owned by
    # other shards contribute the psum/host-sum identity 0.
    @pl.when(iv == 0)
    def _gather():
        slot = _slot_bits(bits_ref, jw, pos.shape, use_device_rng) % R
        li = jnp.clip(local, 0, shard_size - 1)
        nxt = jnp.take(block_ref[...], li * R + slot, axis=0)
        next_ref[...] = jnp.where(owned, nxt, 0).astype(jnp.int32)
    # --- tally: owned stopped walks into the shard-local counts tile ---
    v0 = iv * vertex_block
    lb = jnp.where((stop > 0) & owned, local - v0, -1)
    onehot = lb[:, None] == jnp.arange(vertex_block)[None, :]   # [BW, BV]
    counts_ref[...] += onehot.sum(axis=0).astype(jnp.int32)


def _stitch_gather_local_kernel(
    pos_ref, bits_ref, base_ref, block_ref, next_ref, *, R: int,
    shard_size: int, use_device_rng: bool,
):
    jw = pl.program_id(0)
    pos = pos_ref[...]
    local = pos - base_ref[0]
    owned = (local >= 0) & (local < shard_size)
    slot = _slot_bits(bits_ref, jw, pos.shape, use_device_rng) % R
    li = jnp.clip(local, 0, shard_size - 1)
    nxt = jnp.take(block_ref[...], li * R + slot, axis=0)
    next_ref[...] = jnp.where(owned, nxt, 0).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("R", "shard_size", "walk_block", "interpret",
                     "use_device_rng"),
)
def stitch_gather_local(
    pos: jnp.ndarray,        # int32[W] — current *global* vertex per walk
    bits: jnp.ndarray,       # int32[W] — slot bits; int32[1] seed in device-rng mode
    base: jnp.ndarray,       # int32[1] — first global vertex this shard owns
    block: jnp.ndarray,      # int32[shard_size · R] — this shard's flat slab block
    R: int,
    shard_size: int,
    walk_block: int = DEFAULT_WALK_BLOCK,
    *,
    interpret: bool,
    use_device_rng: bool = False,
):
    """Gather-only per-shard stitch round → ``next_contrib int32[W]``.

    The tally-free twin of :func:`stitch_step_local` (see
    :func:`stitch_gather`): owned walks gather from the local block, the
    rest contribute the additive identity 0, and the per-round tally is
    simply not computed — the wave histograms once over final positions.
    """
    (W,) = pos.shape
    if W % walk_block != 0:
        raise ValueError(f"W={W} not a multiple of {walk_block}")
    szR = block.shape[0]
    grid = (W // walk_block,)
    kernel = functools.partial(
        _stitch_gather_local_kernel, R=R, shard_size=shard_size,
        use_device_rng=use_device_rng)
    bits_spec = (pl.BlockSpec((1,), lambda jw: (0,)) if use_device_rng
                 else pl.BlockSpec((walk_block,), lambda jw: (jw,)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((walk_block,), lambda jw: (jw,)),        # pos
            bits_spec,                                            # bits | seed
            pl.BlockSpec((1,), lambda jw: (0,)),                  # base
            pl.BlockSpec((szR,), lambda jw: (0,)),                # slab block
        ],
        out_specs=pl.BlockSpec((walk_block,), lambda jw: (jw,)),
        out_shape=jax.ShapeDtypeStruct((W,), jnp.int32),
        interpret=interpret,
    )(pos, bits, base, block)


@functools.partial(
    jax.jit,
    static_argnames=("R", "shard_size", "sz_pad", "vertex_block",
                     "walk_block", "interpret", "use_device_rng"),
)
def stitch_step_local(
    pos: jnp.ndarray,        # int32[W] — current *global* vertex per walk
    stop: jnp.ndarray,       # int32[W] — 1 where the walk halts this round
    bits: jnp.ndarray,       # int32[W] — slot bits; int32[1] seed in device-rng mode
    base: jnp.ndarray,       # int32[1] — first global vertex this shard owns
    block: jnp.ndarray,      # int32[shard_size · R] — this shard's flat slab block
    R: int,
    shard_size: int,
    sz_pad: int,             # local counts bins, multiple of vertex_block
    vertex_block: int = DEFAULT_VERTEX_BLOCK,
    walk_block: int = DEFAULT_WALK_BLOCK,
    *,
    interpret: bool,
    use_device_rng: bool = False,
):
    """Per-shard stitch round against a local slab block.

    Returns ``(next_contrib int32[W], stop_counts int32[sz_pad])`` where
    ``next_contrib`` is ``endpoints[pos, slot]`` for owned walks and ``0``
    otherwise, and the tally covers only vertices in
    ``[base, base + shard_size)`` rebased to local bins — both compose
    across shards by summation.
    """
    (W,) = pos.shape
    if sz_pad % vertex_block != 0:
        raise ValueError(f"sz_pad={sz_pad} not a multiple of {vertex_block}")
    if W % walk_block != 0:
        raise ValueError(f"W={W} not a multiple of {walk_block}")
    szR = block.shape[0]
    grid = (sz_pad // vertex_block, W // walk_block)
    kernel = functools.partial(
        _stitch_local_kernel, vertex_block=vertex_block, R=R,
        shard_size=shard_size, use_device_rng=use_device_rng)
    bits_spec = (pl.BlockSpec((1,), lambda iv, jw: (0,)) if use_device_rng
                 else pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)))
    counts, nxt = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)),   # pos
            pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)),   # stop
            bits_spec,                                           # bits | seed
            pl.BlockSpec((1,), lambda iv, jw: (0,)),             # base
            pl.BlockSpec((szR,), lambda iv, jw: (0,)),           # slab block
        ],
        out_specs=(
            pl.BlockSpec((vertex_block,), lambda iv, jw: (iv,)),
            pl.BlockSpec((walk_block,), lambda iv, jw: (jw,)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((sz_pad,), jnp.int32),
            jax.ShapeDtypeStruct((W,), jnp.int32),
        ),
        interpret=interpret,
    )(pos, stop, bits, base, block)
    return nxt, counts
