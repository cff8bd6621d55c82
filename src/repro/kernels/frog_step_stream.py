"""Pallas TPU kernel: HBM-streaming walker superstep (sorted-frog pipeline).

The resident ``frog_step.py`` kernel keeps the *entire* graph block
(``row_ptr``/``col_idx``/``deg``) in VMEM, which caps shard size at a few MB
of CSR — far below the paper's Twitter-scale shards. This kernel lifts that:
the graph lives in HBM as **uniform per-vertex-block slabs** (:class:`
BlockedCSR`) and only the slab of the vertex block currently being processed
is brought into VMEM, driven by a scalar-prefetched schedule:

  1. (XLA prologue, ``ops.frog_step(impl="stream")``) frogs are argsorted by
     vertex and laid out so each ``frog_block`` belongs to exactly one
     ``vertex_block`` (per-block segments padded to a ``frog_block``
     multiple with inert frogs);
  2. the grid iterates over sorted frog blocks; the scalar-prefetched
     ``blk_vid[b]`` array drives the BlockSpec index maps, so the Pallas
     pipeline DMAs exactly the CSR slab (local row offsets, degrees, edge
     destinations) of the vertex block that frog block needs — and because
     sorted frog blocks visit vertex blocks in nondecreasing order, the
     pipeline's revisit elision means **each graph slab streams HBM → VMEM
     at most once per superstep**, double-buffered against compute;
  3. the per-block death tally is a **sort-compacted segment sum** (prefix
     sum over the die flags + one ``searchsorted`` of the block's bin edges
     into the already-sorted positions) instead of the resident kernel's
     O(frog_block · vertex_block) one-hot tile;
  4. the counts tile for vertex block ``v`` stays VMEM-resident across the
     consecutive frog blocks that map to it and is flushed when the grid
     moves on (never revisited — the sort guarantees contiguity).

VMEM working set per grid step: ``4 · (3·BV + E_blk + 5·BF)`` bytes (three
BV-slabs + edge slab + pos/die/bits/next/prefix frog tiles) — bounded by the
block shapes, **independent of n and nnz**; HBM holds the full
``4 · (2·n_pad + num_vb · E_blk + 5·P_pad)`` working set. The resident
kernel needs ``4 · (2n + nnz)`` bytes of VMEM for the graph alone.

Random bits default to the caller (``jax.random`` outside), keeping the
kernel deterministic and byte-for-byte testable against
``ref.frog_step_ref`` (the ops wrapper unsorts the outputs) — the
interpret-mode determinism contract. On real TPU pass
``use_device_rng=True`` (the bits operand becomes an ``int32[1]`` seed):
the slot draw then comes from the in-kernel ``pltpu.prng_random_bits``
seeded per frog block, and the HBM bits stream disappears.

Dangling guard: ``d_out == 0`` ⇒ the frog stays put (the self-loop
convention, see graph/csr.py:uniform_successor — asserted identical across
implementations by tests/test_stream_step.py).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_VERTEX_BLOCK = 512
DEFAULT_FROG_BLOCK = 1024


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["row_off", "deg", "col"],
                   meta_fields=["vertex_block"])
@dataclasses.dataclass(frozen=True)
class BlockedCSR:
    """CSR re-laid-out as uniform per-vertex-block slabs (the DMA unit).

    Attributes:
      vertex_block: BV — vertices per slab.
      row_off: int32[num_vb, BV] — offset of each vertex's edges *within its
        block's edge slab* (``row_ptr[v] - row_ptr[v0]``).
      deg:     int32[num_vb, BV] — out-degrees (0 for pad vertices ≥ n).
      col:     int32[num_vb, E_blk] — edge destinations (global vertex ids),
        each block's edges packed at the front, tail untouched garbage that
        no in-range ``row_off + slot`` ever reads.

    ``E_blk`` (slab width) is the max per-block nnz — static, so every slab
    DMA has the same shape and the Pallas pipeline can double-buffer it.
    """

    vertex_block: int
    row_off: jnp.ndarray
    deg: jnp.ndarray
    col: jnp.ndarray

    @property
    def num_blocks(self) -> int:
        return int(self.row_off.shape[0])

    @property
    def n_pad(self) -> int:
        return self.num_blocks * self.vertex_block

    @property
    def e_blk(self) -> int:
        return int(self.col.shape[1])


def max_block_nnz(row_ptr, n: int, vertex_block: int) -> int:
    """Max per-vertex-block edge count — the natural slab width for
    :func:`block_csr` (exposed so multi-shard builders can force one
    uniform width across shards)."""
    rp = np.asarray(row_ptr, dtype=np.int64)
    bv = min(vertex_block, max(8, n))
    num_vb = -(-n // bv)
    block_nnz = rp[np.minimum(np.arange(1, num_vb + 1) * bv, n)] - rp[
        np.minimum(np.arange(num_vb) * bv, n)]
    return int(max(1, block_nnz.max()))


def round_e_blk(natural: int) -> int:
    """Slab-width alignment rule (8-lane multiples) — the single definition
    shared by :func:`block_csr`'s default and the engine's cross-shard
    forced width."""
    return max(8, int(np.ceil(natural / 8) * 8))


def block_csr(
    row_ptr, col_idx, deg, n: int,
    vertex_block: int = DEFAULT_VERTEX_BLOCK,
    e_blk: int | None = None,
) -> BlockedCSR:
    """Builds the uniform-slab layout from CSR arrays (host-side, O(nnz)).

    The inputs must be concrete (the layout's slab width is a static shape);
    callers inside traced code pass a prebuilt ``BlockedCSR`` to
    ``ops.frog_step`` instead. ``e_blk`` forces a slab width (≥ the natural
    :func:`max_block_nnz`) — how the engine keeps one width across shards.
    """
    rp = np.asarray(row_ptr, dtype=np.int64)
    col = np.asarray(col_idx, dtype=np.int32)
    dg = np.asarray(deg, dtype=np.int32)
    bv = min(vertex_block, max(8, n))
    num_vb = -(-n // bv)
    n_pad = num_vb * bv
    natural = max_block_nnz(row_ptr, n, vertex_block)
    if e_blk is None:
        e_blk = round_e_blk(natural)
    elif e_blk < natural:
        raise ValueError(f"e_blk={e_blk} < max per-block nnz {natural}")
    row_off = np.zeros((num_vb, bv), dtype=np.int32)
    deg_b = np.zeros((num_vb, bv), dtype=np.int32)
    col_b = np.zeros((num_vb, e_blk), dtype=np.int32)
    for i in range(num_vb):
        v0, v1 = i * bv, min((i + 1) * bv, n)
        lo, hi = int(rp[v0]), int(rp[v1])
        row_off[i, : v1 - v0] = rp[v0:v1] - lo
        deg_b[i, : v1 - v0] = dg[v0:v1]
        col_b[i, : hi - lo] = col[lo:hi]
    return BlockedCSR(
        vertex_block=bv,
        row_off=jnp.asarray(row_off),
        deg=jnp.asarray(deg_b),
        col=jnp.asarray(col_b),
    )


def _stream_kernel(
    vid_ref,                      # scalar prefetch: int32[num_fb]
    pos_ref, die_ref, bits_ref,   # int32[BF] — sorted/padded frog tiles
    row_off_ref, deg_ref, col_ref,  # (1, BV), (1, BV), (1, E_blk) slabs
    counts_ref, next_ref,         # int32[BV], int32[BF]
    *, vertex_block: int, use_device_rng: bool,
):
    b = pl.program_id(0)
    vid = vid_ref[b]
    # First frog block of this vertex block → fresh counts tile. (The tile
    # stays resident across the consecutive blocks with the same vid and is
    # flushed exactly once when the grid moves on — sorted order guarantees
    # a vid never comes back.)
    first = jnp.logical_or(b == 0, vid != vid_ref[jnp.maximum(b - 1, 0)])

    @pl.when(first)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    pos = pos_ref[...]                                          # [BF] global
    die = die_ref[...]                                          # [BF] 0/1
    v0 = vid * vertex_block
    local = pos - v0                                            # in [0, BV)
    # --- scatter(): draw slot, gather successor from the streamed slab ---
    d = jnp.take(deg_ref[0], local, axis=0)
    if use_device_rng:
        # Each frog block is visited exactly once (the grid IS the sorted
        # frog-block sequence), so one per-block seed suffices; the large
        # odd multiplier keeps consecutive caller seeds (superstep indices)
        # off each other's block streams.
        pltpu.prng_seed(bits_ref[0] * 1000003 + b)
        raw = pltpu.bitcast(pltpu.prng_random_bits(pos.shape), jnp.uint32)
        bits = (raw >> 1).astype(jnp.int32)
    else:
        bits = bits_ref[...]
    slot = bits % jnp.maximum(d, 1)
    edge = jnp.take(row_off_ref[0], local, axis=0) + slot
    nxt = jnp.take(col_ref[0], edge, axis=0)
    next_ref[...] = jnp.where(d > 0, nxt, pos).astype(jnp.int32)
    # --- apply() tally: sort-compacted segment sum over the sorted tile ---
    # pos is sorted within the block, so per-bin death counts are prefix-sum
    # differences at searchsorted bin edges: O(BF + BV·log BF) work instead
    # of the resident kernel's O(BF·BV) one-hot tile.
    prefix = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(die.astype(jnp.int32))])
    edges = v0 + jnp.arange(vertex_block + 1, dtype=jnp.int32)
    bounds = jnp.searchsorted(pos, edges, side="left").astype(jnp.int32)
    counts_ref[...] += (
        jnp.take(prefix, bounds[1:], axis=0)
        - jnp.take(prefix, bounds[:-1], axis=0)
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_fb", "vertex_block", "frog_block", "interpret",
                     "use_device_rng"),
)
def frog_step_stream_sorted(
    pos_p: jnp.ndarray,       # int32[P_pad] — block-sorted, padded positions
    die_p: jnp.ndarray,       # int32[P_pad] — 0 on padding slots
    bits_p: jnp.ndarray,      # int32[P_pad]; int32[1] seed in device-rng mode
    blk_vid: jnp.ndarray,     # int32[num_fb] — vertex block per frog block
    row_off: jnp.ndarray,     # int32[num_vb, BV]
    deg: jnp.ndarray,         # int32[num_vb, BV]
    col: jnp.ndarray,         # int32[num_vb, E_blk]
    num_fb: int,
    vertex_block: int = DEFAULT_VERTEX_BLOCK,
    frog_block: int = DEFAULT_FROG_BLOCK,
    *,
    interpret: bool,
    use_device_rng: bool = False,
):
    """Streamed superstep over pre-sorted frogs.

    Returns ``(next int32[P_pad], counts int32[n_pad])`` in the *sorted*
    frog order; ``ops.frog_step`` owns the sort/unsort and the zeroing of
    never-visited count blocks. ``blk_vid`` must be nondecreasing.
    """
    num_vb = row_off.shape[0]
    e_blk = col.shape[1]
    bits_spec = (pl.BlockSpec((1,), lambda b, vid: (0,)) if use_device_rng
                 else pl.BlockSpec((frog_block,), lambda b, vid: (b,)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_fb,),
        in_specs=[
            pl.BlockSpec((frog_block,), lambda b, vid: (b,)),       # pos
            pl.BlockSpec((frog_block,), lambda b, vid: (b,)),       # die
            bits_spec,                                              # bits | seed
            # Slabs are viewed [num_vb, 1, ·]: Mosaic wants a block's last
            # two dims divisible by (8, 128) or equal to the array's, and a
            # (1, BV) block of a [num_vb, BV] array is neither.
            pl.BlockSpec((None, 1, vertex_block),
                         lambda b, vid: (vid[b], 0, 0)),
            pl.BlockSpec((None, 1, vertex_block),
                         lambda b, vid: (vid[b], 0, 0)),
            pl.BlockSpec((None, 1, e_blk), lambda b, vid: (vid[b], 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((vertex_block,), lambda b, vid: (vid[b],)),
            pl.BlockSpec((frog_block,), lambda b, vid: (b,)),
        ),
    )
    kernel = functools.partial(_stream_kernel, vertex_block=vertex_block,
                               use_device_rng=use_device_rng)
    counts, nxt = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((num_vb * vertex_block,), jnp.int32),
            jax.ShapeDtypeStruct((pos_p.shape[0],), jnp.int32),
        ),
        interpret=interpret,
    )(blk_vid, pos_p, die_p, bits_p, row_off[:, None], deg[:, None],
      col[:, None])
    return nxt, counts
