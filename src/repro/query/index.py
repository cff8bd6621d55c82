"""Offline walk-segment index (the PowerWalk precompute, FrogWild flavour).

For every vertex ``v`` the index stores ``R`` independent endpoints of plain
(p_s = 1, no-death) random walks of exactly ``L`` steps started at ``v`` —
a dense ``int32[n, R]`` slab. Each stored endpoint is an exact sample of
the L-step transition kernel ``P^L(· | v)``, so the online engine can
replace L walker supersteps with one gather from row ``v``. Sizing note:
pick ``R ≥ t/L`` (stitches per walk) — the engine's slot rotation then
guarantees a walk never rereads a cell and its composed marginal is exact;
cell sharing across walks only adds variance (tests/test_query.py checks
the distribution statistically).

The full slab is ``4·n·R`` bytes — the Twitter-scale memory hog — so the
index exists in two forms:

* :class:`WalkIndex` — the dense slab (single-device serving, small n);
* :class:`ShardedWalkIndex` — the slab as ``num_shards`` range-partitioned
  ``[shard_size, R]`` blocks that are **never concatenated on a device**:
  the sharded :class:`~repro.query.scheduler.QueryScheduler` wave gathers
  each walk's next segment from the block of the shard that owns its
  current vertex, so peak per-device slab memory is ``4·n·R/S`` bytes.

Build is sharded via ``graph/partition.py``; the per-shard step program is
shared between two drivers built on the one shard-execution layer
(``distributed/runtime.py``):

* :func:`build_walk_index` — the host shard loop (single device);
* :func:`build_walk_index_sharded` — the same per-shard program as one
  ``shard_map`` over the runtime's ``"vertex"`` mesh axis: every device
  materializes only its own ``[shard_size, R]`` slab block, and per-shard
  blocks are persisted independently.

The inner step is a batched variant of the walker superstep and can run
through the fused Pallas kernels (``step_impl="pallas"`` for the
VMEM-resident kernel, ``"stream"`` for the HBM-streaming sorted-frog
kernel, ``"auto"`` to pick by VMEM budget).

**Per-vertex key streams (dynamic-graph contract).** A segment's
randomness is derived per *(vertex, step)* — ``fold_in(fold_in(key, v),
l)`` drawing ``R`` slot bits at shape ``(R,)`` — never per batch shape,
so a row's endpoints are byte-identical whether walked in a full-shard
build, a ``shard_map`` build, or an arbitrary row/slot subset. This is
what lets ``repro.dynamic.refresh_walk_index`` rebuild exactly the
invalidated segments of a mutated graph and still produce a slab
byte-identical to a from-scratch build at the new epoch. The build scan
additionally records, per segment, a bitmask over ``32·_MASK_WORDS``
vertex-id blocks of every vertex whose out-edge the segment consumed —
stored as ``visited_blocks`` (uint32[n, R, W]) — so staleness under a
mutation batch is one vectorized bitwise check, not a re-walk.

Persistence goes through ``checkpoint/`` atomic step directories, so index
builds inherit the crash-safety and GC story of model checkpoints. A
sharded build writes one checkpoint dir per shard (``<dir>/shard_<s>/
step_<k>/``, the runtime's per-shard round-trip); :func:`load_walk_index`
detects the sharded layout and either reassembles the slab
(``reassemble=True``, the legacy reader) or hands the per-shard blocks
straight to the serving layer (``reassemble=False`` →
:class:`ShardedWalkIndex` — no device ever sees the full slab).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.checkpoint import (CheckpointCorruptError, latest_step,
                              save_checkpoint)
from repro.config import WalkIndexConfig, warn_deprecated
from repro.distributed.runtime import (ShardRuntime, list_shard_dirs,
                                       load_checkpoint_tree,
                                       load_shard_checkpoints,
                                       quarantine_shard_dir,
                                       save_shard_checkpoint, shard_dir)
from repro.graph.csr import CSRGraph, uniform_successor
from repro.graph.partition import partition_graph

# WalkIndexConfig is defined in repro/config.py (the layered-config module —
# single definition per flag) and re-exported here for back-compat.

# Per-segment visited-block bitmask geometry: ``32 · _MASK_WORDS`` vertex-id
# blocks of ``segment_mask_block_size(n)`` consecutive ids each. For
# n ≤ 256 the blocks are single vertices (invalidation is exact); larger
# graphs trade one conservative bit per
# ``ceil(n / 256)`` ids for a fixed 32-byte-per-segment footprint.
_MASK_WORDS = 8


def segment_mask_block_size(n: int) -> int:
    """Vertex ids per visited-block bit for an n-vertex graph (the one
    formula shared by the index build and ``repro.dynamic`` invalidation —
    they must agree or staleness checks would be unsound)."""
    return max(1, -(-n // (32 * _MASK_WORDS)))


@dataclasses.dataclass(frozen=True)
class WalkIndex:
    """Dense per-vertex walk-segment endpoints.

    Attributes:
      endpoints:   int32[n, R] — ``endpoints[v, r] ~ P^L(· | v)`` i.i.d.
      segment_len: L, the number of steps each stored segment advanced.
      seed:        build seed (provenance; queries use their own keys).
      visited_blocks: uint32[n, R, _MASK_WORDS] — per-segment bitmask of
                   the vertex-id blocks whose out-edges the segment
                   consumed (the dynamic-graph invalidation input; None
                   on indexes loaded from pre-epoch checkpoints).
      graph_epoch: mutation epoch of the graph this slab was walked on.
      mutation_offset: that graph's mutation-log offset (manifest cross-
                   check against ``CSRGraph.mutation_offset``).
    """

    endpoints: jnp.ndarray
    segment_len: int
    seed: int
    visited_blocks: Optional[np.ndarray] = None
    graph_epoch: int = 0
    mutation_offset: int = 0

    @property
    def n(self) -> int:
        return int(self.endpoints.shape[0])

    @property
    def segments_per_vertex(self) -> int:
        return int(self.endpoints.shape[1])


@dataclasses.dataclass(frozen=True)
class ShardedWalkIndex:
    """The walk-index slab as range-partitioned per-shard blocks.

    ``blocks[s]`` holds the ``[shard_size, R]`` endpoints of vertices
    ``[s · shard_size, (s+1) · shard_size)`` (host memory; the sharded
    scheduler places block ``s`` on device ``s`` of the serving mesh, or
    feeds blocks one at a time on a single device — the full slab is never
    concatenated on any device).

    Attributes:
      blocks:      int32[S, shard_size, R] — host-side stacked blocks.
      n:           true vertex count (``S · shard_size ≥ n``; padded rows
                   are never gathered — walk positions are graph vertices).
      segment_len: L, steps per precomputed segment.
      seed:        build seed (provenance).
      visited_blocks: uint32[S, shard_size, R, _MASK_WORDS] per-segment
                   visited-block bitmasks (None for pre-epoch checkpoints).
      graph_epoch / mutation_offset: epoch provenance of the graph this
                   slab was walked on (see :class:`WalkIndex`).
    """

    blocks: np.ndarray
    n: int
    segment_len: int
    seed: int
    visited_blocks: Optional[np.ndarray] = None
    graph_epoch: int = 0
    mutation_offset: int = 0

    @property
    def num_shards(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def shard_size(self) -> int:
        return int(self.blocks.shape[1])

    @property
    def segments_per_vertex(self) -> int:
        return int(self.blocks.shape[2])

    def reassemble(self) -> WalkIndex:
        """Dense slab (tests / the legacy gathered serving path) — this is
        exactly the concatenation the sharded scheduler avoids."""
        S, sz, R = self.blocks.shape
        vb = self.visited_blocks
        if vb is not None:
            vb = np.asarray(vb).reshape(S * sz, R, _MASK_WORDS)[: self.n]
        return WalkIndex(
            endpoints=jnp.asarray(
                self.blocks.reshape(S * sz, R)[: self.n], jnp.int32),
            segment_len=self.segment_len,
            seed=self.seed,
            visited_blocks=vb,
            graph_epoch=self.graph_epoch,
            mutation_offset=self.mutation_offset,
        )


def shard_walk_index(index: WalkIndex, num_shards: int) -> ShardedWalkIndex:
    """Range-partitions a dense index into serving blocks.

    Rows are padded to a ``num_shards`` multiple; padded rows are zero and
    unreachable (walk positions are always real graph vertices < n).
    """
    n, R = index.endpoints.shape
    sz = -(-n // num_shards)
    ep = np.zeros((num_shards * sz, R), np.int32)
    ep[:n] = np.asarray(index.endpoints)
    vb = None
    if index.visited_blocks is not None:
        vb = np.zeros((num_shards * sz, R, _MASK_WORDS), np.uint32)
        vb[:n] = np.asarray(index.visited_blocks)
        vb = vb.reshape(num_shards, sz, R, _MASK_WORDS)
    return ShardedWalkIndex(
        blocks=ep.reshape(num_shards, sz, R), n=n,
        segment_len=index.segment_len, seed=index.seed,
        visited_blocks=vb, graph_epoch=index.graph_epoch,
        mutation_offset=index.mutation_offset,
    )


def _segment_step(row_ptr, col_idx, deg, n, step_impl, pos, bits):
    """One no-death plain walker move for a batch of segment walks.

    The segment walk is the p_T = 0, p_s = 1 corner of the walker
    superstep: with ``step_impl != "xla"`` it routes through the fused
    Pallas kernels (resident or HBM-streaming — the death tally is all
    zeros and discarded). ``bits`` are the callers' per-walker slot draws
    (per-vertex key streams — see the module docstring).
    """
    if step_impl == "xla":
        return uniform_successor(row_ptr, col_idx, deg, pos, bits)
    from repro.kernels import ops

    nxt, _ = ops.frog_step(
        pos, jnp.zeros_like(pos), bits, row_ptr, col_idx, deg, n,
        impl=step_impl,
    )
    return nxt


def _block_one_hot(pos, block_size, num_words):
    """uint32[len(pos), num_words] — the visited-block bit of each walker's
    current vertex (out-of-range blocks, i.e. graph-padding rows, contribute
    no bit)."""
    blk = (pos // block_size).astype(jnp.uint32)
    word = blk >> 5
    bit = (blk & jnp.uint32(31))[:, None]
    eq = jnp.arange(num_words, dtype=jnp.uint32)[None, :] == word[:, None]
    return eq.astype(jnp.uint32) << bit


def _segment_walk_rows(row_ptr, col_idx, deg, n, step_impl, R, L,
                       block_size, vertices, key):
    """The one segment-walk program under every build and refresh path.

    Walks the L-step segments of ``vertices`` — all ``R`` slots per row.
    Randomness is per ``(vertex, step)``:
    ``fold_in(fold_in(key, v), l)`` drawing the row's ``R`` slot bits at
    shape ``(R,)``, so a row's stream is independent of the batch it is
    walked in — full-shard builds, ``shard_map`` builds, and arbitrary
    stale-row subsets all produce byte-identical cells.

    Returns ``(endpoints[C, R], visited_masks[C, R, W])``. The mask ORs the
    block bit of the *intermediate* vertices only (``p_1..p_{L-1}``): the
    start's out-edge consumption is covered exactly — per vertex, not per
    block — by the invalidator's source rule, so recording its block here
    would only drag every block-mate of a mutated vertex stale, and the
    endpoint consumes no edge at all.
    """
    C = vertices.shape[0]
    row_keys = jax.vmap(lambda v: jax.random.fold_in(key, v))(vertices)
    # each row's start vertex, once per slot (a broadcast: jnp.repeat
    # lowers to a scatter whose TPU compile time grows with C)
    pos0 = jnp.broadcast_to(vertices.astype(jnp.int32)[:, None],
                            (C, R)).reshape(-1)
    mask0 = jnp.zeros((pos0.shape[0], _MASK_WORDS), jnp.uint32)

    def step(carry, l):
        pos, mask = carry
        ks = jax.vmap(lambda kk: jax.random.fold_in(kk, l))(row_keys)
        bits = jax.vmap(
            lambda kk: jax.random.randint(kk, (R,), 0, 1 << 30, jnp.int32)
        )(ks)
        # Materialize the bits before the hop: fused into the gathers'
        # indices, the per-row threefry makes the TPU compile time grow
        # with C (302 s for one LiveJournal-sized build shard compiled for
        # v5e, about 30 s with the barrier).
        bits = jax.lax.optimization_barrier(bits.reshape(-1))
        nxt = _segment_step(row_ptr, col_idx, deg, n, step_impl, pos, bits)
        oh = _block_one_hot(nxt, block_size, _MASK_WORDS)
        mask = jnp.where(l < L - 1, mask | oh, mask)
        return (nxt, mask), None

    (pos, mask), _ = jax.lax.scan(step, (pos0, mask0),
                                  jnp.arange(L, dtype=jnp.int32))
    return pos.reshape(C, R), mask.reshape(C, R, _MASK_WORDS)


@functools.lru_cache(maxsize=None)
def _row_walk_program(n, step_impl, R, L, block_size):
    """The process-wide compiled row walker for one geometry.

    Graph buffers are *traced operands*, not closure constants, so every
    build, shard repair, and incremental refresh at the same geometry
    shares one compile — a mutated graph at a new epoch re-dispatches the
    cached program instead of re-tracing (only a changed ``col_idx``
    length, i.e. a net edge-count change, costs a new trace). Wrapping
    this in another ``jax.jit`` at a call site would inline and re-trace
    it per wrapper; call it directly.
    """

    def run(row_ptr, col_idx, deg, vertices, key):
        return _segment_walk_rows(row_ptr, col_idx, deg, n, step_impl,
                                  R, L, block_size, vertices, key)

    return jax.jit(run)


@dataclasses.dataclass(frozen=True)
class _ShardWalker:
    """Per-shard front-end over the cached :func:`_row_walk_program`.

    ``block_size`` is ``segment_mask_block_size`` of the *real* vertex
    count (``n`` here is the padded graph's, used only for kernel bounds);
    padded rows' walks stay on their self-loops ≥ real n and fall outside
    the mask range, contributing no bits. Call it directly — the row
    program inside is already jitted and shared process-wide; wrapping the
    call in ``jax.jit`` again would re-trace it per wrapper.
    """

    row_ptr: jnp.ndarray
    col_idx: jnp.ndarray
    deg: jnp.ndarray
    n: int
    shard_size: int
    cfg: WalkIndexConfig
    block_size: int

    def __call__(self, lo: jnp.ndarray, key: jax.Array):
        vs = lo + jnp.arange(self.shard_size, dtype=jnp.int32)
        run = _row_walk_program(
            self.n, self.cfg.step_impl, self.cfg.segments_per_vertex,
            self.cfg.segment_len, self.block_size)
        return run(self.row_ptr, self.col_idx, self.deg, vs, key)


def build_walk_index(
    g: CSRGraph, cfg: WalkIndexConfig, key: Optional[jax.Array] = None
) -> WalkIndex:
    """Deprecated entry point — use :meth:`repro.service.FrogWildService.
    ensure_index` (or :func:`repro.service.build_index`). Delegates through
    the service so the slab is byte-identical to the facade's."""
    warn_deprecated("build_walk_index", "FrogWildService.ensure_index")
    from repro import service

    return service.build_index(g, cfg, key=key)


def _build_walk_index(
    g: CSRGraph, cfg: WalkIndexConfig, key: Optional[jax.Array] = None
) -> WalkIndex:
    """Builds the ``int32[n, R]`` endpoint slab, one range shard at a time
    (the runtime's single-device host-loop dispatch)."""
    if cfg.segment_len < 1:
        raise ValueError(f"segment_len must be ≥ 1, got {cfg.segment_len}")
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)
    gp, part = partition_graph(g, cfg.num_shards)
    walker = _ShardWalker(
        row_ptr=gp.row_ptr, col_idx=gp.col_idx, deg=gp.out_deg, n=gp.n,
        shard_size=part.shard_size, cfg=cfg,
        block_size=segment_mask_block_size(g.n),
    )
    rt = ShardRuntime(num_shards=cfg.num_shards, mesh=None)

    def shard(s):
        # per-vertex key streams: every shard gets the same base key — the
        # vertex id folded inside the walk program is the only selector.
        with telemetry.span("repro.index.walk"):
            out = jax.block_until_ready(
                walker(jnp.int32(part.bounds(s)[0]), key))
        with telemetry.span("repro.index.copy_out"):
            return jax.tree_util.tree_map(np.asarray, out)

    pairs = rt.map_shards(shard)
    with telemetry.span("repro.index.copy_out"):
        endpoints = jnp.asarray(
            np.concatenate([p[0] for p in pairs], axis=0)[: g.n], jnp.int32)
        masks = np.concatenate([p[1] for p in pairs],
                               axis=0)[: g.n].astype(np.uint32)
    return WalkIndex(
        endpoints=endpoints,
        segment_len=cfg.segment_len,
        seed=cfg.seed,
        visited_blocks=masks,
        graph_epoch=int(getattr(g, "epoch", 0)),
        mutation_offset=int(getattr(g, "mutation_offset", 0)),
    )


def build_walk_index_sharded(
    g: CSRGraph,
    cfg: WalkIndexConfig,
    mesh,
    directory: Optional[str] = None,
    key: Optional[jax.Array] = None,
    axis_name: str = "vertex",
    step: int = 0,
    reassemble: bool = True,
) -> Union[WalkIndex, ShardedWalkIndex]:
    """Deprecated entry point — use :meth:`repro.service.FrogWildService.
    ensure_index` (or :func:`repro.service.build_index` with ``mesh=``).
    Delegates through the service so the slab is byte-identical."""
    warn_deprecated("build_walk_index_sharded", "FrogWildService.ensure_index")
    from repro import service

    return service.build_index(g, cfg, mesh=mesh, directory=directory,
                               key=key, axis_name=axis_name, step=step,
                               reassemble=reassemble)


def _build_walk_index_sharded(
    g: CSRGraph,
    cfg: WalkIndexConfig,
    mesh,
    directory: Optional[str] = None,
    key: Optional[jax.Array] = None,
    axis_name: str = "vertex",
    step: int = 0,
    reassemble: bool = True,
) -> Union[WalkIndex, ShardedWalkIndex]:
    """Builds the slab as **one** ``shard_map`` program over ``mesh``.

    Each device walks its own range shard's ``shard_size · R`` segment
    frogs and materializes only its ``[shard_size, R]`` slab block
    (``out_specs=P(axis_name)`` — device memory holds ``4nR/S`` bytes of
    slab). The graph CSR is a replicated operand; randomness is the
    per-vertex key stream (``fold_in(key, v)`` inside the shared walk
    program — see the module docstring), so a shard's block is
    byte-identical to the host loop's and to any row-subset rebuild,
    independent of mesh shape.

    With ``directory`` set, every shard's block is persisted as its own
    atomic checkpoint (``save_walk_index_shard``) before the function
    returns. ``reassemble=False`` returns the :class:`ShardedWalkIndex`
    blocks directly (the sharded-serving input); the default reassembles
    the dense :class:`WalkIndex` for legacy readers.
    """
    if cfg.segment_len < 1:
        raise ValueError(f"segment_len must be ≥ 1, got {cfg.segment_len}")
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)
    rt = ShardRuntime.for_mesh(mesh, axis_name)
    S = rt.num_shards
    gp, part = partition_graph(g, S)
    sz = part.shard_size
    R, L = cfg.segments_per_vertex, cfg.segment_len
    bs = segment_mask_block_size(g.n)

    def body(row_ptr, col_idx, deg, key_data):
        k = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        me = jax.lax.axis_index(axis_name)
        vs = me * sz + jnp.arange(sz, dtype=jnp.int32)
        ep, mk = _segment_walk_rows(row_ptr, col_idx, deg, gp.n,
                                    cfg.step_impl, R, L, bs, vs, k)
        return ep.reshape(1, sz, R), mk.reshape(1, sz, R, _MASK_WORDS)

    # check_vma=False: jax has no replication rule for pallas_call, and the
    # fused step backends lower through one (the body is trivially
    # per-shard — nothing cross-device to check).
    fn = rt.sharded_call(body, num_sharded=0, num_replicated=4,
                         num_outputs=2, check_vma=False)
    # the CSR is a replicated operand, not a constant baked into the program
    csr = jax.device_put((gp.row_ptr, gp.col_idx, gp.out_deg),
                         rt.sharding(replicated=True))
    with telemetry.span("repro.index.walk"):
        ep, mk = jax.block_until_ready(fn(*csr, ShardRuntime.key_data(key)))
    with telemetry.span("repro.index.copy_out"):
        blocks = np.asarray(ep)                       # [S, sz, R]
        masks = np.asarray(mk).astype(np.uint32)      # [S, sz, R, W]
    g_epoch = int(getattr(g, "epoch", 0))
    g_offset = int(getattr(g, "mutation_offset", 0))
    if directory is not None:
        for s in range(S):
            save_walk_index_shard(
                directory, s, S, g.n, blocks[s], cfg.segment_len, cfg.seed,
                step=step, visited_blocks=masks[s], graph_epoch=g_epoch,
                mutation_offset=g_offset)
    sharded = ShardedWalkIndex(blocks=blocks, n=g.n,
                               segment_len=cfg.segment_len, seed=cfg.seed,
                               visited_blocks=masks, graph_epoch=g_epoch,
                               mutation_offset=g_offset)
    if not reassemble:
        return sharded
    with telemetry.span("repro.index.copy_out"):
        return sharded.reassemble()


# --- persistence (checkpoint/ atomic step directories) ----------------------


def _index_tree(index: WalkIndex) -> dict:
    tree = {
        "endpoints": index.endpoints,
        "segment_len": jnp.int32(index.segment_len),
        "seed": jnp.int32(index.seed),
        "graph_epoch": jnp.int32(index.graph_epoch),
        "mutation_offset": jnp.int32(index.mutation_offset),
    }
    if index.visited_blocks is not None:
        tree["visited_blocks"] = jnp.asarray(index.visited_blocks,
                                             jnp.uint32)
    return tree


def save_walk_index_shard(
    directory: str,
    shard: int,
    num_shards: int,
    n: int,
    block: np.ndarray,            # int32[shard_size, R] — this shard's slab
    segment_len: int,
    seed: int,
    step: int = 0,
    *,
    visited_blocks: Optional[np.ndarray] = None,
    graph_epoch: int = 0,
    mutation_offset: int = 0,
) -> str:
    """Atomic save of one shard's slab block through the runtime's
    per-shard checkpoint layout (``<directory>/shard_<s>/step_<k>/``) —
    each shard is an independent checkpoint dir, so a sharded build can
    persist (and crash/retry) one shard at a time without ever exposing a
    torn slab. ``graph_epoch`` / ``mutation_offset`` stamp the manifest
    with the source graph's mutation provenance; ``visited_blocks`` rides
    along when the build recorded per-segment masks."""
    block = jnp.asarray(block, dtype=jnp.int32)
    tree = {
        "endpoints": block,
        "segment_len": jnp.int32(segment_len),
        "seed": jnp.int32(seed),
        "shard": jnp.int32(shard),
        "num_shards": jnp.int32(num_shards),
        "n": jnp.int32(n),
        "segments_per_vertex": jnp.int32(block.shape[1]),
        "graph_epoch": jnp.int32(graph_epoch),
        "mutation_offset": jnp.int32(mutation_offset),
    }
    if visited_blocks is not None:
        tree["visited_blocks"] = jnp.asarray(visited_blocks, jnp.uint32)
    return save_shard_checkpoint(directory, shard, tree, step=step)


def save_walk_index(directory: str, index: WalkIndex, step: int = 0) -> str:
    """Atomic save under ``<directory>/step_<k>/`` (checkpoint layout)."""
    return save_checkpoint(directory, step, _index_tree(index))


def load_walk_index(
    directory: str, step: Optional[int] = None, reassemble: bool = True
) -> Union[WalkIndex, ShardedWalkIndex]:
    """Restores the latest (or given) index build from ``directory``.

    Handles both layouts: a monolithic ``save_walk_index`` checkpoint, and
    the per-shard layout written by a sharded build (``<directory>/
    shard_<s>/step_<k>/``), whose blocks are validated (all shards
    present, consistent metadata). ``reassemble=True`` concatenates them
    into the dense slab (legacy readers); ``reassemble=False`` hands the
    per-shard blocks to the caller as a :class:`ShardedWalkIndex` — the
    sharded scheduler's input, with no full-slab concatenation (a
    monolithic checkpoint is returned as a single-shard index).
    """
    shard_dirs = list_shard_dirs(directory)
    if not shard_dirs:
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no walk index under {directory!r}")
        tree = load_checkpoint_tree(directory, step)
        vb = tree.get("visited_blocks")
        index = WalkIndex(
            endpoints=jnp.asarray(tree["endpoints"], jnp.int32),
            segment_len=int(tree["segment_len"]),
            seed=int(tree["seed"]),
            visited_blocks=(None if vb is None
                            else np.asarray(vb, np.uint32)),
            graph_epoch=int(tree.get("graph_epoch", 0)),
            mutation_offset=int(tree.get("mutation_offset", 0)),
        )
        return index if reassemble else shard_walk_index(index, 1)

    trees = load_shard_checkpoints(directory, step, on_error="collect")
    good, bad = _split_shard_trees(directory, trees)
    meta = _shard_meta_consensus(directory, good, bad)
    if bad:
        R, L = (meta.R, meta.L) if meta is not None else ("?", "?")
        detail = "; ".join(f"{shard_dir(directory, s)}: {e}"
                           for s, e in sorted(bad.items()))
        raise CheckpointCorruptError(
            f"walk index under {directory!r} has corrupt or partial shard "
            f"checkpoints (expected int32[shard_size, R={R}] blocks of "
            f"L={L}-step segments): {detail} — quarantine and rebuild "
            f"them (load_or_repair_walk_index does both)")
    missing = sorted(set(range(meta.num_shards)) - set(good))
    if missing:
        raise FileNotFoundError(
            f"walk index under {directory!r} is missing shards {missing} "
            f"(expected {meta.num_shards} shard dirs of "
            f"int32[shard_size, R={meta.R}] blocks, L={meta.L})")
    return _assemble_sharded(good, meta, reassemble)


_ShardMeta = collections.namedtuple(
    "_ShardMeta",
    ["num_shards", "n", "L", "seed", "R", "graph_epoch", "mutation_offset"])


def _split_shard_trees(directory, trees):
    """Separates healthy shard trees from failed loads; a tree whose
    payload shape contradicts its own metadata counts as corrupt."""
    good: Dict[int, dict] = {}
    bad: Dict[int, Exception] = {}
    for s, tree in trees.items():
        if isinstance(tree, Exception):
            bad[s] = tree
            continue
        try:
            R = int(tree["segments_per_vertex"])
            ep = np.asarray(tree["endpoints"])
            if ep.ndim != 2 or ep.shape[1] != R:
                raise CheckpointCorruptError(
                    f"shard block has shape {ep.shape}, metadata says "
                    f"R={R}")
            good[s] = tree
        except (KeyError, CheckpointCorruptError) as e:
            bad[s] = e if isinstance(e, CheckpointCorruptError) else (
                CheckpointCorruptError(
                    f"shard checkpoint is missing leaf {e}"))
    return good, bad


def _shard_meta_consensus(directory, good, bad):
    """Majority metadata across healthy shards; dissenting shards are
    reclassified as corrupt (moved to ``bad``). None when no healthy
    shard survives."""
    metas = {
        s: _ShardMeta(int(t["num_shards"]), int(t["n"]),
                      int(t["segment_len"]), int(t["seed"]),
                      int(t["segments_per_vertex"]),
                      int(t.get("graph_epoch", 0)),
                      int(t.get("mutation_offset", 0)))
        for s, t in good.items()
    }
    if not metas:
        return None
    consensus, _ = collections.Counter(metas.values()).most_common(1)[0]
    for s, m in metas.items():
        if m != consensus:
            bad[s] = CheckpointCorruptError(
                f"shard metadata {tuple(m)} disagrees with the "
                f"{tuple(consensus)} consensus under {directory!r}")
            del good[s]
    return consensus


def _assemble_sharded(good, meta, reassemble):
    vb = None
    if all("visited_blocks" in good[s] for s in range(meta.num_shards)):
        vb = np.stack([np.asarray(good[s]["visited_blocks"])
                       for s in range(meta.num_shards)]).astype(np.uint32)
    sharded = ShardedWalkIndex(
        blocks=np.stack([np.asarray(good[s]["endpoints"])
                         for s in range(meta.num_shards)]).astype(np.int32),
        n=meta.n, segment_len=meta.L, seed=meta.seed,
        visited_blocks=vb, graph_epoch=meta.graph_epoch,
        mutation_offset=meta.mutation_offset,
    )
    return sharded.reassemble() if reassemble else sharded


def rebuild_shard_blocks(
    g: CSRGraph, cfg: WalkIndexConfig, shards: List[int]
) -> Dict[int, tuple]:
    """Rebuilds just the named shards' slab blocks with the build's exact
    per-vertex key stream (``fold_in(PRNGKey(cfg.seed), v)`` over the
    ``partition_graph(g, cfg.num_shards)`` ranges) — byte-identical to the
    blocks the original host-loop *or* ``shard_map`` build produced, so a
    quarantined shard can be regenerated without touching the others.
    Returns ``{shard: (endpoints int32[sz, R], visited uint32[sz, R, W])}``.
    """
    gp, part = partition_graph(g, cfg.num_shards)
    walker = _ShardWalker(
        row_ptr=gp.row_ptr, col_idx=gp.col_idx, deg=gp.out_deg, n=gp.n,
        shard_size=part.shard_size, cfg=cfg,
        block_size=segment_mask_block_size(g.n),
    )
    key = jax.random.PRNGKey(cfg.seed)
    out = {}
    for s in shards:
        ep, mk = walker(jnp.int32(part.bounds(s)[0]), key)
        out[s] = (np.asarray(ep), np.asarray(mk).astype(np.uint32))
    return out


def load_or_repair_walk_index(
    directory: str,
    g: CSRGraph,
    cfg: WalkIndexConfig,
    step: Optional[int] = None,
    reassemble: bool = True,
) -> Union[WalkIndex, ShardedWalkIndex]:
    """Like :func:`load_walk_index`, but self-healing for the per-shard
    layout: a corrupt, torn, or missing shard checkpoint is quarantined
    (``quarantine.shard_<s>`` — kept for forensics, invisible to loaders)
    and its slab block rebuilt via :func:`rebuild_shard_blocks` with the
    original build's key stream, then persisted and served. Only the
    broken shards are rebuilt; healthy blocks are never re-walked.

    The monolithic (dense) layout has no sub-unit to repair — corruption
    there propagates as :class:`~repro.checkpoint.CheckpointCorruptError`
    and the caller rebuilds the whole index.
    """
    if not list_shard_dirs(directory):
        return load_walk_index(directory, step, reassemble)

    trees = load_shard_checkpoints(directory, step, on_error="collect")
    good, bad = _split_shard_trees(directory, trees)
    meta = _shard_meta_consensus(directory, good, bad)
    if meta is None:
        # every shard is broken: fall back to the caller's config geometry
        meta = _ShardMeta(cfg.num_shards, g.n, cfg.segment_len, cfg.seed,
                          cfg.segments_per_vertex,
                          int(getattr(g, "epoch", 0)),
                          int(getattr(g, "mutation_offset", 0)))
    if meta.n != g.n:
        raise ValueError(
            f"walk index under {directory!r} was built for n={meta.n} but "
            f"the service graph has n={g.n}; refusing to repair across "
            f"graphs — point checkpoint_dir elsewhere or rebuild")
    if meta.graph_epoch != int(getattr(g, "epoch", 0)):
        raise ValueError(
            f"walk index under {directory!r} was built at graph epoch "
            f"{meta.graph_epoch} but the service graph is at epoch "
            f"{int(getattr(g, 'epoch', 0))}; a repair would mix epochs — "
            f"refresh the slab (repro.dynamic.refresh_walk_index) or "
            f"rebuild at the current epoch")
    missing = sorted(set(range(meta.num_shards)) - set(good))
    broken = sorted(set(bad) | set(missing))
    if not broken:
        return _assemble_sharded(good, meta, reassemble)

    build_cfg = dataclasses.replace(
        cfg, num_shards=meta.num_shards, segments_per_vertex=meta.R,
        segment_len=meta.L, seed=meta.seed)
    rebuilt = rebuild_shard_blocks(g, build_cfg, broken)
    healthy_step = step
    if healthy_step is None:
        steps = [latest_step(shard_dir(directory, s)) for s in good]
        healthy_step = next((s for s in steps if s is not None), 0)
    for s in broken:
        if os.path.isdir(shard_dir(directory, s)):
            quarantine_shard_dir(directory, s)
        ep, mk = rebuilt[s]
        save_walk_index_shard(
            directory, s, meta.num_shards, g.n, ep, meta.L,
            meta.seed, step=healthy_step, visited_blocks=mk,
            graph_epoch=meta.graph_epoch,
            mutation_offset=meta.mutation_offset)
        good[s] = {"endpoints": ep, "visited_blocks": mk}
    return _assemble_sharded(good, meta, reassemble)
