"""Distributed FrogWild! over a JAX mesh — the PowerGraph role.

Vertices are range-sharded over a 1-D ``"vertex"`` mesh axis; each shard owns
the CSR row-block of its vertices' out-edges. One superstep =

  init     frogs arrive from the previous exchange (fixed-capacity buffers);
  apply    each frog dies w.p. p_T and is tallied in the owner's counter;
  sync     each (vertex, destination-shard) channel opens w.p. p_s
           (the paper's randomized mirror synchronization — Definition 8's
           erasure model at exactly the granularity of the GraphLab patch);
  scatter  survivors redraw uniformly among edges on *open* channels
           ("blocking walk", Process 19; Example 10 repair guarantees one
           open edge), are bucketed per destination shard, and exchanged
           with a single all-to-all.

The all-to-all buffers are **fixed-capacity per channel** (like MoE token
dispatch): static shapes for XLA, a measured overflow counter instead of
dynamic resizing. Frogs have no identity (paper §3.3's first optimization) —
the payload is just destination vertex ids, and the cost model in netcost.py
counts only open channels, matching what GraphLab's sparse transport would
put on the wire.

The *same* shard program is used for execution (``distributed_frogwild``)
and for the large-scale dry-run (``frogwild_dryrun_lowered`` — ShapeDtype-
Structs only, no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.config import EngineConfig, warn_deprecated
from repro.core.blocking import (channel_enum_draw, coin_uniform,
                                 rejection_is_profitable)
from repro.distributed.runtime import ShardRuntime
from repro.graph.csr import CSRGraph
from repro.graph.partition import partition_graph
from repro.kernels.frog_step_stream import BlockedCSR

# EngineConfig is defined in repro/config.py (the layered-config module —
# single definition per flag) and re-exported here for back-compat.


@dataclasses.dataclass(frozen=True)
class DistributedGraph:
    """Stacked per-shard CSR blocks (leading axis = shard, sharded on mesh).

    For dry-runs this carries only the *shapes* (arrays are None).
    """

    num_shards: int
    shard_size: int                   # vertices per shard (padded)
    n: int                            # original vertex count
    nnz_max: int                      # padded edges per shard
    row_ptr: jnp.ndarray | None = None      # int32[S, shard_size + 1]
    col_idx: jnp.ndarray | None = None      # int32[S, nnz_max] (global dest)
    deg: jnp.ndarray | None = None          # int32[S, shard_size]
    edge_src: jnp.ndarray | None = None     # int32[S, nnz_max] (local source)
    edge_dst_shard: jnp.ndarray | None = None  # int32[S, nnz_max]
    chan_cnt: jnp.ndarray | None = None     # int32[S, shard_size, num_shards]
    col_sorted: jnp.ndarray | None = None   # int32[S, nnz_max] (channel-sorted)
    # Streamed-step slab layout (kernels/frog_step_stream.BlockedCSR per
    # shard, uniform static shapes across shards). Present only when
    # build_distributed_graph was given a vertex_block; the fused
    # step_impl="stream" path requires it.
    vertex_block: int = 0                   # BV (0 = no blocked layout)
    nnz_blk_max: int = 0                    # E_blk
    blk_row_off: jnp.ndarray | None = None  # int32[S, num_vb, BV]
    blk_deg: jnp.ndarray | None = None      # int32[S, num_vb, BV]
    blk_col: jnp.ndarray | None = None      # int32[S, num_vb, E_blk]
    # chan_cnt[s, v, d] — #out-edges of vertex v (on shard s) into shard d:
    # the "mirror" structure (has_edge_to ≡ chan_cnt > 0). A (v, d) sync
    # message is owed only when v is active AND the channel opened — the
    # quantity p_s throttles in GraphLab. col_sorted is each vertex's CSR
    # segment reordered by destination shard — the exact channel-enumeration
    # draw indexes into it via chan_cnt's prefix offsets.

    @property
    def n_padded(self) -> int:
        return self.num_shards * self.shard_size

    @property
    def has_blocked(self) -> bool:
        return self.vertex_block > 0

    @property
    def num_vertex_blocks(self) -> int:
        return (-(-self.shard_size // self.vertex_block)
                if self.has_blocked else 0)

    def array_specs(self):
        S, sz, nnz = self.num_shards, self.shard_size, self.nnz_max
        specs = [
            jax.ShapeDtypeStruct((S, sz + 1), jnp.int32),
            jax.ShapeDtypeStruct((S, nnz), jnp.int32),
            jax.ShapeDtypeStruct((S, sz), jnp.int32),
            jax.ShapeDtypeStruct((S, nnz), jnp.int32),
            jax.ShapeDtypeStruct((S, nnz), jnp.int32),
            jax.ShapeDtypeStruct((S, sz, S), jnp.int32),
            jax.ShapeDtypeStruct((S, nnz), jnp.int32),
        ]
        if self.has_blocked:
            nvb, bv, eb = self.num_vertex_blocks, self.vertex_block, self.nnz_blk_max
            specs += [
                jax.ShapeDtypeStruct((S, nvb, bv), jnp.int32),
                jax.ShapeDtypeStruct((S, nvb, bv), jnp.int32),
                jax.ShapeDtypeStruct((S, nvb, eb), jnp.int32),
            ]
        return tuple(specs)

    def arrays(self):
        base = (self.row_ptr, self.col_idx, self.deg, self.edge_src,
                self.edge_dst_shard, self.chan_cnt, self.col_sorted)
        if self.has_blocked:
            return base + (self.blk_row_off, self.blk_deg, self.blk_col)
        return base


@dataclasses.dataclass
class EngineResult:
    counts: jnp.ndarray                 # int32[n] — stop tallies (global)
    pi_hat: jnp.ndarray                 # f32[n]
    sent_per_step: np.ndarray           # int64[t] — frogs exchanged each step
    open_channels_per_step: np.ndarray  # int64[t] — (shard→shard) pairs used
    sync_msgs_per_step: np.ndarray      # int64[t] — (active vertex, mirror)
    overflow: int                       # frogs dropped by capacity (want 0)
    config: EngineConfig


def build_distributed_graph(
    g: CSRGraph, num_shards: int, vertex_block: int | None = None
) -> DistributedGraph:
    """Splits CSR rows into per-shard blocks with uniform padded shapes.

    With ``vertex_block`` set, each shard's row block is additionally laid
    out as uniform per-vertex-block slabs (the streamed ``frog_step``
    kernel's DMA unit) — required for ``EngineConfig.step_impl`` of
    ``"stream"``/``"auto"``.
    """
    gp, part = partition_graph(g, num_shards)
    if int(np.asarray(g.out_deg).min()) < 1:
        # Both step paths index col_idx[row_ptr[v] + slot] unguarded — a
        # deg-0 vertex would read a neighbour's edge (xla draw) or leak a
        # local id as a global destination (fused kernels). build_csr's
        # dangling repair is a precondition, so enforce it here.
        raise ValueError(
            "engine graphs need d_out ≥ 1 everywhere; repair dangling "
            "vertices first (graph/csr.py:build_csr dangling= policy)")
    gn = gp.to_numpy()
    S, sz = num_shards, part.shard_size
    nnz_per = [int(gn.row_ptr[(s + 1) * sz] - gn.row_ptr[s * sz]) for s in range(S)]
    nnz_max = max(8, int(np.ceil(max(nnz_per) / 8) * 8))

    # Per-edge source / destination-shard / channel layout come from the
    # graph's memoized derived arrays (computed once per CSRGraph, shared
    # with the walker oracle) — each shard block just slices and re-bases.
    es_global = np.asarray(gp.edge_src)
    eds_global = np.asarray(gp.edge_dst_shard(num_shards))
    cs_global, cnt_global, _ = (np.asarray(a)
                                for a in gp.channel_layout(num_shards))
    row_ptr = np.zeros((S, sz + 1), dtype=np.int32)
    col_idx = np.zeros((S, nnz_max), dtype=np.int32)
    deg = np.zeros((S, sz), dtype=np.int32)
    edge_src = np.zeros((S, nnz_max), dtype=np.int32)
    edge_dst_shard = np.zeros((S, nnz_max), dtype=np.int32)
    col_sorted = np.zeros((S, nnz_max), dtype=np.int32)
    for s in range(S):
        lo = int(gn.row_ptr[s * sz])
        hi = int(gn.row_ptr[(s + 1) * sz])
        row_ptr[s] = gn.row_ptr[s * sz : (s + 1) * sz + 1] - lo
        col_idx[s, : hi - lo] = gn.col_idx[lo:hi]
        deg[s] = gn.out_deg[s * sz : (s + 1) * sz]
        edge_src[s, : hi - lo] = es_global[lo:hi] - s * sz
        edge_dst_shard[s, : hi - lo] = eds_global[lo:hi]
        col_sorted[s, : hi - lo] = cs_global[lo:hi]
    chan_cnt = cnt_global.reshape(S, sz, S).astype(np.int32)

    blocked = {}
    if vertex_block is not None:
        from repro.kernels.frog_step_stream import (block_csr, max_block_nnz,
                                                    round_e_blk)

        # One slab layout per shard via the kernel's own builder, with a
        # uniform slab width forced across shards (the shard body's
        # BlockedCSR must have one static E_blk).
        e_blk = round_e_blk(max(max_block_nnz(row_ptr[s], sz, vertex_block)
                                for s in range(S)))
        per_shard = [
            block_csr(row_ptr[s], col_idx[s], deg[s], sz,
                      vertex_block=vertex_block, e_blk=e_blk)
            for s in range(S)
        ]
        blocked = dict(
            vertex_block=per_shard[0].vertex_block, nnz_blk_max=e_blk,
            blk_row_off=jnp.stack([b.row_off for b in per_shard]),
            blk_deg=jnp.stack([b.deg for b in per_shard]),
            blk_col=jnp.stack([b.col for b in per_shard]),
        )

    return DistributedGraph(
        num_shards=S, shard_size=sz, n=g.n, nnz_max=nnz_max,
        row_ptr=jnp.asarray(row_ptr),
        col_idx=jnp.asarray(col_idx),
        deg=jnp.asarray(deg),
        edge_src=jnp.asarray(edge_src),
        edge_dst_shard=jnp.asarray(edge_dst_shard),
        chan_cnt=jnp.asarray(chan_cnt),
        col_sorted=jnp.asarray(col_sorted),
        **blocked,
    )


def channel_capacity(cfg: EngineConfig, S: int) -> int:
    """Expected frogs per (shard → shard) channel is N/S²; the blocking walk
    concentrates them into the open p_s fraction, hence the 1/p_s term."""
    expected = cfg.num_frogs / (S * S * max(cfg.p_s, 1e-3))
    cap = int(np.ceil(cfg.capacity_factor * max(expected, 1.0)))
    return max(8, int(np.ceil(cap / 8) * 8))


def _pack_by_shard(
    dest: jnp.ndarray, S: int, shard_size: int, cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Buckets frogs (global dest ids, -1 = empty) into a (S, cap) buffer.

    Sort-based packing: stable argsort by destination shard, rank-in-group by
    index arithmetic, capacity overflow dropped (and counted). This is the
    same fixed-capacity dispatch pattern as MoE token routing.
    """
    B = dest.shape[0]
    valid = dest >= 0
    ds = jnp.where(valid, dest // shard_size, S)      # trash bucket S
    order = jnp.argsort(ds)                           # stable — groups shards
    ds_s = ds[order]
    dv_s = dest[order]
    first = jnp.searchsorted(ds_s, jnp.arange(S), side="left")
    rank = jnp.arange(B, dtype=jnp.int32) - first[jnp.clip(ds_s, 0, S - 1)].astype(jnp.int32)
    ok = (ds_s < S) & (rank < cap)
    row = jnp.where(ok, ds_s, S)                      # OOB rows drop
    col = jnp.where(ok, rank, 0)
    buf = jnp.full((S, cap), -1, dtype=jnp.int32)
    buf = buf.at[row, col].set(dv_s, mode="drop")
    n_sent = ok.sum()
    return buf, n_sent, valid.sum() - n_sent


def _blocking_draw_cumsum(
    pos_local: jnp.ndarray,       # int32[B] local vertex (garbage if dead)
    row_ptr: jnp.ndarray,         # int32[shard_size + 1]
    col_idx: jnp.ndarray,         # int32[nnz_max]
    deg: jnp.ndarray,             # int32[shard_size]
    edge_src: jnp.ndarray,        # int32[nnz_max]
    edge_dst_shard: jnp.ndarray,  # int32[nnz_max]
    coins: jnp.ndarray,           # bool[shard_size, S] — open sync channels
    key: jax.Array,
) -> jnp.ndarray:
    """O(nnz) reference scatter draw (per-edge mask + cumsum + searchsorted)."""
    B = pos_local.shape[0]
    shard_size = deg.shape[0]
    nnz_max = col_idx.shape[0]
    k_force, k_draw = jax.random.split(key)

    real_edge = jnp.arange(nnz_max, dtype=jnp.int32) < row_ptr[-1]
    kept = coins[edge_src, edge_dst_shard] & real_edge
    csum = jnp.cumsum(kept.astype(jnp.int32))
    kb = jnp.concatenate([jnp.zeros((1,), jnp.int32), csum])
    kv = kb[row_ptr[pos_local + 1]] - kb[row_ptr[pos_local]]
    # Example 10 repair: one uniformly-chosen edge per fully-blocked vertex.
    forced_slot = (
        jax.random.randint(k_force, (shard_size,), 0, 1 << 30, jnp.int32)
        % jnp.maximum(deg, 1)
    )
    forced_edge = row_ptr[:-1] + forced_slot
    u = jax.random.randint(k_draw, (B,), 0, 1 << 30, jnp.int32)
    u = u % jnp.maximum(kv, 1)
    target = kb[row_ptr[pos_local]] + u + 1
    edge = jnp.searchsorted(csum, target, side="left").astype(jnp.int32)
    edge = jnp.where(kv > 0, edge, forced_edge[pos_local])
    return col_idx[edge]


def _blocking_draw(
    pos_local: jnp.ndarray,       # int32[B] local vertex (garbage if dead)
    row_ptr: jnp.ndarray,         # int32[shard_size + 1]
    col_idx: jnp.ndarray,         # int32[nnz_max]
    deg: jnp.ndarray,             # int32[shard_size]
    edge_src: jnp.ndarray,        # int32[nnz_max]
    edge_dst_shard: jnp.ndarray,  # int32[nnz_max]
    chan_cnt: jnp.ndarray,        # int32[shard_size, S]
    chan_off: jnp.ndarray,        # int32[shard_size, S]
    col_sorted: jnp.ndarray,      # int32[nnz_max] (channel-sorted dests)
    coins: jnp.ndarray | None,    # bool[shard_size, S] — open sync channels
    p_s: float,
    key: jax.Array,
    draw: str = "rejection",
    alive: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """One scatter draw per frog among edges on open channels (Process 19).

    The default ``rejection`` path never touches per-edge state: each frog
    enumerates its ≤ S (vertex, mirror) channels against the superstep's
    coin grid (the same grid the sync accounting charges, so the draw and
    the wire cost always agree on which channels opened) and samples a kept
    edge exactly — O(B · S) instead of O(nnz_max), skew-safe
    (core/blocking.py:channel_enum_draw).
    """
    B = pos_local.shape[0]
    if p_s >= 1.0:
        u = jax.random.randint(key, (B,), 0, 1 << 30, jnp.int32)
        slot = u % jnp.maximum(deg[pos_local], 1)
        return col_idx[row_ptr[pos_local] + slot]
    if draw == "cumsum":
        return _blocking_draw_cumsum(
            pos_local, row_ptr, col_idx, deg, edge_src, edge_dst_shard,
            coins, key,
        )
    if draw != "rejection":
        raise ValueError(f"unknown draw impl {draw!r}")
    edge = channel_enum_draw(
        key, pos_local, row_ptr[pos_local], deg[pos_local],
        chan_cnt[pos_local], chan_off[pos_local], coins[pos_local],
        skip=None if alive is None else ~alive,
    )
    return col_sorted[edge]


def make_shard_body(dg: DistributedGraph, cfg: EngineConfig):
    """The per-shard superstep program (shared by run and dry-run paths).

    Takes stacked blocks ([1, ...] per shard) + a raw uint32 PRNG key; returns
    (counts[1, shard_size], stats[1, t, 3]).
    """
    S, sz, n = dg.num_shards, dg.shard_size, dg.n
    ax = cfg.axis_name
    cap = channel_capacity(cfg, S)
    B = S * cap
    t = cfg.num_steps
    f0 = cfg.num_frogs // S
    if f0 > B:
        raise ValueError(f"buffer too small: {f0} initial frogs > B={B}")
    draw_mode = cfg.draw
    if draw_mode == "auto":
        draw_mode = ("rejection"
                     if rejection_is_profitable(B, dg.nnz_max, cfg.p_s,
                                                num_channels=S)
                     else "cumsum")
    # Fused plain-step path: at p_s = 1 the shard-local tally + move route
    # through ops.frog_step (resident or HBM-streaming kernel).
    use_fused = cfg.p_s >= 1.0 and cfg.step_impl != "xla"
    if cfg.step_impl != "xla" and cfg.p_s < 1.0:
        raise ValueError(
            f"step_impl={cfg.step_impl!r} fuses the plain (p_s = 1) step; "
            f"the blocking walk at p_s={cfg.p_s} uses the draw paths")
    if cfg.step_impl in ("stream", "auto") and not dg.has_blocked:
        # Inside shard_map the graph arrays are traced, so without the
        # prebuilt slabs "auto" could only ever fall back to the resident
        # kernel — silently recreating the VMEM cap it exists to lift.
        raise ValueError(
            f"step_impl={cfg.step_impl!r} needs the blocked slab layout — "
            "build the graph with build_distributed_graph(g, S, "
            "vertex_block=...)")

    def shard_body(row_ptr, col_idx, deg, edge_src, edge_dst_shard,
                   chan_cnt, col_sorted, *rest):
        *blk, key_data = rest
        row_ptr, col_idx = row_ptr[0], col_idx[0]
        deg, edge_src, edge_dst_shard = deg[0], edge_src[0], edge_dst_shard[0]
        chan_cnt, col_sorted = chan_cnt[0], col_sorted[0]
        blocked = (BlockedCSR(vertex_block=dg.vertex_block,
                              row_off=blk[0][0], deg=blk[1][0], col=blk[2][0])
                   if blk else None)
        has_edge_to = chan_cnt > 0
        chan_off = jnp.cumsum(chan_cnt, axis=-1) - chan_cnt
        me = jax.lax.axis_index(ax)
        base = me * sz
        n_local = jnp.clip(n - base, 1, sz)

        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        k = jax.random.fold_in(key, me)
        k_init, k_run = jax.random.split(k)
        pos0 = base + (
            jax.random.randint(k_init, (B,), 0, 1 << 30, jnp.int32) % n_local
        )
        frogs0 = jnp.where(jnp.arange(B) < f0, pos0, -1)
        counts0 = jax.lax.pcast(
            jnp.zeros((sz + 1,), jnp.int32), (ax,), to="varying"
        )                                               # last bin = trash

        def step(carry, step_key):
            frogs, counts = carry
            valid = frogs >= 0
            v_local = jnp.clip(frogs - base, 0, sz - 1)
            k_die, k_coin, k_draw = jax.random.split(step_key, 3)
            # apply(): deaths tallied where they happen.
            die = jax.random.bernoulli(k_die, cfg.p_T, (B,)) & valid
            if use_fused:
                from repro.kernels import ops

                # One fused kernel pass tallies the deaths *and* draws the
                # successors (col_idx carries global dest ids, so nxt is
                # already a global destination; build_distributed_graph
                # rejects deg-0 vertices — build_csr repairs real ones and
                # partition padding self-loops the rest — so the kernels'
                # local dangling guard can never fire here).
                bits = jax.random.randint(k_draw, (B,), 0, 1 << 30,
                                          jnp.int32)
                nxt, death_counts = ops.frog_step(
                    v_local, die.astype(jnp.int32), bits,
                    row_ptr, col_idx, deg, sz,
                    impl=cfg.step_impl, blocked=blocked,
                )
                counts = counts.at[:-1].add(death_counts)
            else:
                counts = counts.at[jnp.where(die, v_local, sz)].add(1)
            alive = valid & ~die
            # <sync>: one coin per (vertex, mirror shard) — the p_s patch.
            # The coin is a pure hash of (k_coin, v·S + d): this grid (used
            # only for sync accounting + the cumsum reference draw) and the
            # rejection draw's pointwise acceptance checks see identical
            # coins without sharing any materialized state.
            if cfg.p_s < 1.0:
                chan_grid = (
                    jnp.arange(sz, dtype=jnp.int32)[:, None] * S
                    + jnp.arange(S, dtype=jnp.int32)[None, :]
                )
                coins = coin_uniform(k_coin, chan_grid) < cfg.p_s
            else:
                coins = jnp.ones((sz, S), dtype=bool)
            # GraphLab-faithful sync accounting: a message is owed for every
            # (active vertex, existing mirror) pair whose channel opened.
            occ = jnp.zeros((sz + 1,), jnp.int32).at[
                jnp.where(alive, v_local, sz)
            ].add(1)
            active = occ[:sz] > 0
            sync_msgs = (active[:, None] & coins & has_edge_to).sum()
            if use_fused:
                dest = nxt
            else:
                dest = _blocking_draw(
                    v_local, row_ptr, col_idx, deg, edge_src, edge_dst_shard,
                    chan_cnt, chan_off, col_sorted, coins, cfg.p_s, k_draw,
                    draw=draw_mode, alive=alive,
                )
            dest = jnp.where(alive, dest, -1)
            buf, n_sent, ovf = _pack_by_shard(dest, S, sz, cap)
            open_ch = (buf >= 0).any(axis=1).sum()
            recv = jax.lax.all_to_all(
                buf[:, None], ax, split_axis=0, concat_axis=0, tiled=False
            )[:, 0]
            frogs = recv.reshape(B)
            stats = jnp.stack([n_sent.astype(jnp.int32),
                               open_ch.astype(jnp.int32),
                               ovf.astype(jnp.int32),
                               sync_msgs.astype(jnp.int32)])
            return (frogs, counts), stats

        step_keys = jax.random.split(k_run, t)
        (frogs, counts), stats = jax.lax.scan(step, (frogs0, counts0), step_keys)
        # cut-off at t: survivors halt and are tallied (Process 15).
        valid = frogs >= 0
        v_local = jnp.clip(frogs - base, 0, sz - 1)
        counts = counts.at[jnp.where(valid, v_local, sz)].add(1)
        return counts[None, :sz], stats[None]

    return shard_body


def _sharded_fn(dg: DistributedGraph, cfg: EngineConfig, mesh: Mesh):
    rt = ShardRuntime.for_mesh(mesh, cfg.axis_name)
    # jax has no replication rule for pallas_call: the fused step backends
    # need the varying-manual-axes check off (the body is per-shard; the
    # only cross-device op is the all_to_all exchange).
    return rt.shard_map_fn(
        make_shard_body(dg, cfg),
        num_sharded=len(dg.array_specs()), num_replicated=1, num_outputs=2,
        check_vma=cfg.step_impl == "xla",
    )


def distributed_frogwild(
    dg: DistributedGraph, cfg: EngineConfig, mesh: Mesh, seed: int = 0
) -> EngineResult:
    """Deprecated entry point — use :meth:`repro.service.FrogWildService.
    pagerank` with a mesh (or :func:`repro.service.batch_pagerank`).
    Delegates through the service so the answer is byte-identical."""
    warn_deprecated("distributed_frogwild", "FrogWildService.pagerank")
    from repro import service

    return service.batch_pagerank(dg, cfg, mesh=mesh, seed=seed)


def _distributed_frogwild(
    dg: DistributedGraph, cfg: EngineConfig, mesh: Mesh, seed: int = 0
) -> EngineResult:
    """Runs the full FrogWild! process under ``mesh`` and returns π̂ + stats."""
    rt = ShardRuntime.for_mesh(mesh, cfg.axis_name)
    if rt.num_shards != dg.num_shards:
        raise ValueError(
            f"mesh has {rt.num_shards} devices, graph has {dg.num_shards} shards"
        )
    fn = jax.jit(_sharded_fn(dg, cfg, mesh))
    key_data = ShardRuntime.key_data(jax.random.PRNGKey(seed))
    counts, stats = fn(*dg.arrays(), key_data)
    # Replicate before dropping the padding rows: on a mesh with Explicit
    # axes (jax.make_mesh's default) slicing the sharded counts is refused
    # as ambiguous.
    counts = jax.device_put(counts, rt.sharding(replicated=True))
    counts = counts.reshape(-1)[: dg.n]
    stats = np.asarray(stats)                         # [S, t, 4]
    total = (cfg.num_frogs // dg.num_shards) * dg.num_shards
    return EngineResult(
        counts=counts,
        pi_hat=counts.astype(jnp.float32) / total,
        sent_per_step=stats[:, :, 0].sum(axis=0).astype(np.int64),
        open_channels_per_step=stats[:, :, 1].sum(axis=0).astype(np.int64),
        sync_msgs_per_step=stats[:, :, 3].sum(axis=0).astype(np.int64),
        overflow=int(stats[:, :, 2].sum()),
        config=cfg,
    )


def frogwild_dryrun_lowered(dg: DistributedGraph, cfg: EngineConfig, mesh: Mesh):
    """Lowers the identical shard program from ShapeDtypeStructs only —
    the multi-pod dry-run entry point (no graph data, no allocation)."""
    rt = ShardRuntime.for_mesh(mesh, cfg.axis_name)
    sh, rep = rt.sharding(), rt.sharding(replicated=True)
    fn = _sharded_fn(dg, cfg, mesh)
    specs = dg.array_specs() + (jax.ShapeDtypeStruct((2,), jnp.uint32),)
    return jax.jit(
        fn, in_shardings=(sh,) * len(dg.array_specs()) + (rep,)
    ).lower(*specs)
