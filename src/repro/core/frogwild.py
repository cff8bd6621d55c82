"""FrogWild! walker-centric oracle (paper §2.2, Appendix A).

This is the *semantic reference* for the whole system: N discrete walkers
("frogs") start uniformly at random, take at most ``t`` steps following the
original transition matrix P, die with probability ``p_T`` at each apply()
(⇒ Geometric(p_T) lifespans truncated at t — Process 15, provably identical
in distribution to walking the Google matrix Q, Lemma 16), and are tallied
where they stop. The estimator π̂ = c/N (Definition 5).

Partial synchronization is modelled by **edge erasures** (Definition 8):
at every step a random subset of edges is disabled and frogs redraw uniformly
among surviving out-edges of their vertex (the "blocking walk", Process 19).
Three erasure models are implemented:

* ``none``           — p_s = 1, the plain process.
* ``independent``    — Example 9: every edge erased i.i.d. w.p. 1 − p_s.
                       With "at least one out-edge per node" repair
                       (Example 10) so walkers are never lost.
* ``channel``        — edges grouped by destination shard; one coin per
                       (vertex, destination-shard) pair. This is exactly what
                       the distributed engine does (and what the paper's
                       GraphLab patch does per mirror machine); Theorem 1's
                       analysis covers it through Definition 8.

Two interchangeable blocking-walk draws (``cfg.draw``):

* ``rejection`` — per-frog rejection sampling with pointwise keyed-hash
                  coins: O(N · 1/p_s) work per superstep, independent of
                  nnz (see core/blocking.py).
* ``cumsum``    — the direct per-edge mask + cumsum + searchsorted draw:
                  O(nnz) per superstep. Kept as the distributional
                  reference the rejection path is tested against.
* ``auto``      — (default) rejection exactly when its probe budget
                  undercuts the per-edge pass (the paper's N ≪ E regime).

The plain (p_s = 1) step can additionally run through the fused Pallas
``frog_step`` kernels (``cfg.step_impl``: ``xla`` | ``pallas`` | ``stream``
| ``auto`` | ``ref`` — ``stream`` is the HBM-streaming sorted-frog kernel
whose VMEM footprint is bounded by block shapes, not graph size;
``auto`` picks between the resident and streamed kernels by VMEM budget).

Everything is pure JAX (lax.scan over steps) and runs on CPU.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import FrogWildConfig, warn_deprecated
from repro.core.blocking import (channel_enum_draw, coin_uniform,
                                 rejection_blocking_draw,
                                 rejection_is_profitable)
from repro.graph.csr import CSRGraph, uniform_successor
from repro.kernels import ops
from repro.kernels.frog_step_stream import BlockedCSR, block_csr

# FrogWildConfig is defined in repro/config.py (the layered-config module —
# single definition per flag) and re-exported here for back-compat.


@dataclasses.dataclass
class FrogWildResult:
    counts: jnp.ndarray               # int32[n] — c(i), frogs stopped at i
    pi_hat: jnp.ndarray               # f32[n]   — counts / N (Definition 5)
    num_frogs: int


def _kept_mask(
    key: jax.Array,
    g: CSRGraph,
    dst_shard: jnp.ndarray,
    cfg: FrogWildConfig,
) -> jnp.ndarray:
    """Per-edge keep mask for one superstep (cumsum reference path only)."""
    if cfg.erasure == "independent":
        return jax.random.bernoulli(key, cfg.p_s, shape=g.col_idx.shape)
    elif cfg.erasure == "channel":
        # One coin per (source vertex, destination shard): all edges of v
        # going to shard s share the coin — the engine/mirror granularity.
        coins = jax.random.bernoulli(
            key, cfg.p_s, shape=(g.n, cfg.num_shards)
        )
        return coins[g.edge_src, dst_shard]
    raise ValueError(f"unknown erasure model {cfg.erasure!r}")


def draw_next_cumsum(
    g: CSRGraph, cfg: FrogWildConfig, key: jax.Array, pos: jnp.ndarray
) -> jnp.ndarray:
    """One blocking-walk scatter draw, O(nnz) reference implementation."""
    n = g.n
    row_ptr, col_idx, deg = g.row_ptr, g.col_idx, g.out_deg
    dst_shard = g.edge_dst_shard(cfg.num_shards)
    N = pos.shape[0]
    k_mask, k_force, k_draw = jax.random.split(key, 3)
    kept = _kept_mask(k_mask, g, dst_shard, cfg)
    csum = jnp.cumsum(kept.astype(jnp.int32))            # inclusive
    kept_before = jnp.concatenate([jnp.zeros((1,), jnp.int32), csum])
    # surviving out-degree per frog's vertex
    kv = kept_before[row_ptr[pos + 1]] - kept_before[row_ptr[pos]]
    # Example 10 repair: one forced edge per vertex when all erased.
    forced_slot = (
        jax.random.randint(k_force, (n,), 0, 1 << 30, jnp.int32)
        % jnp.maximum(deg, 1)
    )
    forced_edge = row_ptr[jnp.arange(n)] + forced_slot
    # rank among kept edges of the frog's vertex
    u = jax.random.randint(k_draw, (N,), 0, 1 << 30, jnp.int32)
    u = u % jnp.maximum(kv, 1)
    target = kept_before[row_ptr[pos]] + u + 1           # 1-indexed rank
    edge = jnp.searchsorted(csum, target, side="left").astype(jnp.int32)
    edge = jnp.where(kv > 0, edge, forced_edge[pos])
    nxt = col_idx[edge]
    return jnp.where(deg[pos] > 0, nxt, pos)


def draw_next_rejection(
    g: CSRGraph, cfg: FrogWildConfig, key: jax.Array, pos: jnp.ndarray
) -> jnp.ndarray:
    """One blocking-walk scatter draw in O(N) probes, independent of nnz.

    Independent model → edge rejection sampling (O(N · 1/p_s) probes);
    channel model → exact channel enumeration (O(N · S) probes) — rejection
    is not skew-safe at channel granularity (see core/blocking.py).
    """
    if cfg.erasure == "independent":
        chan_of = lambda v, e: e                       # one coin per edge
        edge = rejection_blocking_draw(
            key, pos, g.row_ptr, g.out_deg, cfg.p_s, chan_of
        )
        return jnp.where(g.out_deg[pos] > 0, g.col_idx[edge], pos)
    elif cfg.erasure == "channel":
        S = cfg.num_shards
        col_sorted, chan_cnt, chan_off = g.channel_layout(S)
        k_coin, k_draw = jax.random.split(key)
        chan_ids = pos[:, None] * S + jnp.arange(S, dtype=jnp.int32)[None, :]
        coins_open = coin_uniform(k_coin, chan_ids) < cfg.p_s
        edge = channel_enum_draw(
            k_draw, pos, g.row_ptr[pos], g.out_deg[pos],
            chan_cnt[pos], chan_off[pos], coins_open,
        )
        return jnp.where(g.out_deg[pos] > 0, col_sorted[edge], pos)
    raise ValueError(f"unknown erasure model {cfg.erasure!r}")


def _draw_impl(cfg: FrogWildConfig, num_frogs: int, nnz: int) -> str:
    """``cfg.draw`` with ``auto`` resolved for ``num_frogs`` walkers."""
    if cfg.draw != "auto":
        return cfg.draw
    nc = cfg.num_shards if cfg.erasure == "channel" else None
    return ("rejection"
            if rejection_is_profitable(num_frogs, nnz, cfg.p_s, nc)
            else "cumsum")


def draw_next(
    g: CSRGraph, cfg: FrogWildConfig, key: jax.Array, pos: jnp.ndarray
) -> jnp.ndarray:
    """One scatter draw under ``cfg`` (dispatches on ``cfg.draw``).

    ``auto`` picks rejection exactly when its probe budget undercuts the
    O(nnz) per-edge pass (the paper's N ≪ E regime); both impls remain
    forcible and are distribution-equivalent (tests/test_blocking_draw.py).
    Module-level so tests and benchmarks can exercise a single superstep's
    draw in isolation.
    """
    draw = _draw_impl(cfg, pos.shape[0], g.nnz)
    if draw == "cumsum":
        return draw_next_cumsum(g, cfg, key, pos)
    elif draw == "rejection":
        return draw_next_rejection(g, cfg, key, pos)
    raise ValueError(f"unknown draw impl {cfg.draw!r}")


def frogwild_run(
    g: CSRGraph,
    cfg: FrogWildConfig,
    key: jax.Array,
) -> FrogWildResult:
    """Deprecated entry point — use :meth:`repro.service.FrogWildService.
    pagerank` (or :func:`repro.service.batch_pagerank`). Delegates through
    the service so the answer is byte-identical to the facade's."""
    warn_deprecated("frogwild_run", "FrogWildService.pagerank")
    from repro import service

    return service.batch_pagerank(g, cfg, key=key)


def _uses_erasure(cfg: FrogWildConfig) -> bool:
    return cfg.erasure != "none" and cfg.p_s < 1.0


def _frogwild_walks(
    g: CSRGraph,
    cfg: FrogWildConfig,
    key: jax.Array,
    blocked: Optional[BlockedCSR] = None,
) -> FrogWildResult:
    """Runs the FrogWild! process and returns the stop-counter estimator.
    ``blocked`` is the streamed step's slab layout, for traced graphs."""
    n = g.n
    N, t = cfg.num_frogs, cfg.num_steps
    row_ptr = g.row_ptr
    col_idx = g.col_idx
    deg = g.out_deg
    use_erasure = _uses_erasure(cfg)
    use_fused = (not use_erasure) and cfg.step_impl != "xla"

    k_init, k_loop = jax.random.split(key)
    pos0 = jax.random.randint(k_init, (N,), 0, n, dtype=jnp.int32)
    alive0 = jnp.ones((N,), dtype=bool)
    counts0 = jnp.zeros((n,), dtype=jnp.int32)

    def plain_move(kmove: jax.Array, pos: jnp.ndarray) -> jnp.ndarray:
        bits = jax.random.randint(kmove, (N,), 0, 1 << 30, dtype=jnp.int32)
        return uniform_successor(row_ptr, col_idx, deg, pos, bits)

    def step(carry, step_key):
        pos, alive, counts = carry
        k_die, k_move = jax.random.split(step_key)
        # apply(): each arriving frog dies w.p. p_T and is tallied here.
        die = jax.random.bernoulli(k_die, cfg.p_T, shape=(N,)) & alive
        if use_fused:
            slot_bits = jax.random.randint(k_move, (N,), 0, 1 << 30, jnp.int32)
            nxt, death_counts = ops.frog_step(
                pos, die, slot_bits, row_ptr, col_idx, deg, n,
                impl=cfg.step_impl, blocked=blocked,
            )
            counts = counts + death_counts
        else:
            counts = counts.at[pos].add(die.astype(jnp.int32))
            # scatter(): survivors traverse one (non-erased) out-edge.
            nxt = (draw_next(g, cfg, k_move, pos) if use_erasure
                   else plain_move(k_move, pos))
        alive = alive & ~die
        pos = jnp.where(alive, nxt, pos)
        return (pos, alive, counts), None

    keys = jax.random.split(k_loop, t)
    (pos, alive, counts), _ = jax.lax.scan(step, (pos0, alive0, counts0), keys)
    # cut-off at t: all surviving frogs halt and are tallied (Process 15).
    counts = counts.at[pos].add(alive.astype(jnp.int32))
    pi_hat = counts.astype(jnp.float32) / N
    return FrogWildResult(counts=counts, pi_hat=pi_hat, num_frogs=N)


def _concrete_layouts(g: CSRGraph, cfg: FrogWildConfig):
    """The layouts the walk under ``cfg`` reads that only the concrete
    graph can build: the erasure draw's memoized per-edge arrays (keyed as
    in ``g._derived``) and the streamed step's slab layout (or ``None``)."""
    keys = []
    if _uses_erasure(cfg):
        S = cfg.num_shards
        if _draw_impl(cfg, cfg.num_frogs, g.nnz) == "cumsum":
            g.edge_dst_shard(S)
            keys = [("edge_dst_shard", S)]
            if cfg.erasure == "channel":
                g.edge_src
                keys.append("edge_src")
        elif cfg.erasure == "channel":
            g.channel_layout(S)
            keys = [("channel_layout", S)]
    blocked = None
    if not _uses_erasure(cfg) and ops.step_streams(cfg.step_impl, g.n, g.nnz):
        blocked = block_csr(g.row_ptr, g.col_idx, g.out_deg, g.n)
    return {k: g._derived[k] for k in keys}, blocked


@functools.partial(jax.jit, static_argnames=("n", "cfg", "layout_keys"))
def _walks_program(row_ptr, col_idx, out_deg, layouts, blocked, key, *,
                   n: int, cfg: FrogWildConfig, layout_keys: tuple):
    g = CSRGraph(n=n, row_ptr=row_ptr, col_idx=col_idx, out_deg=out_deg,
                 _derived=dict(zip(layout_keys, layouts)))
    return _as_tuple(_frogwild_walks(g, cfg, key, blocked))


def frogwild_walks(
    g: CSRGraph, cfg: FrogWildConfig, key: jax.Array
) -> FrogWildResult:
    """One jitted FrogWild! run. The graph and its derived layouts are
    operands: closed over, the CSR would be baked into the executable as
    constants (about 700 MB of executable at LiveJournal's size, too large
    for the compile cache, and recompiled for every call)."""
    layouts, blocked = _concrete_layouts(g, cfg)
    counts, pi_hat = _walks_program(
        g.row_ptr, g.col_idx, g.out_deg, tuple(layouts.values()), blocked,
        key, n=g.n, cfg=cfg, layout_keys=tuple(layouts))
    return FrogWildResult(counts=counts, pi_hat=pi_hat,
                          num_frogs=cfg.num_frogs)


def frogwild(
    g: CSRGraph, cfg: FrogWildConfig, seed: int = 0
) -> FrogWildResult:
    return frogwild_walks(g, cfg, jax.random.PRNGKey(seed))


def _as_tuple(r: FrogWildResult):
    return r.counts, r.pi_hat
