"""Online query engine: stitch precomputed walk segments into query answers.

A FrogWild walk truncated at ``t`` steps takes ``τ = min(G, t)`` moves with
``P(G = m) = p_T (1 − p_T)^m`` (the Geometric death clock of Process 15, so
``τ`` moves are followed by the tally). The engine samples ``τ`` per walk up
front and composes the τ-step walk from the index:

    τ = q · L + r,   q = τ // L,  r = τ mod L
    → ``r`` direct walker steps, then ``q`` segment stitches (each stitch
      gathers one uniformly-chosen precomputed endpoint of the walk's
      current vertex — an exact sample of ``P^L``).

The composed endpoint is distributed exactly as a τ-step walk
(tests/test_query.py, chi-square + TV against the direct walk) as long as a
walk never rereads a slab cell: round ``j`` reads slot ``(s0 + j) mod R``
(per-walk random offset ``s0``), so cells can only repeat after R stitches —
pick ``R ≥ t/L`` and every gather is a fresh ``P^L`` sample. Sharing cells
*across* walks correlates them (inflating estimator variance FAST-PPR-style
by ≈ ``1 + q̄/R``) but never biases a walk's own marginal.

Per-query planning inverts Theorem 1 at ``p_s = 1`` (index segments are
fully-synced walks): the mixing term bounds ``t``, the ``1/N`` sampling term
bounds the walk count, each at ``ε/2`` — so the served estimate carries the
same ``(ε, δ)`` guarantee as an offline run with those parameters.

Geometry of the work: a query of ``N`` walks costs ``N·(r̄ + τ̄/L)`` gathers
instead of the restart baseline's ``N·τ̄`` CSR draws — the stitch divides
the per-walk step count by ``L`` (benchmarks/bench_query.py measures the
end-to-end queries/sec win).
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import theory
from repro.distributed.runtime import record_wave_trace
from repro.graph.csr import CSRGraph, uniform_successor
from repro.kernels import ops
from repro.query.index import WalkIndex

# Donating the walk-state operands lets XLA write wave outputs into the
# dead input buffers; when a buffer's shape/layout doesn't match any output
# jax emits a UserWarning per compile. That mismatch is expected here (one
# int32[W] output, five donated operands, some bool) and harmless —
# silence exactly that message, nothing else.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """Device-program shape for one query, derived from ``(ε, δ)``.

    ``num_steps`` is the walk truncation ``t`` (mixing term ≤ ε/2) and
    ``num_walks`` the sample count ``N`` (sampling term ≤ ε/2), so Theorem 1
    gives ``μ_k(π̂) > μ_k(π) − ε`` w.p. ≥ 1 − δ — *unless* the caller's
    ``max_steps`` / ``max_walks`` caps truncated the inversion, in which
    case ``epsilon_bound`` (the ε Theorem 1 actually certifies for this
    (t, N)) exceeds the requested ``epsilon``; check it when the guarantee
    matters.
    """

    num_walks: int
    num_steps: int
    epsilon: float               # requested
    delta: float
    k: int
    epsilon_bound: float = 0.0   # achieved (== requested iff no cap bound)

    def num_rounds(self, segment_len: int) -> int:
        """Stitch rounds needed: ``⌊t/L⌋`` (the residual covers ``t mod L``)."""
        return self.num_steps // segment_len


def plan_query(
    k: int,
    epsilon: float,
    delta: float = 0.1,
    p_T: float = 0.15,
    max_walks: Optional[int] = None,
    max_steps: int = 64,
    segments_per_vertex: Optional[int] = None,
    segment_len: Optional[int] = None,
) -> QueryPlan:
    """Inverts Theorem 1 into ``(t, N)`` at ``p_s = 1``.

    mixing_term(p_T, t) ≤ ε/2  ⇔  (1−p_T)^{t+1} ≤ (ε/2)² p_T
    sampling_term = √(k/(δN)) ≤ ε/2  ⇔  N ≥ 4k/(δ ε²)

    With the serving index's ``(segments_per_vertex, segment_len)`` =
    ``(R, L)`` given, ``t`` is additionally clamped to the reuse-free stitch
    budget ``⌊t/L⌋ ≤ R`` (i.e. ``t ≤ R·L + L − 1``): beyond it a walk can
    reread a slab cell and the stitched marginal is biased (see
    :func:`check_segment_budget`), so the plan trades the silent bias for
    an honest, *recorded* truncation — ``epsilon_bound`` then exceeds the
    requested ``epsilon`` exactly as for any other binding cap.
    """
    if not (0.0 < epsilon):
        raise ValueError(f"epsilon must be positive, got {epsilon}")
    if (segments_per_vertex is None) != (segment_len is None):
        raise ValueError(
            "segments_per_vertex and segment_len come as a pair (the "
            "index's (R, L)); got only one of them")
    target = (epsilon / 2.0) ** 2 * p_T
    if target >= 1.0:
        t = 1
    else:
        t = max(1, math.ceil(math.log(target) / math.log(1.0 - p_T) - 1.0))
    t = min(t, max_steps)
    if segments_per_vertex is not None:
        t = min(t, segments_per_vertex * segment_len + segment_len - 1)
    n_walks = max(1, math.ceil(4.0 * k / (delta * epsilon**2)))
    if max_walks is not None:
        n_walks = min(n_walks, max_walks)
    achieved = theory.epsilon_bound(p_T, t, k, delta, n_walks, 1.0, 0.0)
    return QueryPlan(num_walks=n_walks, num_steps=t, epsilon=epsilon,
                     delta=delta, k=k, epsilon_bound=achieved)


def check_segment_budget(segments_per_vertex: int, num_rounds: int) -> None:
    """Warns when the index cannot cover the stitch budget reuse-free.

    The slot rotation only guarantees a walk never rereads a slab cell while
    its stitch count stays ≤ R; with ``num_rounds > R`` a walk that revisits
    a vertex R rounds later rereads a cell and deterministically repeats the
    hop — a small statistical bias. Serving still works, but the exactness
    claim doesn't hold; rebuild the index with R ≥ t/L to restore it.

    Planned queries never get here: :func:`plan_query` given the index's
    ``(R, L)`` clamps ``t`` to the reuse-free budget up front and records
    the truncation in ``epsilon_bound`` — this warning is the safety net
    for hand-built plans / direct ``walk_wave`` callers.
    """
    if num_rounds > segments_per_vertex:
        warnings.warn(
            f"walk index has R={segments_per_vertex} segments/vertex but the "
            f"query plan needs up to {num_rounds} stitch rounds: walks may "
            f"reread segments and the stitched distribution is no longer "
            f"exact. Rebuild with segments_per_vertex ≥ {num_rounds}.",
            stacklevel=3,
        )


def sample_walk_lengths(
    key: jax.Array, num_walks: int, p_T: float, max_steps
) -> jnp.ndarray:
    """``τ ~ min(Geometric(p_T), max_steps)`` per walk (number of moves).

    ``max_steps`` may be a scalar or an int32[W] per-walk truncation (the
    scheduler packs queries with different planned ``t`` into one wave).
    """
    u = jnp.maximum(jax.random.uniform(key, (num_walks,)), 1e-12)
    m = jnp.floor(jnp.log(u) / math.log(1.0 - p_T)).astype(jnp.int32)
    return jnp.clip(m, 0, max_steps).astype(jnp.int32)


def _plain_steps(
    row_ptr: jnp.ndarray,
    col_idx: jnp.ndarray,
    deg: jnp.ndarray,
    pos: jnp.ndarray,
    active_until: jnp.ndarray,   # int32[W] — walk takes steps s < active_until
    key: jax.Array,
    num_steps: int,
) -> jnp.ndarray:
    """``active_until[w]`` masked plain walker steps (the stitch residual)."""
    if num_steps == 0:
        return pos

    def step(carry, k):
        pos, s = carry
        bits = jax.random.randint(k, pos.shape, 0, 1 << 30, jnp.int32)
        nxt = uniform_successor(row_ptr, col_idx, deg, pos, bits)
        pos = jnp.where(s < active_until, nxt, pos)
        return (pos, s + 1), None

    (pos, _), _ = jax.lax.scan(
        step, (pos, jnp.int32(0)), jax.random.split(key, num_steps))
    return pos


@dataclasses.dataclass(frozen=True)
class WaveSpec:
    """Static geometry of one compiled scheduler wave program — the AOT
    ladder cache key (:class:`repro.distributed.runtime.WaveProgramCache`).

    ``(W, Q)`` are the *bucket* shapes (walk slots / query slots the
    operands are padded to), ``(S, sz)`` the shard granularity of the
    eviction mask (``S=1, sz=n`` for a dense slab — the mask never flips),
    and ``q_max`` the static stitch-round budget the ``lax.scan`` runs.
    Everything that changes the traced Python body is in here; arrays
    (slab, graph, walk state) are operands, so two schedulers with equal
    specs share one executable.
    """

    n: int               # graph vertices
    R: int               # segments per vertex
    L: int               # segment length
    q_max: int           # stitch rounds (lax.scan length)
    S: int               # shards (eviction-mask entries)
    sz: int              # shard size (n for dense)
    W: int               # walk-slot bucket
    Q: int               # query-slot bucket
    p_T: float           # geometric stop probability (baked into lengths)
    impl: str            # stitch backend: xla | pallas | ref
    donate: bool         # donate walk-state operands to the executable


def wave_prep(
    row_ptr: jnp.ndarray,
    col_idx: jnp.ndarray,
    deg: jnp.ndarray,
    start: jnp.ndarray,          # int32[W] — pinned start vertex (PPR)
    uniform: jnp.ndarray,        # bool[W]  — True → uniform random start
    t_cap: jnp.ndarray,          # int32[W] — per-walk truncation cap
    key: jax.Array,
    *,
    n: int,
    L: int,
    p_T: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared wave prologue: starts, lengths, residual steps, slot offsets.

    One definition so every dispatch path (fused, mesh, legacy host loop)
    consumes the *same* key stream — the byte-identity contract across
    paths reduces to "same prologue, same rounds".
    Returns ``(pos int32[W], q int32[W], s0 int32[W])``.
    """
    W = start.shape[0]
    k_start, k_tau, k_walk = jax.random.split(key, 3)
    pos0 = jnp.where(
        uniform,
        jax.random.randint(k_start, (W,), 0, n, dtype=jnp.int32),
        start,
    )
    tau = sample_walk_lengths(k_tau, W, p_T, t_cap)
    k_res, k_slot = jax.random.split(k_walk)
    q = tau // L
    pos = _plain_steps(row_ptr, col_idx, deg, pos0, tau % L, k_res, L)
    s0 = jax.random.randint(k_slot, pos.shape, 0, 1 << 30, jnp.int32)
    return pos, q, s0


def build_wave_program(spec: WaveSpec):
    """One fused, jitted wave program for ``spec``: prologue + ``lax.scan``
    over stitch rounds — a single device dispatch where the legacy sharded
    host loop paid ``S × q_max`` of them.

    Signature of the returned program::

        wave(slab_flat, row_ptr, col_idx, deg,
             start, uniform, qid, t_cap, key_data, lost) -> int32[W]

    The result is each walk's final vertex, ``-1`` for a walk that is
    discarded (``qid == Q``: an idle slot) or died on an evicted shard —
    ``4·W`` bytes, whatever ``n``. The scheduler counts them per query.

    ``slab_flat`` is the flat endpoint slab — the dense ``[n, R]`` slab, or
    the sharded index's stacked blocks ``[S·sz, R]`` flattened (row-padded;
    walk positions are graph vertices < n ≤ S·sz, so padding rows are never
    gathered). Because every walk is owned by exactly one shard and the
    other shards contribute the additive identity, gathering from the
    stacked slab is *bit-identical* to the per-shard masked-gather-and-sum
    the host loop runs — which is what lets one program serve both the
    gathered and the sharded single-device paths.

    ``lost`` is the bool[S] eviction mask: a walk that still needs a gather
    while sitting in a lost shard's endpoint range — or whose final vertex
    lands in one — dies (its endpoint is ``-1``).
    All-False masks leave the program bit-identical to an unmasked one.

    With ``spec.donate`` the walk-state operands (start / uniform / qid /
    t_cap / lost) are donated — they are dead after the prologue, so XLA
    may reuse their buffers instead of round-tripping fresh allocations
    every wave. ``key_data`` is never donated (callers re-derive it from a
    live key across fault-supervision retries).
    """
    n, R, L, Q, S, sz = spec.n, spec.R, spec.L, spec.Q, spec.S, spec.sz

    def wave(slab_flat, row_ptr, col_idx, deg,
             start, uniform, qid, t_cap, key_data, lost):
        record_wave_trace(spec)   # executes while tracing, not per call
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        pos, q, s0 = wave_prep(row_ptr, col_idx, deg, start, uniform,
                               t_cap, key, n=n, L=L, p_T=spec.p_T)
        alive = jnp.ones(pos.shape, bool)

        def round_fn(carry, j):
            pos, alive = carry
            alive = alive & ~(lost[jnp.clip(pos // sz, 0, S - 1)] & (j < q))
            if spec.impl == "xla":
                nxt = jnp.take(slab_flat, pos * R + (s0 + j) % R, axis=0)
            else:
                # gather-only stitch kernel: the per-round tally is not
                # computed at all (the host counts the endpoints).
                nxt, _ = ops.stitch_step(
                    pos, (q == j).astype(jnp.int32), s0 + j,
                    slab_flat.reshape(-1, R), n, impl=spec.impl,
                    tally=False)
            pos = jnp.where((j < q) & alive, nxt, pos)
            return (pos, alive), None

        if spec.q_max > 0:
            (pos, alive), _ = jax.lax.scan(
                round_fn, (pos, alive),
                jnp.arange(spec.q_max, dtype=jnp.int32))
        alive = alive & ~lost[jnp.clip(pos // sz, 0, S - 1)]
        return jnp.where(alive & (qid < Q), pos, -1)

    donate = (4, 5, 6, 7, 9) if spec.donate else ()
    return jax.jit(wave, donate_argnums=donate)


def walk_wave(
    row_ptr: jnp.ndarray,
    col_idx: jnp.ndarray,
    deg: jnp.ndarray,
    endpoints: jnp.ndarray,      # int32[n, R] — index slab
    pos0: jnp.ndarray,           # int32[W] — per-walk start vertex
    tau: jnp.ndarray,            # int32[W] — per-walk total moves (≤ L·q_max + L−1)
    key: jax.Array,
    segment_len: int,
    num_rounds: int,             # q_max — static stitch-round budget
    impl: str = "xla",           # xla | pallas | ref — stitch backend
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Advances ``W`` walks by ``τ`` moves each via residual + stitching.

    Returns ``(final_pos int32[W], stop_counts int32[n])``. ``stop_counts``
    comes from the fused gather-and-tally kernel (``impl != "xla"``): round
    ``j`` tallies walks with ``q == j`` while gathering the next segment for
    walks with ``q > j``. With ``impl == "xla"`` the tally is deferred to
    one final histogram over ``final_pos`` — the two are identical because a
    stopped walk's position never changes (tests assert count equality).
    """
    L = segment_len
    n = deg.shape[0]
    R = endpoints.shape[1]
    k_res, k_slot = jax.random.split(key)
    q = tau // L
    r = tau % L
    # residual first: r < L direct steps (order of composition is free —
    # any r + q·L decomposition yields the same τ-step marginal).
    pos = _plain_steps(row_ptr, col_idx, deg, pos0, r, k_res, L)

    # Anti-reuse slot rotation: round j reads slot (s0 + j) mod R. A walk
    # that revisits a vertex therefore never rereads a slab cell while its
    # stitch count stays ≤ R, so every gather is a *fresh* P^L sample and
    # the composed marginal is exact (rereading a cell would deterministically
    # repeat the hop — a measurable bias, see tests). s0 is uniform per walk,
    # so each individual read is still a uniform slot.
    s0 = jax.random.randint(k_slot, pos.shape, 0, 1 << 30, jnp.int32)

    if impl == "xla":
        def round_(carry, j):
            pos, = carry
            nxt = jnp.take(endpoints.reshape(-1),
                           pos * R + (s0 + j) % R, axis=0)
            pos = jnp.where(j < q, nxt, pos)
            return (pos,), None

        if num_rounds > 0:
            (pos,), _ = jax.lax.scan(
                round_, (pos,), jnp.arange(num_rounds, dtype=jnp.int32))
        counts = ops.frog_count(pos, n, impl="ref")
        return pos, counts

    # fused gather-and-tally path: num_rounds + 1 kernel rounds, the last
    # only tallies walks that used the full stitch budget.
    counts = jnp.zeros((n,), jnp.int32)
    for j in range(num_rounds + 1):
        nxt, c = ops.stitch_step(
            pos, (q == j).astype(jnp.int32), s0 + j, endpoints, n, impl=impl)
        counts = counts + c
        pos = jnp.where(j < q, nxt, pos)
    return pos, counts


def query_counts(
    g: CSRGraph,
    index: WalkIndex,
    plan: QueryPlan,
    key: jax.Array,
    source: Optional[int] = None,
    p_T: float = 0.15,
    impl: str = "xla",
) -> jnp.ndarray:
    """Single-query convenience: the stop-counter histogram ``int32[n]``.

    ``source=None`` → global top-k start distribution (uniform over
    vertices, the FrogWild estimator); ``source=v`` → personalized PageRank
    from ``v`` (walk endpoints of Geometric(p_T)-length walks from ``v`` are
    PPR(v) samples with damping 1 − p_T). ``π̂ = counts / num_walks``.
    """
    W = plan.num_walks
    check_segment_budget(index.segments_per_vertex,
                         plan.num_rounds(index.segment_len))
    k_start, k_tau, k_walk = jax.random.split(key, 3)
    if source is None:
        pos0 = jax.random.randint(k_start, (W,), 0, g.n, dtype=jnp.int32)
    else:
        if not 0 <= source < g.n:
            # XLA gathers clamp out-of-range indices, which would silently
            # answer for vertex 0 / n-1 instead of the caller's vertex.
            raise ValueError(f"ppr source {source} outside [0, {g.n})")
        pos0 = jnp.full((W,), source, dtype=jnp.int32)
    tau = sample_walk_lengths(k_tau, W, p_T, plan.num_steps)
    _, counts = walk_wave(
        g.row_ptr, g.col_idx, g.out_deg, index.endpoints,
        pos0, tau, k_walk, index.segment_len,
        plan.num_rounds(index.segment_len), impl=impl,
    )
    return counts
