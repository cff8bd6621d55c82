"""Host-side continuous-batching query scheduler (fixed-slot design) with
sharded-slab serving and deadline-aware admission.

The device program is one fixed shape — ``max_walks`` walk slots ×
``max_queries`` query slots — and scheduling is pure host logic, exactly the
``serving/scheduler.py`` contract. Each wave:

  admit     queued queries claim free query slots, earliest deadline first;
  allocate  walk slots are split fairly among active queries (equal shares),
            with shares and leftovers handed out in earliest-deadline-first
            order — continuous batching, not generational: a query spanning
            several waves keeps its slot while finished queries free theirs
            mid-flight;
  execute   one wave program advances all walks (residual steps + index
            stitching, ``query/engine.py``) and hands the host each walk's
            final vertex; each query slot counts its own walks' endpoints
            over their support (sorted vertex ids and their counts), so
            nothing per query is sized by ``n``;
  retire    queries whose walk budget completed finalize top-k from their
            counts and release the slot.

**Execution dispatch** (the ``distributed/runtime.py`` layer): with a dense
:class:`~repro.query.index.WalkIndex` the wave is the single-device gathered
program (whole slab resident). With a :class:`~repro.query.index.
ShardedWalkIndex` the slab is *never reassembled*: on a mesh the wave runs
as one ``shard_map`` over the runtime's ``"vertex"`` axis — device ``s``
holds only its ``[shard_size, R]`` slab block, each stitch round routes
every walk to the shard owning its current vertex by endpoint range
(masked local gather), and per-shard partial results are reduced with
``psum``, so the walk state — and the endpoints the wave returns — stay
replicated. On a single device the identical per-shard program runs as the
runtime's host loop, one block resident at a time. All three paths draw
from the same key stream, so with the same slab content they return
byte-identical endpoints, hence answers (tests assert it).

**Admission** is deadline- and queue-depth-aware: ``QueryRequest.slo_s``
declares a latency SLO, and ``submit()`` checks the Theorem-1 ``(t, N)``
plan against the remaining wave budget (measured wave time × waves at full
machine throughput — the FAST-PPR-style per-query budget), charged for the
already-admitted walk demand that outranks the request under EDF
(earlier-or-equal deadlines; no-SLO work is never charged). An infeasible
query is
rejected up front, or — with ``allow_downgrade`` — its walk count is
clamped to what fits and the weakened guarantee is *recorded* in
``QueryPlan.epsilon_bound`` (never a silent miss). Plans are also clamped
to the index's reuse-free stitch budget (``plan_query(segments_per_vertex,
segment_len)``), so an undersized index degrades to an honest, recorded
``epsilon_bound`` instead of a silent statistical bias.

Different queries in one wave may have different planned truncations ``t``
(per-walk ``t_cap``) and different kinds (global top-k draws uniform starts,
personalized PageRank pins the start vertex) — the program shape never
changes, so XLA compiles exactly once per scheduler.

**Anytime serving** (PR 5): per-query tallies track the walks *executed*
so far, and :meth:`QueryScheduler.partial` exposes the estimate together
with the ε Theorem 1 certifies for those walks — monotone non-increasing
wave over wave. A request with ``early_stop`` finishes as soon as that
bound reaches its requested ``epsilon``, even with walk budget left. The
public way to drive all of this is the :class:`repro.service.QueryHandle`
future (``submit()`` / ``run()`` here are deprecation shims kept for the
legacy callers).

**Degradation contract** (PR 6). FrogWild tolerates missing contributions
by design — partial synchronization drops mirror updates and Theorem 1
prices the loss — and the wave supervisor extends that lens to serving
faults:

* a **transient** fault or a wave exceeding ``wave_timeout_s`` is retried
  (bounded by ``max_retries``, exponential backoff + jitter) from the
  *same* wave key, so a successful retry is byte-identical to an unfaulted
  wave; a mesh dispatch that keeps failing fails over once to the
  host-loop dispatch of the identical per-shard program (byte-identical
  answers — failover is principled, not best-effort);
* a **permanent** shard fault evicts the shard: subsequent stitch rounds
  mask its endpoint range (a walk needing a gather from — or a final tally
  in — a lost range is dropped), per-query scores renormalize by the walks
  that actually completed, and ``epsilon_bound`` widens to exactly the ε
  Theorem 1 certifies for those surviving walks (the early-stopping
  accounting applied to loss instead of budget). Results carry
  ``degraded`` / ``shards_lost`` / ``walks_lost`` provenance, queued SLO
  work is re-admitted against the shrunken capacity, and with zero faults
  the masked programs are bit-for-bit the unfaulted ones;
* retried / stalled / degraded waves never feed the admission wave-time
  EMA (and clean outliers are clamped), so one bad wave cannot poison
  ``wave_time_estimate_s`` into spurious SLO rejections.

Injection (:class:`~repro.distributed.faults.FaultPlan`) drives all of the
above deterministically in-process; ``WaveFailedError`` is the only way a
wave surfaces an error, and it leaves no partial tallies behind.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import math
import random
import time
from typing import Dict, List, Optional, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.config import warn_deprecated
from repro.core import theory
from repro.distributed.faults import (FaultEvent, FaultInjector, ShardFault,
                                      WaveFailedError, WaveTimeout)
from repro.distributed.runtime import ShardRuntime, record_wave_trace
from repro.graph.csr import CSRGraph
from repro.kernels import ops
from repro.query.engine import (QueryPlan, WaveSpec, build_wave_program,
                                plan_query, wave_prep)
from repro.query.index import ShardedWalkIndex, WalkIndex

# A "clean" wave more than this factor above the EMA is clamped before the
# fold — one GC pause or page-fault storm must not trip SLO rejections.
_EMA_OUTLIER_CLAMP = 4.0


def _topk_stable(scores: np.ndarray, k: int) -> np.ndarray:
    """First ``k`` indices of ``np.argsort(-scores, kind="stable")`` without
    sorting all ``n`` scores.

    The scheduler ranks a query's counts over their support with it, at
    every :meth:`QueryScheduler.partial` snapshot and at finalize.
    ``np.partition`` finds the k-th largest in O(n); the candidate set
    ``scores >= kth`` is a superset of the stable top-k (it includes every
    boundary tie), and a stable descending sort of just the candidates
    reproduces the full sort's relative order.
    """
    n = scores.shape[0]
    if k >= n:
        return np.argsort(-scores, kind="stable")[:k]
    kth = np.partition(scores, n - k)[n - k]
    cand = np.flatnonzero(scores >= kth)
    return cand[np.argsort(-scores[cand], kind="stable")][:k]


@dataclasses.dataclass
class QueryRequest:
    rid: int
    kind: str = "topk"               # "topk" | "ppr"
    k: int = 10
    source: int = 0                  # PPR start vertex (ignored for topk)
    epsilon: float = 0.3
    delta: float = 0.1
    num_walks: Optional[int] = None  # override the (ε, δ) plan's walk count
    slo_s: Optional[float] = None    # latency SLO (deadline = submit + slo_s)
    allow_downgrade: bool = False    # shrink the plan to fit the SLO budget
    early_stop: bool = False         # finish once the anytime Theorem-1
                                     # bound reaches epsilon (QueryHandle mode)
    t_submit: Optional[float] = None # stamped by QueryScheduler.submit()


class RejectReason(str, enum.Enum):
    """Why admission refused a request — structured, so a routing layer
    (the gateway) can branch on it without string-matching ``reason``.

    * ``NONE``           — not rejected (the decision admitted the request).
    * ``INFEASIBLE_SLO`` — the SLO is shorter than a single wave: no walk
      budget could ever fit it, retrying elsewhere with the same SLO is
      pointless.
    * ``CAPACITY``       — the Theorem-1 plan (plus the EDF-charged
      backlog) needs more waves than the SLO leaves; another, less loaded
      replica may well admit it.
    * ``SHARD_LOSS``     — a post-admission re-check after shard eviction
      shrank capacity; the replica is degraded and a healthy replica
      should be preferred.
    """

    NONE = "none"
    INFEASIBLE_SLO = "infeasible_slo"
    CAPACITY = "capacity"
    SHARD_LOSS = "shard_loss"


@dataclasses.dataclass(frozen=True)
class AdmissionDecision:
    """What the admission controller did with a ``submit()``.

    ``admitted=False`` means the request was dropped at the door (its
    Theorem-1 plan cannot fit the remaining wave budget before the
    deadline) with the *kind* of refusal in ``reason_code`` (a
    :class:`RejectReason`) and the human-readable detail in ``reason``;
    ``downgraded=True`` means it was admitted with a clamped walk count
    whose weaker guarantee is recorded in ``plan.epsilon_bound``.
    """

    rid: int
    admitted: bool
    reason: str = ""
    reason_code: RejectReason = RejectReason.NONE
    downgraded: bool = False
    plan: Optional[QueryPlan] = None
    num_walks: int = 0


@dataclasses.dataclass
class QueryResult:
    rid: int
    kind: str
    vertices: np.ndarray             # int64[k] — estimated top-k
    scores: np.ndarray               # f64[k]  — π̂ / PPR estimates
    num_walks: int                   # walks actually executed (≤ budget)
    num_steps: int
    waves: int                       # device waves this query spanned
    latency_s: float
    epsilon_bound: float = 0.0       # the ε Theorem 1 certifies for (t, N)
    downgraded: bool = False         # admission shrank the plan to fit SLO
    met_slo: Optional[bool] = None   # None when no SLO was requested
    early_stopped: bool = False      # anytime bound met before the budget
    degraded: bool = False           # some walks died on evicted shards
    shards_lost: Tuple[int, ...] = ()  # shards evicted while this query ran
    walks_lost: int = 0              # allocated walks that never tallied
    epoch: int = 0                   # graph epoch this query was served on


@dataclasses.dataclass(frozen=True)
class QueryPartial:
    """Anytime snapshot of an in-flight (or finished) query.

    ``epsilon_bound`` is the ε Theorem 1 certifies for the walks tallied
    *so far* (``math.inf`` before the first wave lands); it tightens
    monotonically as waves accumulate — the anytime property the
    :class:`repro.service.QueryHandle` future exposes.
    """

    rid: int
    kind: str
    k: int
    vertices: np.ndarray             # int64[≤k] — current top-k estimate
    scores: np.ndarray               # f64[≤k]
    walks_done: int
    waves: int
    epsilon_bound: float
    done: bool
    degraded: bool = False
    shards_lost: Tuple[int, ...] = ()
    walks_lost: int = 0


@dataclasses.dataclass(frozen=True)
class SchedulerStats:
    """One structured snapshot of the scheduler's serving/admission state.

    ``backlog_walks`` is the scheduler's own admission accounting — the
    queued plus in-flight walk demand a new no-SLO request would be
    EDF-charged behind (every outstanding deadline outranks ∞). The
    gateway's replica router keys on it; everything else feeds the
    metrics/health layer.
    """

    queued: int                      # requests waiting for a query slot
    active: int                      # requests occupying a slot
    finished: int                    # results retired so far
    rejected: int                    # admission refusals so far
    cancelled: int
    backlog_walks: int               # queued + in-flight walk demand
    waves_run: int
    walks_executed: int              # walks whose tallies landed
    wave_time_ema_s: Optional[float]
    wave_occupancy: float            # allocated walk slots / capacity
    lost_shards: Tuple[int, ...]
    max_walks: int
    max_queries: int
    epoch: int = 0                   # graph epoch this scheduler serves
    # bytes of wave result copied to the host, summed over waves: each
    # wave's int32[W_b] endpoints, 4·W_b bytes whatever n is
    tally_bytes_out: int = 0


@dataclasses.dataclass
class _Queued:
    req: QueryRequest
    plan: QueryPlan
    walks: int
    deadline: float                  # math.inf when no SLO
    downgraded: bool


def _empty() -> np.ndarray:
    return np.zeros(0, np.int64)


@dataclasses.dataclass
class _Active:
    req: QueryRequest
    plan: QueryPlan
    remaining: int
    total_walks: int
    waves: int
    t_submit: float
    deadline: float
    downgraded: bool
    executed: int = 0                # walks whose tallies have landed
    lost: int = 0                    # allocated walks that died on a lost shard
    shards_lost: Tuple[int, ...] = ()  # evicted shards seen by this query
    # the endpoint count over its support: sorted vertex ids, their counts
    # (every vertex outside ``ids`` has count 0)
    ids: np.ndarray = dataclasses.field(default_factory=_empty)
    hits: np.ndarray = dataclasses.field(default_factory=_empty)

    def count(self, ends: np.ndarray) -> None:
        """Adds one wave's landed endpoints (vertex ids ≥ 0) to the count:
        O(m + w log(m + w)) for a support of m and w endpoints."""
        new, c = np.unique(ends, return_counts=True)
        at = np.searchsorted(self.ids, new)
        seen = at < self.ids.size
        seen[seen] = self.ids[at[seen]] == new[seen]
        self.hits[at[seen]] += c[seen]
        self.ids = np.insert(self.ids, at[~seen], new[~seen])
        self.hits = np.insert(self.hits, at[~seen], c[~seen])

    def top(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """``(vertices, counts)`` of the first ``k`` (≤ n) entries of
        ``np.argsort(-dense, kind="stable")`` over the dense count vector:
        the support ranked by ``_topk_stable`` (ids ascending, so ties keep
        the dense order), then, if the support is smaller than ``k``, the
        zeros in ascending id order — the smallest ids outside it."""
        at = _topk_stable(self.hits, k)
        vertices, hits = self.ids[at], self.hits[at]
        if vertices.size < k:
            pad = np.setdiff1d(np.arange(k), self.ids)[:k - vertices.size]
            vertices = np.concatenate([vertices, pad])
            hits = np.concatenate([hits, np.zeros(pad.size, np.int64)])
        return vertices, hits


class QueryScheduler:
    def __init__(
        self,
        g: CSRGraph,
        index: Union[WalkIndex, ShardedWalkIndex],
        max_walks: int = 8192,
        max_queries: int = 8,
        max_steps: int = 32,
        p_T: float = 0.15,
        impl: str = "xla",
        seed: int = 0,
        runtime: Optional[ShardRuntime] = None,
        wave_time_estimate_s: Optional[float] = None,
        fault_injector: Optional[FaultInjector] = None,
        wave_timeout_s: Optional[float] = None,
        max_retries: int = 2,
        backoff_base_s: float = 0.02,
        backoff_max_s: float = 0.5,
        sharded_dispatch: str = "fused",
        donate_wave_buffers: bool = True,
        walk_buckets: Optional[Tuple[int, ...]] = None,
        query_buckets: Optional[Tuple[int, ...]] = None,
        aot_warmup: bool = False,
    ):
        if sharded_dispatch not in ("fused", "loop"):
            raise ValueError(
                f"sharded_dispatch must be 'fused' or 'loop', got "
                f"{sharded_dispatch!r}")
        self.g = g
        self.index = index
        # the epoch this scheduler serves, pinned at construction: a
        # mutation commit builds a *new* scheduler for e+1 and retires
        # this one once its pinned queries settle (two-epoch serving).
        self.epoch = int(getattr(g, "epoch", 0))
        self.max_walks = max_walks
        self.max_queries = max_queries
        self.max_steps = max_steps
        self.p_T = p_T
        self.impl = impl
        self.donate_wave_buffers = donate_wave_buffers
        # AOT wave-program ladder: waves run at the smallest bucket shape
        # ≥ the allocation, so the set of compiled programs is fixed up
        # front (hyadmin-style per-batch-size wrappers) — a shifting query
        # mix re-buckets instead of retracing. The top bucket is always
        # the full (max_walks, max_queries) shape.
        self._walk_ladder = self._normalize_buckets(
            walk_buckets, max_walks, "walk_buckets",
            floor=max(1, max_walks // 8))
        self._query_ladder = self._normalize_buckets(
            query_buckets, max_queries, "query_buckets", floor=1)
        self._wave_fns: Dict[Tuple[int, int], object] = {}
        self.queue: List[_Queued] = []
        self.active: Dict[int, _Active] = {}
        self.finished: List[QueryResult] = []
        self.rejected: List[AdmissionDecision] = []
        self.cancelled: List[int] = []
        self._key = jax.random.PRNGKey(seed)
        self._wave_time = wave_time_estimate_s   # EMA of measured wave s
        self._waves_run = 0
        self._walks_allocated = 0    # walk slots handed out across all waves
        self._walks_executed = 0     # walks whose tallies actually landed
        self._tally_bytes_out = 0    # endpoint bytes copied device → host
        # --- fault-tolerance state (PR 6) ---
        self._injector = fault_injector
        self.wave_timeout_s = wave_timeout_s
        self.max_retries = max_retries
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.lost_shards: Set[int] = set()
        self.fault_log: List[FaultEvent] = []
        self._backoff_rng = random.Random(seed)
        self._failed_over = False
        # bool[S] eviction mask, a replicated wave operand: all-False (the
        # zero-fault case) leaves every masked program bit-identical to the
        # unmasked one. Dense slabs get a 1-wide mask that never flips.
        self._lost = np.zeros(
            index.num_shards if isinstance(index, ShardedWalkIndex) else 1,
            bool)
        self._placed_blocks = self._placed_csr = None
        if isinstance(index, ShardedWalkIndex):
            self.runtime = (runtime if runtime is not None
                            else ShardRuntime.acquire(index.num_shards))
            if self.runtime.num_shards != index.num_shards:
                raise ValueError(
                    f"runtime has {self.runtime.num_shards} shards, index "
                    f"has {index.num_shards}")
            self._S = index.num_shards
            self._sz = index.shard_size
            # stacked blocks flattened = the row-padded dense slab. Walk
            # positions are graph vertices < n ≤ S·sz, so the fused wave's
            # gathers never touch the padding rows — which is what makes
            # one program byte-identical to the per-shard host loop.
            self._slab_flat = jnp.asarray(
                index.blocks.reshape(self._S * self._sz, -1)).reshape(-1)
            if self.runtime.is_mesh:
                self.dispatch = "mesh"
                # kept as an attribute so tests can assert the per-device
                # placement (each device holds exactly one [shard_size, R]
                # block — 4nR/S bytes of slab, never the whole thing).
                self._placed_blocks = self.runtime.place_sharded(
                    jnp.asarray(index.blocks))
                # the CSR rides into the mesh program as a replicated
                # operand, not as constants baked into its executable
                self._placed_csr = jax.device_put(
                    (g.row_ptr, g.col_idx, g.out_deg),
                    self.runtime.sharding(replicated=True))
            else:
                self.dispatch = sharded_dispatch   # "fused" | legacy "loop"
        else:
            self.runtime = runtime
            self.dispatch = "gathered"   # the fused program at S=1
            self._S, self._sz = 1, g.n
            self._slab_flat = jnp.asarray(index.endpoints).reshape(-1)
        if aot_warmup:
            self.warm_ladder()

    # --- device programs (one per ladder bucket, compiled AOT or lazily) --

    @property
    def _q_max(self) -> int:
        return self.max_steps // self.index.segment_len

    @staticmethod
    def _normalize_buckets(buckets: Optional[Tuple[int, ...]], cap: int,
                           name: str, floor: int) -> Tuple[int, ...]:
        """Validates a user ladder (or derives the default: ``cap`` and its
        halvings down to ``floor``). The full shape ``cap`` is always a
        member — the top bucket must fit a fully-allocated wave."""
        if buckets is None:
            out = {cap}
            b = cap
            while b // 2 >= floor:
                b //= 2
                out.add(b)
            return tuple(sorted(out))
        ladder = sorted(set(int(b) for b in buckets))
        if not ladder or ladder[0] < 1 or ladder[-1] > cap:
            raise ValueError(
                f"{name} must be within [1, {cap}], got {buckets!r}")
        if ladder[-1] != cap:
            ladder.append(cap)
        return tuple(ladder)

    @staticmethod
    def _bucket(ladder: Tuple[int, ...], demand: int) -> int:
        """Smallest ladder bucket ≥ demand (the ladder top bounds demand)."""
        for b in ladder:
            if b >= demand:
                return b
        return ladder[-1]

    def _spec(self, W_b: int, Q_b: int) -> WaveSpec:
        return WaveSpec(
            n=self.g.n, R=self.index.segments_per_vertex,
            L=self.index.segment_len, q_max=self._q_max,
            S=self._S, sz=self._sz, W=W_b, Q=Q_b, p_T=self.p_T,
            impl=self.impl, donate=self.donate_wave_buffers)

    def _wave_for(self, W_b: int, Q_b: int):
        """The wave callable for one ladder bucket, built on first use and
        cached — ``wave(start, uniform, qid, t_cap, key, lost)`` with every
        operand at bucket shape. Every dispatch path returns the walks'
        endpoints as a device ``int32[W_b]`` (``-1`` for a discarded or dead
        walk); :meth:`_tally_to_host` brings them to the host."""
        fn = self._wave_fns.get((W_b, Q_b))
        if fn is None:
            if self.dispatch == "mesh":
                fn = self._build_mesh_wave(W_b, Q_b)
            elif self.dispatch == "loop":
                fn = self._build_loop_wave(W_b, Q_b)
            else:
                fn = self._build_fused_wave(W_b, Q_b)
            self._wave_fns[(W_b, Q_b)] = fn
        return fn

    def warm_ladder(self) -> int:
        """AOT-compiles the whole ladder: one dummy wave per (walk-bucket,
        query-bucket) pair, so serving never traces mid-wave — an
        admission-driven change of query mix re-buckets into a warm
        executable. Scheduler state (key stream, EMA, counters) is
        untouched. Returns the number of programs warmed."""
        key = jax.random.PRNGKey(0)   # shapes drive compilation, not bits
        count = 0
        for W_b in self._walk_ladder:
            for Q_b in self._query_ladder:
                wave = self._wave_for(W_b, Q_b)
                jax.block_until_ready(wave(
                    jnp.zeros(W_b, jnp.int32), jnp.zeros(W_b, bool),
                    jnp.full(W_b, Q_b, jnp.int32),
                    jnp.zeros(W_b, jnp.int32), key,
                    jnp.asarray(self._lost)))
                count += 1
        return count

    def _build_fused_wave(self, W_b: int, Q_b: int):
        """The fused single-dispatch wave (gathered and sharded host-side
        serving): prologue + ``lax.scan`` over stitch rounds, compiled once
        per :class:`WaveSpec` in the process-wide
        :meth:`ShardRuntime.wave_cache` — replicas over the same slab
        geometry share the executable (slab and graph arrays are
        operands, not closures)."""
        prog = ShardRuntime.wave_cache().get_or_build(
            self._spec(W_b, Q_b), build_wave_program)
        g = self.g

        def wave(start, uniform, qid, t_cap, key, lost):
            return prog(self._slab_flat, g.row_ptr, g.col_idx, g.out_deg,
                        start, uniform, qid, t_cap,
                        ShardRuntime.key_data(key), lost)

        return wave

    def _shard_round(self, block_flat, base, pos, q, s0, j):
        """One stitch round against one shard's slab block: owned walks
        gather their next endpoint, everyone else contributes the additive
        identity — results sum across shards (psum / host sum). Fully
        traced-``j`` compatible, so it runs under the mesh wave's
        ``lax.scan`` as well as the legacy unrolled host loop."""
        R = self.index.segments_per_vertex
        sz = self.index.shard_size
        if self.impl == "xla":
            slot = (s0 + j) % R
            local = pos - base
            mine = (local >= 0) & (local < sz)
            li = jnp.clip(local, 0, sz - 1)
            nxt = jnp.take(block_flat, li * R + slot, axis=0)
            return jnp.where(mine & (j < q), nxt, 0)
        # gather-only local-index stitch kernel ("pallas" | "ref"): the
        # host counts the final positions, so the per-round tally is not
        # computed at all (tally=False).
        nxt, _ = ops.stitch_step_local(
            pos, (q == j).astype(jnp.int32), s0 + j,
            block_flat.reshape(sz, R), base, impl=self.impl, tally=False)
        return jnp.where(j < q, nxt, 0)

    def _stitch_rounds(self, pos, q, round_fn, lost_of=None):
        """Applies ``q_max`` stitch rounds where ``round_fn(pos, j)`` sums
        per-shard contributions; stopped walks (``j ≥ q``) keep their
        position. This is the legacy *unrolled* round loop, kept under the
        ``sharded_dispatch="loop"`` path as the reference the fused
        ``lax.scan`` waves are byte-compared against.

        ``lost_of(pos) -> bool[W]`` marks walks sitting in an evicted
        shard's endpoint range. A walk that still needs a gather from a
        lost range (``j < q``) — or whose *final* vertex lands in one —
        dies: ``alive`` goes False and its position freezes, so the wave
        can return ``-1`` for it. With no evictions the mask is
        all-False and the emitted program is bit-identical to the unmasked
        one. Returns ``(pos, alive)`` (``alive is None`` without a mask).
        """
        alive = None
        if lost_of is not None:
            alive = jnp.ones(pos.shape, bool)
        for j in range(self._q_max):
            if lost_of is not None:
                alive = alive & ~(lost_of(pos) & (j < q))
            nxt = round_fn(pos, j)
            adv = (j < q) if alive is None else ((j < q) & alive)
            pos = jnp.where(adv, nxt, pos)
        if lost_of is not None:
            alive = alive & ~lost_of(pos)
        return pos, alive

    def _build_mesh_wave(self, W_b: int, Q_b: int):
        """Sharded wave: one ``shard_map`` over the runtime's vertex axis.

        Device ``s`` holds only slab block ``s`` (``in_specs=P(axis)``);
        walk state is replicated and advanced identically on every device,
        with the per-round gather contribution reduced by ``psum`` inside
        one ``lax.scan`` over the stitch rounds — one dispatch per wave,
        same as the fused single-device program. The endpoints are
        replicated too, so the output is too (``out_specs=P()``): the host
        copies one device's ``int32[W]``. Walk-state operands are
        donated. Mesh programs close over the (unhashable) mesh, so they
        cache per-scheduler in ``_wave_fns``, not in the process-wide
        ladder cache.
        """
        rt, index, g = self.runtime, self.index, self.g
        Q = Q_b
        S = rt.num_shards
        sz = index.shard_size
        ax = rt.axis_name
        spec = self._spec(W_b, Q_b)

        def body(blocks, row_ptr, col_idx, deg, start, uniform, qid, t_cap,
                 key_data, lost):
            record_wave_trace(spec)
            block_flat = blocks[0].reshape(-1)
            base = jax.lax.axis_index(ax) * sz
            key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
            pos, q, s0 = wave_prep(
                row_ptr, col_idx, deg, start, uniform, t_cap,
                key, n=g.n, L=index.segment_len, p_T=self.p_T)
            alive = jnp.ones(pos.shape, bool)

            def round_fn(carry, j):
                pos, alive = carry
                # an evicted shard's range is masked identically on every
                # device (``lost`` is replicated) — the mesh simulates
                # loss; real device loss fails over to the fused path.
                alive = alive & ~(lost[jnp.clip(pos // sz, 0, S - 1)]
                                  & (j < q))
                contrib = self._shard_round(block_flat, base, pos, q, s0, j)
                # every walk is owned by exactly one shard; stopped walks
                # contribute 0 everywhere and keep their position.
                nxt = jax.lax.psum(contrib, ax)
                pos = jnp.where((j < q) & alive, nxt, pos)
                return (pos, alive), None

            if self._q_max > 0:
                (pos, alive), _ = jax.lax.scan(
                    round_fn, (pos, alive),
                    jnp.arange(self._q_max, dtype=jnp.int32))
            alive = alive & ~lost[jnp.clip(pos // sz, 0, S - 1)]
            return jnp.where(alive & (qid < Q), pos, -1)

        # check_vma=False: the fused stitch backends lower through
        # pallas_call (no replication rule), and the body mixes replicated
        # walk state with per-shard slab blocks by construction. Donation
        # skips the blocks and the CSR (operands 0-3, reused every wave)
        # and key_data.
        donate = (4, 5, 6, 7, 9) if self.donate_wave_buffers else ()
        fn = rt.sharded_call(body, num_sharded=1, num_replicated=9,
                             check_vma=False, donate_argnums=donate,
                             replicated_out=True)
        blocks, csr = self._placed_blocks, self._placed_csr

        def wave(start, uniform, qid, t_cap, key, lost):
            return fn(blocks, *csr, start, uniform, qid, t_cap,
                      ShardRuntime.key_data(key), lost)

        return wave

    def _build_loop_wave(self, W_b: int, Q_b: int):
        """Legacy sharded wave on a single device: the host-loop dispatch
        of the per-shard program — S × q_max separate device calls per
        wave, cross-shard sums on the host. Superseded as the default by
        the fused single-dispatch program (``sharded_dispatch="fused"``);
        kept selectable because it is the structural reference the fused
        wave is byte-compared against in tests and the bench smoke."""
        rt, index, g = self.runtime, self.index, self.g
        Q = Q_b
        S = rt.num_shards
        sz = index.shard_size

        # the CSR is an operand: closed over, it would be baked into the
        # executable as constants
        prep = jax.jit(functools.partial(
            wave_prep, n=g.n, L=index.segment_len, p_T=self.p_T))
        round_s = jax.jit(self._shard_round)
        blocks = [jnp.asarray(index.blocks[s].reshape(-1))
                  for s in range(rt.num_shards)]

        def wave(start, uniform, qid, t_cap, key, lost):
            pos, q, s0 = prep(g.row_ptr, g.col_idx, g.out_deg, start,
                              uniform, t_cap, key)
            lost_host = np.asarray(lost)

            def round_fn(pos, j):
                # an evicted shard's block is genuinely never touched on
                # this path: walks needing it are dead (masked below), so
                # skipping its contribution changes no surviving value.
                contribs = [
                    round_s(blocks[s], jnp.int32(s * sz),
                            pos, q, s0, jnp.int32(j))
                    for s in range(S) if not lost_host[s]]
                return sum(contribs)

            lost_of = lambda p: lost[jnp.clip(p // sz, 0, S - 1)]
            pos, alive = self._stitch_rounds(pos, q, round_fn, lost_of)
            return jnp.where(alive & (qid < Q), pos, -1)

        return wave

    # --- admission (deadline-aware) --------------------------------------

    def submit(self, req: QueryRequest) -> AdmissionDecision:
        """Deprecated entry point — use :meth:`repro.service.
        FrogWildService.topk` / :meth:`~repro.service.FrogWildService.ppr`,
        whose :class:`~repro.service.QueryHandle` futures delegate here."""
        warn_deprecated("QueryScheduler.submit", "FrogWildService.topk/ppr")
        return self._submit(req)

    def _submit(self, req: QueryRequest) -> AdmissionDecision:
        """Validates, plans, and admission-checks a request.

        Returns the :class:`AdmissionDecision`; rejected requests are
        recorded in ``self.rejected`` and never enter the queue. The
        latency clock starts here, so queue wait counts toward both
        ``latency_s`` and the SLO.
        """
        if req.num_walks is not None and req.num_walks <= 0:
            raise ValueError(
                f"request {req.rid}: num_walks must be positive, got "
                f"{req.num_walks}")
        if req.kind == "ppr" and not (0 <= req.source < self.g.n):
            raise ValueError(
                f"request {req.rid}: ppr source {req.source} outside "
                f"[0, {self.g.n})")
        if req.kind not in ("topk", "ppr"):
            raise ValueError(f"request {req.rid}: unknown kind {req.kind!r}")
        if req.slo_s is not None and req.slo_s <= 0:
            raise ValueError(
                f"request {req.rid}: slo_s must be positive, got {req.slo_s}")
        if req.t_submit is None:
            req.t_submit = time.perf_counter()

        # the plan is clamped to the index's reuse-free stitch budget — an
        # undersized index yields a recorded epsilon_bound, not a bias.
        plan = plan_query(
            req.k, req.epsilon, req.delta, p_T=self.p_T,
            max_steps=self.max_steps,
            segments_per_vertex=self.index.segments_per_vertex,
            segment_len=self.index.segment_len)
        walks = req.num_walks if req.num_walks is not None else plan.num_walks
        downgraded = False

        if req.slo_s is not None and self._wave_time is not None:
            # Remaining wave budget under the SLO at full-machine
            # throughput (max_walks walks per wave) — charged for *queue
            # depth*: already-admitted walk demand whose deadline is at or
            # before this request's outranks it under EDF and drains from
            # the same wave budget first (no-SLO work, deadline = ∞, is
            # never charged — EDF orders it behind every deadline). This
            # is an estimate, not a certainty: fair-share allocation still
            # guarantees every active query its per-wave share, so a
            # charged query can finish sooner than the model says — the
            # estimate deliberately errs toward protecting the SLOs
            # already admitted.
            deadline_new = req.t_submit + req.slo_s
            backlog = (sum(e.walks for e in self.queue
                           if e.deadline <= deadline_new)
                       + sum(a.remaining for a in self.active.values()
                             if a.deadline <= deadline_new))
            feasible = int(req.slo_s / self._wave_time)
            eff = self._effective_walks()
            needed = -(-(walks + backlog) // eff)
            if feasible < 1:
                return self._reject(
                    req, plan,
                    f"SLO {req.slo_s:.3g}s is shorter than one wave "
                    f"(≈{self._wave_time:.3g}s)",
                    RejectReason.INFEASIBLE_SLO)
            if needed > feasible:
                budget = feasible * eff - backlog
                if not req.allow_downgrade or budget < 1:
                    return self._reject(
                        req, plan,
                        f"plan needs {needed} waves ({backlog} walks "
                        f"queued ahead at earlier deadlines), only "
                        f"{feasible} fit the {req.slo_s:.3g}s SLO",
                        RejectReason.CAPACITY)
                plan = plan_query(
                    req.k, req.epsilon, req.delta, p_T=self.p_T,
                    max_walks=budget, max_steps=self.max_steps,
                    segments_per_vertex=self.index.segments_per_vertex,
                    segment_len=self.index.segment_len)
                walks = min(budget, plan.num_walks if req.num_walks is None
                            else req.num_walks)
                downgraded = True

        deadline = (math.inf if req.slo_s is None
                    else req.t_submit + req.slo_s)
        self.queue.append(_Queued(req=req, plan=plan, walks=walks,
                                  deadline=deadline, downgraded=downgraded))
        return AdmissionDecision(rid=req.rid, admitted=True,
                                 downgraded=downgraded, plan=plan,
                                 num_walks=walks)

    def _reject(self, req: QueryRequest, plan: QueryPlan, reason: str,
                code: RejectReason) -> AdmissionDecision:
        decision = AdmissionDecision(rid=req.rid, admitted=False,
                                     reason=reason, reason_code=code,
                                     plan=plan)
        self.rejected.append(decision)
        return decision

    # --- host scheduling --------------------------------------------------

    def _admit(self) -> None:
        """Queued queries claim free slots, earliest deadline first."""
        free = [s for s in range(self.max_queries) if s not in self.active]
        self.queue.sort(key=lambda e: (e.deadline, e.req.t_submit))
        while self.queue and free:
            e = self.queue.pop(0)
            self.active[free.pop(0)] = _Active(
                req=e.req, plan=e.plan, remaining=e.walks,
                total_walks=e.walks, waves=0, t_submit=e.req.t_submit,
                deadline=e.deadline, downgraded=e.downgraded,
            )

    def _edf_order(self) -> List[int]:
        return sorted(self.active,
                      key=lambda s: (self.active[s].deadline, s))

    def _allocate(self) -> Dict[int, int]:
        """Walk-slot split: equal shares, handed out (and topped up from
        the leftovers) in earliest-deadline-first order — a tight-deadline
        query drains its budget first without starving the rest below
        their fair share."""
        slots = {}
        budget = self.max_walks
        order = self._edf_order()
        share = max(1, budget // max(1, len(order)))
        for s in order:
            take = min(self.active[s].remaining, share, budget)
            slots[s] = take
            budget -= take
        for s in order:                      # leftovers, EDF-greedy
            if budget == 0:
                break
            extra = min(self.active[s].remaining - slots[s], budget)
            slots[s] += extra
            budget -= extra
        return {s: w for s, w in slots.items() if w > 0}

    def step_wave(self) -> bool:
        """Runs one device wave; returns False when nothing is in flight.

        The wave runs at the smallest ladder bucket that fits the
        allocation — walk slots padded to ``W_b``, query slots *compacted*
        (EDF allocation order) into query ids ``[0, Q_b)``, each slot's
        walks one contiguous slice, which its endpoints come back in.
        Bucket choice is a pure function of
        host-side scheduler state, so every dispatch path and replica picks
        the same bucket — the cross-path byte-identity contract holds
        bucket by bucket.

        Stages (:mod:`repro.telemetry` spans): ``repro.scheduler.admit``,
        ``repro.wave.prepare``, then per attempt ``repro.wave.launch`` /
        ``.device_wait`` / ``.d2h``, then ``repro.scheduler.accumulate``
        and one ``repro.scheduler.finalize`` per answer, all inside
        ``repro.scheduler.step_wave``.
        """
        with telemetry.span("repro.scheduler.step_wave"):
            return self._step_wave()

    def _step_wave(self) -> bool:
        with telemetry.span("repro.scheduler.admit"):
            self._admit()
        if not self.active:
            return False
        with telemetry.span("repro.wave.prepare"):
            alloc = self._allocate()
            W_b = self._bucket(self._walk_ladder, sum(alloc.values()))
            Q_b = self._bucket(self._query_ladder, len(alloc))
            start = np.zeros(W_b, np.int32)
            uniform = np.zeros(W_b, bool)
            qid = np.full(W_b, Q_b, np.int32)    # default: idle, returns -1
            t_cap = np.zeros(W_b, np.int32)
            cursor = 0
            # ``alloc`` preserves EDF order, so compact query id ci is
            # deterministic from (deadlines, slots) alone — identical
            # across dispatch paths.
            for ci, (s, w) in enumerate(alloc.items()):
                a = self.active[s]
                sl = slice(cursor, cursor + w)
                qid[sl] = ci
                t_cap[sl] = a.plan.num_steps
                if a.req.kind == "ppr":
                    start[sl] = a.req.source
                else:
                    uniform[sl] = True
                cursor += w
            self._key, k_wave = jax.random.split(self._key)
        ends, clean, dt = self._run_wave(start, uniform, qid, t_cap,
                                         k_wave, W_b, Q_b)
        now = time.perf_counter()
        self._walks_allocated += sum(alloc.values())
        # EMA of measured wave time — feeds the admission budget check. The
        # scheduler's very first wave includes jit compilation (seconds vs
        # steady-state ms) and would poison the estimate into rejecting
        # feasible SLOs, so it is never folded in. Faulted / stalled /
        # retried waves are skipped too (their wall time measures the fault,
        # not the machine), and a clean outlier is clamped to a bounded
        # multiple of the current estimate.
        self._waves_run += 1
        if self._waves_run > 1 and clean:
            if self._wave_time is not None:
                dt = min(dt, _EMA_OUTLIER_CLAMP * self._wave_time)
            self._wave_time = (dt if self._wave_time is None
                               else 0.5 * self._wave_time + 0.5 * dt)

        done = []
        with telemetry.span("repro.scheduler.accumulate"):
            ends = np.asarray(ends)          # a device array is taken too
            cursor = 0
            for s, w in alloc.items():
                # the slot's walks are the contiguous slice the operand
                # build gave it; a discarded or dead walk's endpoint is -1
                mine = ends[cursor:cursor + w]
                cursor += w
                if s not in self.active:     # evicted mid-wave? impossible
                    continue                 # today, but stay defensive
                a = self.active[s]
                mine = mine[mine >= 0]
                landed = mine.size
                a.count(mine)
                a.remaining -= w
                a.executed += landed
                self._walks_executed += landed
                a.waves += 1
                if landed < w:
                    a.lost += w - landed
                    a.shards_lost = tuple(sorted(self.lost_shards))
                early = (a.remaining > 0 and a.req.early_stop
                         and self.anytime_bound(a.plan.num_steps, a.req.k,
                                                a.req.delta, a.executed)
                         <= a.req.epsilon)
                if a.remaining == 0 or early:
                    done.append((s, early))
        for s, early in done:
            # the span also covers the release of the slot's counts
            with telemetry.span("repro.scheduler.finalize"):
                self.finished.append(
                    self._finalize(self.active.pop(s), now, early=early))
        return True

    # --- wave supervision (fault tolerance) -------------------------------

    def _run_wave(self, start, uniform, qid, t_cap, k_wave, W_b, Q_b):
        """Runs one wave under supervision: injector hooks fire first, the
        dispatch is retried (same key — a successful retry is byte-identical)
        on transient faults / timeouts with exponential backoff, permanent
        shard faults evict the shard and re-run degraded, and a mesh that
        keeps failing fails over once to the fused single-device dispatch.
        Exhausting every option raises :class:`WaveFailedError` with
        nothing tallied.

        The wave callable is re-fetched per attempt (``_wave_for(W_b,
        Q_b)``) — a failover mid-retry picks up the new dispatch path for
        the *same* bucket — and every attempt converts the host operands
        to fresh device buffers, so donation (the executable consumes its
        inputs) can never poison a retry.

        Returns ``(ends, clean, dt)`` — ``ends`` the host ``int32[W_b]``
        endpoints (``-1`` for a discarded or dead walk), ``clean`` False for
        any wave that saw a fault, stall, retry, or eviction (the EMA skips
        those). ``dt`` and the timeout cover the dispatch, the wait for the
        device and the endpoints' copy to the host.
        """
        wave_no = self._waves_run
        attempt = 0
        clean = True
        if self._injector is not None:
            for shard in self._injector.shard_losses_at(wave_no):
                clean = False
                self._evict_shard(shard, wave_no)
        while True:
            t0 = time.perf_counter()
            try:
                if self._injector is not None:
                    stall = self._injector.stall_s(wave_no)
                    if stall:
                        clean = False
                        time.sleep(stall)
                    kind = self._injector.fail_attempt(wave_no, attempt)
                    if kind == "timeout":
                        raise WaveTimeout(
                            f"injected hang (wave {wave_no}, attempt "
                            f"{attempt})")
                    if kind == "transient":
                        raise ShardFault(
                            f"injected transient fault (wave {wave_no}, "
                            f"attempt {attempt})", transient=True)
                with telemetry.span("repro.wave.launch"):
                    wave = self._wave_for(W_b, Q_b)
                    out = wave(
                        jnp.asarray(start), jnp.asarray(uniform),
                        jnp.asarray(qid), jnp.asarray(t_cap), k_wave,
                        jnp.asarray(self._lost))
                with telemetry.span("repro.wave.device_wait"):
                    jax.block_until_ready(out)
                ends = self._tally_to_host(out)
                dt = time.perf_counter() - t0
                if self.wave_timeout_s is not None and dt > self.wave_timeout_s:
                    raise WaveTimeout(
                        f"wave {wave_no} took {dt:.3g}s > wave_timeout_s="
                        f"{self.wave_timeout_s:.3g}s — result discarded")
                return ends, clean, dt
            except ShardFault as e:
                clean = False
                if not e.transient:
                    if e.shard is None:
                        raise WaveFailedError(
                            f"wave {wave_no}: permanent fault named no "
                            f"shard to evict: {e}") from e
                    self._evict_shard(e.shard, wave_no)
                    continue        # degraded re-run, not a retry
                attempt = self._count_retry(wave_no, attempt, e)
            except WaveTimeout as e:
                clean = False
                attempt = self._count_retry(wave_no, attempt, e)

    def _tally_to_host(self, out) -> np.ndarray:
        """The wave's endpoints as a host ``int32[W_b]``: the one copy off
        the device for every dispatch path, counted in
        ``tally_bytes_out``."""
        with telemetry.span("repro.wave.d2h"):
            ends = np.asarray(out)
            self._tally_bytes_out += ends.nbytes
            return ends

    def _count_retry(self, wave_no: int, attempt: int,
                     err: Exception) -> int:
        """Charges one retry; past ``max_retries`` tries the mesh→host-loop
        failover (attempt counter resets — a fresh dispatch path earns a
        fresh budget), then gives up with :class:`WaveFailedError`."""
        attempt += 1
        self.fault_log.append(FaultEvent(
            kind="retry", wave=wave_no, attempt=attempt, detail=str(err)))
        if attempt > self.max_retries:
            if self._failover_to_loop(wave_no, str(err)):
                return 0
            raise WaveFailedError(
                f"wave {wave_no} failed after {attempt} attempts "
                f"(max_retries={self.max_retries}, no failover path left): "
                f"{err}") from err
        time.sleep(self._backoff_s(attempt))
        return attempt

    def _backoff_s(self, attempt: int) -> float:
        """Exponential backoff with ×[0.5, 1.5) seeded jitter."""
        base = min(self.backoff_max_s,
                   self.backoff_base_s * (2 ** (attempt - 1)))
        return base * (0.5 + self._backoff_rng.random())

    def _evict_shard(self, shard: int, wave_no: int) -> None:
        """Permanently removes a shard from serving: flips its eviction
        mask bit (subsequent waves drop walks touching its range) and
        re-runs admission for queued SLO work against the shrunken
        capacity. Evicting the last shard is unservable and raises."""
        if not isinstance(self.index, ShardedWalkIndex):
            raise WaveFailedError(
                f"shard {shard} reported lost but the slab is dense — "
                f"gathered serving has no shard granularity to degrade to; "
                f"rebuild the index")
        S = self.index.num_shards
        if not (0 <= shard < S):
            raise ValueError(f"lost shard {shard} outside [0, {S})")
        if shard in self.lost_shards:
            return
        if len(self.lost_shards) + 1 >= S:
            raise WaveFailedError(
                f"shard {shard} lost but shards "
                f"{sorted(self.lost_shards)} are already evicted — no "
                f"shard left to serve from; rebuild the index")
        self.lost_shards.add(shard)
        self._lost[shard] = True
        self.fault_log.append(FaultEvent(
            kind="shard_loss", wave=wave_no, shard=shard))
        self._readmit_queued(wave_no)

    def _failover_to_loop(self, wave_no: int, reason: str) -> bool:
        """Mesh→single-device failover: rebuilds the wave as the fused
        single-dispatch program over the stacked slab — byte-identical
        answers (the PR-4 contract, now via the fused path). One shot: a
        single-device dispatch has nothing further to fail over to."""
        if (self._failed_over
                or not isinstance(self.index, ShardedWalkIndex)
                or self.runtime is None or not self.runtime.is_mesh):
            return False
        self._failed_over = True
        self.runtime = ShardRuntime(num_shards=self.runtime.num_shards,
                                    axis_name=self.runtime.axis_name,
                                    mesh=None)
        self.dispatch = "fused"
        self._wave_fns.clear()      # drop the mesh programs
        self._placed_blocks = self._placed_csr = None
        self.fault_log.append(FaultEvent(
            kind="failover", wave=wave_no,
            detail=f"mesh dispatch abandoned for single-device fused "
                   f"dispatch: {reason}"))
        return True

    def _effective_walks(self) -> int:
        """Walks the admission model charges per wave: losing shards kills
        the walks that land in their ranges, so full-machine throughput
        shrinks by the surviving-shard fraction (first-order — endpoint
        mass is roughly balanced across range shards)."""
        if isinstance(self.index, ShardedWalkIndex) and self.lost_shards:
            S = self.index.num_shards
            return max(1, int(self.max_walks * (S - len(self.lost_shards))
                              / S))
        return self.max_walks

    def _readmit_queued(self, wave_no: int) -> None:
        """Re-runs admission for queued SLO work after capacity shrank.

        Every queued deadline entry is re-checked (EDF order) against the
        post-eviction effective throughput: still-feasible work stays,
        downgradable work is re-clamped, and the rest moves to
        ``rejected`` — an honest late rejection instead of a silent SLO
        miss discovered at the deadline. No-SLO work is untouched."""
        if self._wave_time is None or not self.queue:
            return
        now = time.perf_counter()
        eff = self._effective_walks()
        keep: List[_Queued] = []
        for e in sorted(self.queue,
                        key=lambda e: (e.deadline, e.req.t_submit)):
            if e.deadline == math.inf:
                keep.append(e)
                continue
            feasible = int((e.deadline - now) / self._wave_time)
            backlog = (sum(q.walks for q in keep
                           if q.deadline <= e.deadline)
                       + sum(a.remaining for a in self.active.values()
                             if a.deadline <= e.deadline))
            needed = -(-(e.walks + backlog) // eff)
            if feasible >= needed:
                keep.append(e)
                continue
            budget = feasible * eff - backlog
            if e.req.allow_downgrade and budget >= 1:
                e.walks = min(e.walks, budget)
                e.downgraded = True
                keep.append(e)
                self.fault_log.append(FaultEvent(
                    kind="readmit", wave=wave_no,
                    detail=f"rid={e.req.rid} downgraded to {e.walks} walks"))
            else:
                self.rejected.append(AdmissionDecision(
                    rid=e.req.rid, admitted=False,
                    reason=(f"re-admission after shard loss (shards "
                            f"{sorted(self.lost_shards)} evicted): plan "
                            f"needs {needed} waves, {feasible} fit the "
                            f"SLO at degraded throughput"),
                    reason_code=RejectReason.SHARD_LOSS,
                    plan=e.plan))
                self.fault_log.append(FaultEvent(
                    kind="readmit", wave=wave_no,
                    detail=f"rid={e.req.rid} rejected"))
        self.queue = keep

    # --- introspection (gateway routing + metrics) ------------------------

    def stats(self) -> SchedulerStats:
        """Structured snapshot of serving/admission state (no waves driven).

        ``backlog_walks`` is exactly the demand ``_submit`` would charge a
        new no-SLO request with under EDF (every outstanding deadline
        outranks ∞): queued walk counts plus the remaining budgets of every
        active slot. The gateway's replica router picks the replica where
        this is smallest.
        """
        backlog = (sum(e.walks for e in self.queue)
                   + sum(a.remaining for a in self.active.values()))
        capacity = self._waves_run * self.max_walks
        return SchedulerStats(
            queued=len(self.queue),
            active=len(self.active),
            finished=len(self.finished),
            rejected=len(self.rejected),
            cancelled=len(self.cancelled),
            backlog_walks=backlog,
            waves_run=self._waves_run,
            walks_executed=self._walks_executed,
            wave_time_ema_s=self._wave_time,
            wave_occupancy=(self._walks_allocated / capacity
                            if capacity else 0.0),
            lost_shards=tuple(sorted(self.lost_shards)),
            max_walks=self.max_walks,
            max_queries=self.max_queries,
            epoch=self.epoch,
            tally_bytes_out=self._tally_bytes_out,
        )

    # --- anytime (ε, δ) refinement ---------------------------------------

    def anytime_bound(self, num_steps: int, k: int, delta: float,
                       executed: int) -> float:
        """The ε Theorem 1 certifies for the walks tallied so far (p_s = 1
        serving walks, p_cap = 0). Monotone non-increasing in ``executed``
        — every extra wave tightens it; ``inf`` before the first wave."""
        if executed < 1:
            return math.inf
        return theory.epsilon_bound(self.p_T, num_steps, k, delta,
                                    executed, 1.0, 0.0)

    def _finalize(self, a: _Active, now: float,
                  early: bool = False) -> QueryResult:
        # scores renormalize by the walks that actually completed — lost
        # walks shrink the denominator rather than biasing the estimate
        # (max() only guards the all-walks-lost corner: counts are all
        # zero there and the bound below is already inf).
        # rank the integer counts (same order as the renormalized scores
        # — a positive scalar divide preserves ranks and ties exactly)
        # and divide only the selected head.
        top, hits = a.top(min(a.req.k, self.g.n))
        scores_top = hits / float(max(1, a.executed))
        latency = now - a.t_submit
        # Early-stopped (anytime) queries carry the bound their executed
        # walks actually certify; budget-drained queries keep the plan's
        # recorded bound (incl. any admission downgrade). A degraded query
        # — walks died on evicted shards — widens to exactly the ε
        # Theorem 1 certifies at N = executed: the lost-walk fraction
        # enters through the sampling term, never silently.
        degraded = a.lost > 0
        bound = (self.anytime_bound(a.plan.num_steps, a.req.k, a.req.delta,
                                     a.executed)
                 if (a.req.early_stop or degraded)
                 else a.plan.epsilon_bound)
        return QueryResult(
            rid=a.req.rid, kind=a.req.kind, vertices=top,
            scores=scores_top, num_walks=a.executed,
            num_steps=a.plan.num_steps, waves=a.waves,
            latency_s=latency,
            epsilon_bound=bound,
            downgraded=a.downgraded,
            met_slo=(None if a.req.slo_s is None
                     else bool(latency <= a.req.slo_s)),
            early_stopped=early,
            degraded=degraded,
            shards_lost=a.shards_lost,
            walks_lost=a.lost,
            epoch=self.epoch,
        )

    # --- anytime introspection (the QueryHandle surface) ------------------

    def query_state(self, rid: int) -> str:
        """``queued`` | ``active`` | ``finished`` | ``rejected`` |
        ``cancelled`` | ``unknown``."""
        if any(r.rid == rid for r in self.finished):
            return "finished"
        if any(a.req.rid == rid for a in self.active.values()):
            return "active"
        if any(e.req.rid == rid for e in self.queue):
            return "queued"
        if rid in self.cancelled:
            return "cancelled"
        if any(d.rid == rid for d in self.rejected):
            return "rejected"
        return "unknown"

    def result_for(self, rid: int) -> QueryResult:
        for r in self.finished:
            if r.rid == rid:
                return r
        raise KeyError(f"query {rid} has no finished result "
                       f"(state: {self.query_state(rid)})")

    def partial(self, rid: int) -> QueryPartial:
        """Anytime snapshot: the current top-k estimate plus the ε the
        tallied walks certify so far (``inf`` before the first wave)."""
        for r in self.finished:
            if r.rid == rid:
                return QueryPartial(
                    rid=rid, kind=r.kind, k=len(r.vertices),
                    vertices=r.vertices, scores=r.scores,
                    walks_done=r.num_walks, waves=r.waves,
                    epsilon_bound=r.epsilon_bound, done=True,
                    degraded=r.degraded, shards_lost=r.shards_lost,
                    walks_lost=r.walks_lost)
        for a in self.active.values():
            if a.req.rid != rid:
                continue
            k = min(a.req.k, self.g.n)
            if a.executed:
                vertices, hits = a.top(k)
                top_scores = hits / float(a.executed)
            else:
                vertices = np.zeros(0, np.int64)
                top_scores = np.zeros(0, np.float64)
            return QueryPartial(
                rid=rid, kind=a.req.kind, k=k, vertices=vertices,
                scores=top_scores, walks_done=a.executed, waves=a.waves,
                epsilon_bound=self.anytime_bound(
                    a.plan.num_steps, a.req.k, a.req.delta, a.executed),
                done=False,
                degraded=a.lost > 0, shards_lost=a.shards_lost,
                walks_lost=a.lost)
        for e in self.queue:
            if e.req.rid == rid:
                return QueryPartial(
                    rid=rid, kind=e.req.kind, k=min(e.req.k, self.g.n),
                    vertices=np.zeros(0, np.int64),
                    scores=np.zeros(0, np.float64),
                    walks_done=0, waves=0, epsilon_bound=math.inf,
                    done=False)
        raise KeyError(f"no in-flight query {rid} "
                       f"(state: {self.query_state(rid)})")

    def cancel(self, rid: int) -> bool:
        """Drops a queued or in-flight query (its tallies are discarded).
        Returns False when there is nothing left to cancel."""
        for i, e in enumerate(self.queue):
            if e.req.rid == rid:
                del self.queue[i]
                self.cancelled.append(rid)
                return True
        for s, a in list(self.active.items()):
            if a.req.rid == rid:
                del self.active[s]
                self.cancelled.append(rid)
                return True
        return False

    def run(self) -> List[QueryResult]:
        """Deprecated entry point — use :meth:`repro.service.
        FrogWildService.drain` (or drive :class:`~repro.service.QueryHandle`
        futures via ``poll()`` / ``result()``)."""
        warn_deprecated("QueryScheduler.run", "FrogWildService.drain")
        return self._drain()

    def _drain(self) -> List[QueryResult]:
        """Drains queue + in-flight queries; returns results in finish order."""
        while self.step_wave():
            pass
        return self.finished
