"""Unified service facade: one front door for batch, index, and serving.

FrogWild is an *anytime* estimator — every extra wave of walks tightens the
Theorem-1 bound — but the repo historically exposed it through four
divergent entry points (``frogwild_run``, ``distributed_frogwild``,
``build_walk_index{,_sharded}``, ``QueryScheduler.submit/run``) with three
overlapping config dataclasses. This module is the redesigned surface:

* :class:`FrogWildService` — ``open(graph_or_path, config)`` owns graph
  ingestion (a :class:`~repro.graph.csr.CSRGraph` or a ``save_graph``
  ``.npz`` path), :class:`~repro.distributed.runtime.ShardRuntime`
  acquisition, and the walk-index lifecycle (build / load / reuse through
  ``checkpoint/`` when ``RuntimeConfig.serving.checkpoint_dir`` is set).
  ``pagerank(eps, delta)`` is the batch estimator, dispatching the
  single-device walker oracle or the mesh engine automatically; ``topk``
  and ``ppr`` return :class:`QueryHandle` futures served by the
  continuous-batching scheduler (admission, EDF allocation, and downgrade
  semantics unchanged underneath).

* :class:`QueryHandle` — a future with ``poll()`` / ``partial()`` /
  ``result()`` / ``cancel()``. Each ``partial()`` snapshot carries the ε
  Theorem 1 certifies for the walks tallied *so far* — monotonically
  tightening wave over wave (FAST-PPR's per-query confidence, PowerWalk's
  index-then-serve decomposition) — and with ``early_stop`` (the default)
  the query completes as soon as the requested ``(ε, δ)`` bound is met,
  even if its walk budget is not drained.

* :func:`batch_pagerank` / :func:`build_index` — the canonical module-level
  dispatchers the legacy entry points now delegate through (they emit
  ``DeprecationWarning`` and return byte-identical results).

Config is the layered :class:`~repro.config.RuntimeConfig` (kernel +
runtime + serving sub-configs — see ``repro/config.py``); the legacy
dataclasses are accepted everywhere a shim needs them.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Union

import jax

from repro.checkpoint import CheckpointCorruptError
from repro.config import (EngineConfig, FrogWildConfig, KernelConfig,
                          RuntimeConfig, ServingConfig, ShardConfig,
                          WalkIndexConfig)
from repro.core.frogwild import frogwild_walks
from repro.distributed.faults import FaultInjector, WaveFailedError
from repro.distributed.runtime import ShardRuntime
from repro.engine import gas as _gas
from repro.graph.csr import CSRGraph, load_graph
from repro.query import index as _qindex
from repro.query.engine import plan_query
from repro.query.index import ShardedWalkIndex, WalkIndex
from repro.query.scheduler import (QueryPartial, QueryRequest, QueryResult,
                                   QueryScheduler, SchedulerStats)

__all__ = [
    "FrogWildService",
    "JoinedQueryHandle",
    "QueryHandle",
    "QueryPartial",
    "RuntimeConfig",
    "KernelConfig",
    "ShardConfig",
    "ServingConfig",
    "batch_pagerank",
    "build_index",
]


# ---------------------------------------------------------------------------
# canonical module-level dispatchers (the legacy shims delegate through these)
# ---------------------------------------------------------------------------


def _as_runtime_config(config) -> RuntimeConfig:
    if isinstance(config, RuntimeConfig):
        return config
    if isinstance(config, FrogWildConfig):
        return RuntimeConfig.from_frogwild(config)
    if isinstance(config, EngineConfig):
        return RuntimeConfig.from_engine(config)
    if isinstance(config, WalkIndexConfig):
        return RuntimeConfig.from_walk_index(config)
    raise TypeError(f"unsupported config type {type(config).__name__}")


def batch_pagerank(
    graph: Union[CSRGraph, "_gas.DistributedGraph"],
    config: Union[RuntimeConfig, FrogWildConfig, EngineConfig],
    *,
    key: Optional[jax.Array] = None,
    seed: Optional[int] = None,
    mesh=None,
):
    """One batch FrogWild run — the single dispatch point under both the
    service's :meth:`FrogWildService.pagerank` and the legacy
    ``frogwild_run`` / ``distributed_frogwild`` shims.

    A mesh (or a prebuilt :class:`~repro.engine.gas.DistributedGraph`)
    routes to the distributed engine (seeded by ``seed``); otherwise the
    single-device walker oracle runs with ``key`` (or ``PRNGKey(seed)``).
    """
    if isinstance(graph, _gas.DistributedGraph):
        if mesh is None:
            raise ValueError("a DistributedGraph run needs mesh=")
        cfg = (config.engine() if isinstance(config, RuntimeConfig)
               else config)
        return _gas._distributed_frogwild(graph, cfg, mesh,
                                          seed=0 if seed is None else seed)
    if mesh is not None:
        rc = _as_runtime_config(config)
        rt = ShardRuntime.for_mesh(mesh, rc.runtime.axis_name)
        dg = _gas.build_distributed_graph(
            graph, rt.num_shards, vertex_block=rc.runtime.vertex_block)
        return _gas._distributed_frogwild(dg, rc.engine(), mesh,
                                          seed=0 if seed is None else seed)
    cfg = config.frogwild() if isinstance(config, RuntimeConfig) else config
    if key is None:
        key = jax.random.PRNGKey(0 if seed is None else seed)
    return frogwild_walks(graph, cfg, key)


def build_index(
    graph: CSRGraph,
    config: Union[RuntimeConfig, WalkIndexConfig],
    *,
    key: Optional[jax.Array] = None,
    mesh=None,
    directory: Optional[str] = None,
    axis_name: str = "vertex",
    step: int = 0,
    reassemble: bool = True,
) -> Union[WalkIndex, ShardedWalkIndex]:
    """One walk-index build — the single dispatch point under the service's
    index lifecycle and the legacy ``build_walk_index{,_sharded}`` shims.

    With a mesh the build runs as one ``shard_map`` (each device
    materializes only its slab block); otherwise the host shard loop. With
    ``directory`` the result is persisted through ``checkpoint/``.
    """
    cfg = (config.walk_index() if isinstance(config, RuntimeConfig)
           else config)
    if mesh is not None:
        return _qindex._build_walk_index_sharded(
            graph, cfg, mesh, directory=directory, key=key,
            axis_name=axis_name, step=step, reassemble=reassemble)
    idx = _qindex._build_walk_index(graph, cfg, key)
    if directory is not None:
        _qindex.save_walk_index(directory, idx, step=step)
    return idx


# ---------------------------------------------------------------------------
# the async query surface
# ---------------------------------------------------------------------------


class QueryHandle:
    """Future for one submitted query, with anytime (ε, δ) refinement.

    * ``poll()``    — advance the service by at most one wave; True when done.
    * ``partial()`` — snapshot of the current estimate; its
      ``epsilon_bound`` (the ε certified for the walks tallied so far)
      tightens monotonically wave over wave.
    * ``result()``  — drive waves until this query completes.
    * ``cancel()``  — drop it from the queue / its slot.

    Handles are cooperative: any handle's ``poll()`` / ``result()``
    advances the shared scheduler, so all in-flight queries make progress
    together (continuous batching).

    A handle pins the scheduler — and therefore the graph epoch and slab
    — it was admitted on: a mutation commit (``apply_mutations``) swaps
    the service's current scheduler for the new epoch's, but this handle
    keeps finishing on its own, byte-identical to a run where no mutation
    ever happened (the two-epoch serving contract).
    """

    def __init__(self, service: "FrogWildService", request: QueryRequest,
                 decision, scheduler: Optional[QueryScheduler] = None):
        self._service = service
        self._sched = (scheduler if scheduler is not None
                       else service.scheduler)
        self.request = request
        self.decision = decision

    @property
    def rid(self) -> int:
        return self.request.rid

    @property
    def admitted(self) -> bool:
        return bool(self.decision.admitted)

    def status(self) -> str:
        """``rejected`` | ``queued`` | ``active`` | ``finished`` |
        ``cancelled``."""
        if not self.admitted:
            return "rejected"
        if self._service.closed:
            # close() cancels in-flight work; a handle outliving its
            # service reports that instead of resurrecting a scheduler.
            return "cancelled"
        return self._sched.query_state(self.rid)

    def done(self) -> bool:
        return self.status() in ("finished", "cancelled", "rejected")

    def poll(self) -> bool:
        """Advances the service by one wave unless already done."""
        if not self.done():
            self._service.step()
        return self.done()

    def partial(self) -> QueryPartial:
        """Current anytime snapshot (no waves are driven)."""
        st = self.status()
        if st in ("rejected", "cancelled"):
            raise RuntimeError(
                f"query {self.rid} is {st}"
                + (f": {self.decision.reason}" if st == "rejected" else ""))
        return self._sched.partial(self.rid)

    def result(self, max_waves: Optional[int] = None) -> QueryResult:
        """Drives waves until this query finishes and returns its result."""
        if not self.admitted:
            raise RuntimeError(
                f"query {self.rid} rejected at admission: "
                f"{self.decision.reason}")
        waves = 0
        while True:
            st = self.status()
            if st == "finished":
                return self._sched.result_for(self.rid)
            if st == "cancelled":
                raise RuntimeError(f"query {self.rid} was cancelled")
            if st == "rejected":
                # shard loss can shrink capacity after admission: the
                # re-admission pass moves infeasible queued work here.
                reason = next(
                    (d.reason for d in self._sched.rejected
                     if d.rid == self.rid), "")
                raise RuntimeError(
                    f"query {self.rid} rejected after admission: {reason}")
            if max_waves is not None and waves >= max_waves:
                raise TimeoutError(
                    f"query {self.rid} still {st} after {waves} waves")
            if not self._service.step():
                raise RuntimeError(
                    f"scheduler idle but query {self.rid} is {st}")
            waves += 1

    def cancel(self) -> bool:
        """Drops the query; False when it already finished (or never ran)."""
        if not self.admitted or self._service.closed:
            return False
        return self._sched.cancel(self.rid)

    def join(self, epsilon: Optional[float] = None,
             delta: Optional[float] = None) -> "JoinedQueryHandle":
        """Attaches a duplicate request to this live handle (in-flight
        dedup — the gateway's join hook).

        Valid only when this handle's target **dominates** the joiner's —
        ``self.ε ≤ ε`` and ``self.δ ≤ δ`` — because then Theorem 1
        guarantees the walks already being executed certify the joiner's
        weaker bound no later than this handle's own. The joined handle
        executes zero walks of its own: it is fed this handle's monotone
        ``partial()`` snapshots and completes the wave *its* (ε, δ) is
        certified — at the latest, the wave this handle finishes.
        """
        eps = self.request.epsilon if epsilon is None else epsilon
        dlt = self.request.delta if delta is None else delta
        if self.request.epsilon > eps or self.request.delta > dlt:
            raise ValueError(
                f"cannot join query {self.rid}: its target "
                f"(ε={self.request.epsilon}, δ={self.request.delta}) does "
                f"not dominate the joiner's (ε={eps}, δ={dlt}) — submit a "
                f"fresh query instead")
        if not self.admitted:
            raise RuntimeError(
                f"cannot join rejected query {self.rid}: "
                f"{self.decision.reason}")
        return JoinedQueryHandle(self, eps, dlt)


class JoinedQueryHandle:
    """A duplicate request riding a live :class:`QueryHandle`.

    Created by :meth:`QueryHandle.join` — the parent's (ε, δ) target must
    dominate this one's. No walks are executed on its behalf: ``poll()`` /
    ``result()`` drive the parent's service, ``partial()`` is the parent's
    snapshot, and the join settles the wave its own (ε, δ) is certified by
    the walks tallied so far. With a target identical to the parent's, the
    settled result *is* the parent's :class:`~repro.query.scheduler.
    QueryResult` object — byte-identical, provenance included.
    """

    def __init__(self, parent: QueryHandle, epsilon: float, delta: float):
        self.parent = parent
        self.epsilon = epsilon
        self.delta = delta
        self._result: Optional[QueryResult] = None
        self._t_join = time.perf_counter()

    @property
    def rid(self) -> int:
        return self.parent.rid

    @property
    def admitted(self) -> bool:
        return self.parent.admitted

    def done(self) -> bool:
        """True when settled — or **terminal**: a parent that was
        cancelled or late-rejected mid-wave can never certify this join,
        so the joiner reports done instead of polling forever (its
        ``result()`` then raises the classified error)."""
        if self._result is not None or self._settle():
            return True
        return self.parent.status() in ("cancelled", "rejected")

    def poll(self) -> bool:
        """Advances the parent's service by one wave unless already done."""
        if not self.done():
            self.parent._service.step()
        return self.done()

    def partial(self) -> QueryPartial:
        """The parent's anytime snapshot (shared tallies)."""
        return self.parent.partial()

    def _settle(self) -> bool:
        """Settles the joined result once certifiable; False until then."""
        parent = self.parent
        st = parent.status()
        if st == "finished":
            # the parent's certificate was issued at (ε_p ≤ ε, δ_p ≤ δ), so
            # it dominates the joiner's target: hand back the parent's
            # result object itself — byte-identical by construction.
            self._result = parent._sched.result_for(parent.rid)
            return True
        if st != "active":
            return False             # queued: no walks yet; cancelled /
                                     # rejected: surfaced by result()
        if (self.epsilon, self.delta) == (parent.request.epsilon,
                                          parent.request.delta):
            return False             # identical target: settle with parent
        sched = parent._sched
        p = sched.partial(self.rid)
        if not p.walks_done:
            return False
        bound = sched.anytime_bound(parent.decision.plan.num_steps,
                                    parent.request.k, self.delta,
                                    p.walks_done)
        if bound > self.epsilon:
            return False
        # the weaker bound is certified mid-flight: freeze this wave's
        # snapshot as the joined result while the parent keeps refining.
        self._result = QueryResult(
            rid=p.rid, kind=p.kind, vertices=p.vertices, scores=p.scores,
            num_walks=p.walks_done,
            num_steps=parent.decision.plan.num_steps, waves=p.waves,
            latency_s=time.perf_counter() - self._t_join,
            epsilon_bound=bound, early_stopped=True, degraded=p.degraded,
            shards_lost=p.shards_lost, walks_lost=p.walks_lost,
            epoch=sched.epoch)
        return True

    def result(self, max_waves: Optional[int] = None) -> QueryResult:
        """Drives waves until this join's (ε, δ) is certified.

        A parent cancelled / late-rejected before certification surfaces
        as a classified :class:`~repro.distributed.faults.WaveFailedError`
        (the gateway's failover migrates joiners *before* cancelling a
        parent, so through the tier this only fires when the caller
        cancels a parent that still has joiners riding it).
        """
        waves = 0
        while True:
            if self.done():
                if self._result is None:
                    st = self.parent.status()
                    raise WaveFailedError(
                        f"joined query {self.rid}: parent handle is {st} "
                        f"before this join's (ε={self.epsilon}, "
                        f"δ={self.delta}) was certified — resubmit")
                return self._result
            st = self.parent.status()
            if max_waves is not None and waves >= max_waves:
                raise TimeoutError(
                    f"joined query {self.rid} still {st} after "
                    f"{waves} waves")
            if not self.parent._service.step():
                raise RuntimeError(
                    f"scheduler idle but joined query {self.rid} is {st}")
            waves += 1


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------


class FrogWildService:
    """The one front door: batch PageRank, walk-index lifecycle, and async
    top-k / PPR serving over a single graph.

    Build one with :meth:`open`; everything else (runtime acquisition,
    index build-or-load, scheduler construction) is lazy and owned by the
    service.
    """

    def __init__(self, graph: CSRGraph, config: RuntimeConfig, *,
                 mesh=None, index=None):
        self.graph = graph
        self.config = config
        self._mesh = mesh
        if mesh is not None:
            self.runtime = ShardRuntime.for_mesh(mesh,
                                                 config.runtime.axis_name)
        elif config.runtime.num_shards > 1:
            self.runtime = ShardRuntime.acquire(config.runtime.num_shards,
                                                config.runtime.axis_name)
        else:
            self.runtime = None
        self._index = index
        self._scheduler: Optional[QueryScheduler] = None
        # retired epochs' schedulers, kept alive until their last pinned
        # query settles (two-epoch serving — see commit_epoch / step).
        self._retiring: List[QueryScheduler] = []
        self._dg = None                  # cached DistributedGraph
        self._dg_key = None
        self._next_rid = 0
        self._closed = False
        # one injector per service: the scheduler consults it per
        # (wave, attempt), and the index loader lets it mangle on-disk
        # checkpoint payloads before the first read (crash-injection).
        self._injector = (FaultInjector(config.faults)
                          if config.faults is not None else None)

    # --- lifecycle -------------------------------------------------------

    @classmethod
    def open(
        cls,
        graph_or_path: Union[CSRGraph, str, os.PathLike],
        config: Optional[RuntimeConfig] = None,
        *,
        mesh=None,
        index: Union[WalkIndex, ShardedWalkIndex, None] = None,
    ) -> "FrogWildService":
        """Opens a service over a graph (or a ``save_graph`` ``.npz`` path).

        ``mesh`` routes batch runs through the distributed engine and (when
        its shard count matches ``config.runtime.num_shards``) sharded
        serving through one ``shard_map``; ``index`` short-circuits the
        index lifecycle with a prebuilt slab.
        """
        if config is None:
            config = RuntimeConfig()
        elif not isinstance(config, RuntimeConfig):
            config = _as_runtime_config(config)
        if isinstance(graph_or_path, (str, os.PathLike)):
            graph = load_graph(os.fspath(graph_or_path))
        elif isinstance(graph_or_path, CSRGraph):
            graph = graph_or_path
        else:
            raise TypeError(
                f"graph_or_path must be a CSRGraph or a path, got "
                f"{type(graph_or_path).__name__}")
        return cls(graph, config, mesh=mesh, index=index)

    @property
    def closed(self) -> bool:
        """True once :meth:`close` ran; a closed service refuses new work."""
        return self._closed

    def close(self) -> None:
        """Tears the service down — idempotent and safe under pool teardown.

        Replicas in a :class:`~repro.gateway.ReplicaPool` share the graph
        and walk-index arrays but each own their scheduler, so close only
        touches per-service state: queued and in-flight queries are
        cancelled (their :class:`QueryHandle`\\ s report ``cancelled``
        afterwards, never an exception), the scheduler / index / graph
        caches are dropped, and every later call — including another
        ``close()`` — is a no-op. Submitting new work on a closed service
        raises ``RuntimeError``.
        """
        if self._closed:
            return
        for sched in [self._scheduler] + self._retiring:
            if sched is not None:
                for rid in ([e.req.rid for e in sched.queue]
                            + [a.req.rid for a in sched.active.values()]):
                    sched.cancel(rid)
        self._retiring = []
        self._scheduler = None
        self._index = None
        self._dg = None
        self._dg_key = None
        self._closed = True

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(
                "FrogWildService is closed — open a new service (or a new "
                "gateway replica) to submit more work")

    def __enter__(self) -> "FrogWildService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # --- walk-index lifecycle -------------------------------------------

    def ensure_index(self) -> Union[WalkIndex, ShardedWalkIndex]:
        """Build / load / reuse the walk index (idempotent).

        With ``serving.checkpoint_dir`` set, an existing on-disk index is
        loaded (and validated against the configured geometry); otherwise
        the index is built — as one ``shard_map`` when the service owns a
        multi-device mesh matching ``runtime.num_shards``, else via the
        host shard loop — and persisted to the checkpoint dir if given.
        The slab is served sharded (never reassembled) whenever
        ``runtime.num_shards > 1``.
        """
        self._check_open()
        if self._index is None:
            self._index = self._load_or_build_index()
        S = self.config.runtime.num_shards
        if S > 1:
            # runtime.num_shards declares the serving layout: a dense slab
            # (built, loaded, or passed in) is range-partitioned here, and
            # a sharded one laid out for a different shard count (e.g. a
            # checkpoint from a differently-configured run) is re-split —
            # never silently served at the checkpoint's layout.
            if isinstance(self._index, WalkIndex):
                self._index = _qindex.shard_walk_index(self._index, S)
            elif self._index.num_shards != S:
                self._index = _qindex.shard_walk_index(
                    self._index.reassemble(), S)
        return self._index

    def _load_or_build_index(self) -> Union[WalkIndex, ShardedWalkIndex]:
        icfg = self.config.walk_index()
        S = self.config.runtime.num_shards
        directory = self.config.serving.checkpoint_dir
        if directory is not None:
            if self._injector is not None:
                # crash-injection hook: mangle on-disk payloads *before*
                # the first read so the repair path below is what serves.
                self._injector.mangle_checkpoints(directory)
            try:
                # self-healing load: corrupt / torn / missing shards of a
                # per-shard layout are quarantined and rebuilt in place
                # with the original build's key stream.
                idx = _qindex.load_or_repair_walk_index(
                    directory, self.graph, icfg, reassemble=(S <= 1))
            except FileNotFoundError:
                idx = None
            except CheckpointCorruptError:
                # monolithic (dense) layout: no sub-unit to repair —
                # rebuild the whole index below (the atomic save replaces
                # the corrupt step dir).
                idx = None
            if idx is not None:
                if (idx.segments_per_vertex != icfg.segments_per_vertex
                        or idx.segment_len != icfg.segment_len):
                    raise ValueError(
                        f"walk index under {directory!r} has (R, L) = "
                        f"({idx.segments_per_vertex}, {idx.segment_len}) "
                        f"but the config wants "
                        f"({icfg.segments_per_vertex}, {icfg.segment_len});"
                        f" rebuild or point checkpoint_dir elsewhere")
                if int(getattr(idx, "graph_epoch", 0)) != int(
                        getattr(self.graph, "epoch", 0)):
                    raise ValueError(
                        f"walk index under {directory!r} was built at "
                        f"graph epoch {idx.graph_epoch} but the service "
                        f"graph is at epoch "
                        f"{int(getattr(self.graph, 'epoch', 0))} — a stale "
                        f"slab would serve wrong answers silently; refresh "
                        f"it (repro.dynamic.refresh_walk_index / "
                        f"load_epoch_index) or rebuild")
                return idx
        if (S > 1 and self.runtime is not None and self.runtime.is_mesh
                and self.runtime.num_shards == S):
            return build_index(
                self.graph, icfg, mesh=self.runtime.mesh,
                axis_name=self.config.runtime.axis_name,
                directory=directory, reassemble=False)
        return build_index(self.graph, icfg, directory=directory)

    # --- batch -----------------------------------------------------------

    def pagerank(
        self,
        epsilon: Optional[float] = None,
        delta: float = 0.1,
        k: int = 10,
        *,
        key: Optional[jax.Array] = None,
        seed: Optional[int] = None,
        config: Optional[RuntimeConfig] = None,
    ):
        """One batch FrogWild estimate of the full PageRank vector.

        With ``epsilon`` given, Theorem 1 is inverted into ``(t, N)`` for a
        ``μ_k`` guarantee at confidence ``1 − delta`` (plans at p_s = 1);
        otherwise the config's ``num_frogs`` / ``num_steps`` run as-is.
        Dispatch is automatic: a service opened with a mesh runs the
        distributed engine (returns :class:`~repro.engine.gas.
        EngineResult`), else the single-device walker oracle (returns
        :class:`~repro.core.frogwild.FrogWildResult`).
        """
        self._check_open()
        rc = config if config is not None else self.config
        if epsilon is not None:
            plan = plan_query(k, epsilon, delta, p_T=rc.p_T,
                              max_steps=rc.serving.max_steps)
            rc = dataclasses.replace(rc, num_frogs=plan.num_walks,
                                     num_steps=plan.num_steps)
        if self._mesh is not None:
            return batch_pagerank(
                self._dgraph(rc), rc.engine(), mesh=self._mesh,
                seed=rc.runtime.seed if seed is None else seed)
        cfg = rc.frogwild()
        if key is None:
            key = jax.random.PRNGKey(rc.runtime.seed if seed is None
                                     else seed)
        return frogwild_walks(self.graph, cfg, key)

    def _dgraph(self, rc: RuntimeConfig) -> "_gas.DistributedGraph":
        """Per-shard CSR blocks for the engine path (cached per shape)."""
        shape = (self.runtime.num_shards, rc.runtime.vertex_block)
        if self._dg is None or self._dg_key != shape:
            self._dg = _gas.build_distributed_graph(
                self.graph, shape[0], vertex_block=shape[1])
            self._dg_key = shape
        return self._dg

    # --- serving ---------------------------------------------------------

    @property
    def scheduler(self) -> QueryScheduler:
        """The (lazily built) continuous-batching scheduler."""
        self._check_open()
        if self._scheduler is None:
            index = self.ensure_index()
            scfg = self.config.serving
            runtime = None
            if (isinstance(index, ShardedWalkIndex)
                    and self.runtime is not None
                    and self.runtime.num_shards == index.num_shards):
                runtime = self.runtime
            self._scheduler = QueryScheduler(
                self.graph, index,
                max_walks=scfg.max_walks, max_queries=scfg.max_queries,
                max_steps=scfg.max_steps, p_T=self.config.p_T,
                impl=self.config.kernel.stitch_impl,
                seed=self.config.runtime.seed, runtime=runtime,
                wave_time_estimate_s=scfg.wave_time_estimate_s,
                fault_injector=self._injector,
                wave_timeout_s=scfg.wave_timeout_s,
                max_retries=scfg.max_retries,
                backoff_base_s=scfg.backoff_base_s,
                backoff_max_s=scfg.backoff_max_s,
                sharded_dispatch=scfg.sharded_dispatch,
                donate_wave_buffers=scfg.donate_wave_buffers,
                walk_buckets=scfg.walk_buckets,
                query_buckets=scfg.query_buckets,
                aot_warmup=scfg.aot_warmup)
        return self._scheduler

    @property
    def lost_shards(self) -> frozenset:
        """Shards evicted from serving so far (empty before any fault)."""
        if self._scheduler is None:
            return frozenset()
        return frozenset(self._scheduler.lost_shards)

    def serving_stats(self) -> Optional[SchedulerStats]:
        """The scheduler's admission-accounting snapshot — ``None`` until
        the first query forces the scheduler into existence (a replica
        that has never served is, by definition, unloaded). The gateway's
        replica router keys on ``backlog_walks``."""
        if self._closed or self._scheduler is None:
            return None
        return self._scheduler.stats()

    @property
    def fault_log(self) -> list:
        """The wave supervisor's fault provenance log (chronological
        :class:`~repro.distributed.faults.FaultEvent` entries)."""
        if self._scheduler is None:
            return []
        return list(self._scheduler.fault_log)

    def topk(
        self,
        k: int = 10,
        epsilon: float = 0.3,
        delta: float = 0.1,
        *,
        num_walks: Optional[int] = None,
        slo_s: Optional[float] = None,
        allow_downgrade: bool = False,
        early_stop: bool = True,
    ) -> QueryHandle:
        """Submits a global top-k query; returns its :class:`QueryHandle`.

        ``num_walks`` overrides the Theorem-1 walk budget (a larger budget
        plus ``early_stop`` gives pure anytime behaviour: the query runs
        until the requested ε is certified, then stops). ``slo_s`` engages
        deadline-aware admission exactly as before.
        """
        return self._submit_request(
            kind="topk", k=k, source=0, epsilon=epsilon, delta=delta,
            num_walks=num_walks, slo_s=slo_s,
            allow_downgrade=allow_downgrade, early_stop=early_stop)

    def ppr(
        self,
        source: int,
        k: int = 10,
        epsilon: float = 0.3,
        delta: float = 0.1,
        *,
        num_walks: Optional[int] = None,
        slo_s: Optional[float] = None,
        allow_downgrade: bool = False,
        early_stop: bool = True,
    ) -> QueryHandle:
        """Submits a personalized-PageRank query pinned at ``source``."""
        return self._submit_request(
            kind="ppr", k=k, source=source, epsilon=epsilon, delta=delta,
            num_walks=num_walks, slo_s=slo_s,
            allow_downgrade=allow_downgrade, early_stop=early_stop)

    def _submit_request(self, **kw) -> QueryHandle:
        req = QueryRequest(rid=self._next_rid, **kw)
        self._next_rid += 1
        sched = self.scheduler
        decision = sched._submit(req)
        # the handle pins the scheduler (and so the epoch/slab) it was
        # admitted on — an epoch commit never disturbs in-flight queries.
        return QueryHandle(self, req, decision, scheduler=sched)

    def resubmit(self, req: QueryRequest) -> QueryHandle:
        """Submits a fresh copy of ``req`` (new rid, new latency clock) —
        the gateway's failover hook: a query whose replica died mid-flight
        is replayed on a healthy replica with the *same plan parameters*.
        On a cold (or freshly restarted) replica the scheduler's key
        stream starts at wave 0, so the replayed answer is byte-identical
        to a fault-free run on a cold replica (asserted in the bench
        smoke)."""
        return self._submit_request(
            kind=req.kind, k=req.k, source=req.source, epsilon=req.epsilon,
            delta=req.delta, num_walks=req.num_walks, slo_s=req.slo_s,
            allow_downgrade=req.allow_downgrade, early_stop=req.early_stop)

    def step(self) -> bool:
        """Runs one device wave; False when nothing is in flight.

        Drives the current epoch's scheduler first, then any retiring
        epochs still carrying pinned queries; a retiring scheduler whose
        last pinned query has settled is released here (its handles keep
        their own references for ``result_for``).
        """
        progressed = self.scheduler.step_wave()
        for sched in list(self._retiring):
            if sched.queue or sched.active:
                progressed = sched.step_wave() or progressed
            if not sched.queue and not sched.active:
                self._retiring.remove(sched)
        return progressed

    def drain(self) -> List[QueryResult]:
        """Drives waves until queue + slots are empty; returns all results
        finished so far (in finish order)."""
        while self._retiring and self.step():
            pass
        return self.scheduler._drain()

    # --- dynamic graphs (epoch lifecycle) ---------------------------------

    @property
    def graph_epoch(self) -> int:
        """The mutation epoch new admissions land on."""
        return int(getattr(self.graph, "epoch", 0))

    @property
    def retiring_epochs(self) -> List[int]:
        """Epochs still draining pinned queries (oldest first)."""
        return [s.epoch for s in self._retiring]

    def commit_epoch(self, graph: CSRGraph, index) -> int:
        """Swaps serving to ``(graph, index)`` at the next epoch.

        The current scheduler — if it still carries queued or active
        queries — moves to the retiring list and keeps draining through
        :meth:`step`; its handles finish byte-identically to a
        never-mutated run (each scheduler owns its key stream, seeded
        identically). New admissions land on the new epoch immediately.
        Returns the committed epoch.
        """
        self._check_open()
        if graph.n != self.graph.n:
            raise ValueError(
                f"epoch commit cannot change the vertex count "
                f"({self.graph.n} → {graph.n})")
        if int(getattr(index, "graph_epoch", 0)) != int(graph.epoch):
            raise ValueError(
                f"slab epoch {getattr(index, 'graph_epoch', 0)} does not "
                f"match graph epoch {graph.epoch} — refusing a mismatched "
                f"commit")
        icfg = self.config.walk_index()
        if (index.segments_per_vertex != icfg.segments_per_vertex
                or index.segment_len != icfg.segment_len):
            raise ValueError(
                f"slab geometry (R, L) = ({index.segments_per_vertex}, "
                f"{index.segment_len}) does not match the service config "
                f"({icfg.segments_per_vertex}, {icfg.segment_len})")
        old = self._scheduler
        if old is not None and (old.queue or old.active):
            self._retiring.append(old)
        self._scheduler = None
        self.graph = graph
        self._index = index
        self._dg = None
        self._dg_key = None
        return int(graph.epoch)

    def apply_mutations(self, batch, *, chunk: int = 1024):
        """Applies one mutation batch end-to-end: compact the CSR at
        ``epoch + 1``, incrementally refresh exactly the invalidated walk
        segments, persist the new slab under its epoch directory (when a
        checkpoint dir is configured), and commit the two-epoch swap.
        Returns the :class:`repro.dynamic.RefreshReport`.
        """
        from repro.dynamic import (apply_mutations as _apply,
                                   refresh_walk_index, save_epoch_index)

        self._check_open()
        index = self.ensure_index()
        new_graph, changed = _apply(self.graph, batch)
        new_index, report = refresh_walk_index(
            index, new_graph, changed,
            step_impl=self.config.walk_index().step_impl, chunk=chunk)
        directory = self.config.serving.checkpoint_dir
        if directory is not None:
            save_epoch_index(directory, new_index)
        self.commit_epoch(new_graph, new_index)
        return report
