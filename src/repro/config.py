"""Layered runtime configuration — every dispatch flag defined exactly once.

Before PR 5 the same knobs (``draw``, ``step_impl``, ``p_s``, seed plumbing)
were declared independently on three per-subsystem dataclasses
(``FrogWildConfig`` for the walker oracle, ``EngineConfig`` for the
distributed engine, ``WalkIndexConfig`` for the index build), so a flag's
default — and its meaning — could drift between layers. This module is now
the single source of truth:

* :class:`KernelConfig`  — kernel dispatch flags (which backend executes a
  walker step / stitch round / tally — see ``kernels/README.md``);
* :class:`ShardConfig`   — placement and runtime shape (shard count, mesh
  axis, exchange-buffer slack, streaming block size, PRNG seed);
* :class:`ServingConfig` — walk-index geometry and scheduler shapes (the
  serving layer's fixed device-program dimensions);
* :class:`RuntimeConfig` — the walk process parameters (``N``, ``t``,
  ``p_T``, ``p_s``, erasure model) plus one instance of each layer above.
  This is the config :class:`repro.service.FrogWildService` consumes.

The legacy dataclasses still exist (tests and downstream code construct
them directly) but are **derived views**: they are defined here, their
shared-field defaults reference the layer defaults (one definition per
flag), and :meth:`RuntimeConfig.frogwild` / :meth:`RuntimeConfig.engine` /
:meth:`RuntimeConfig.walk_index` project a ``RuntimeConfig`` onto them.
The ``from_frogwild`` / ``from_engine`` / ``from_walk_index`` lifters go
the other way, so the deprecation shims can route a legacy call through
the service without changing a single bit of behaviour.

This module is dependency-free (no jax) so every layer can import it.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import TYPE_CHECKING, Optional, Tuple

if TYPE_CHECKING:  # stdlib-only module; safe for type checkers, but not
    # imported at runtime — this module stays jax- and repro-free.
    from repro.distributed.faults import FaultPlan

# Walk-process defaults (paper §2.2: N frogs, t supersteps, teleport p_T,
# synchronization probability p_s) — shared by RuntimeConfig and the legacy
# per-subsystem views.
DEFAULT_NUM_FROGS = 100_000
DEFAULT_NUM_STEPS = 4
DEFAULT_P_T = 0.15
DEFAULT_P_S = 1.0


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Kernel dispatch flags (see ``kernels/README.md`` for the full table).

    ``draw`` picks the blocking-walk scatter draw, ``step_impl`` the plain
    (p_s = 1) walker-step backend, ``stitch_impl`` the serving wave's
    stitch-round backend.
    """

    draw: str = "auto"          # auto | rejection | cumsum
    step_impl: str = "xla"      # xla | pallas | stream | auto | ref
    stitch_impl: str = "xla"    # xla | pallas | ref


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """Placement / runtime-shape layer.

    ``num_shards`` is the range-shard count used for the channel erasure
    granularity, engine placement, and sharded serving; ``vertex_block``
    enables the blocked CSR slabs the streaming step kernel needs.
    """

    num_shards: int = 1
    axis_name: str = "vertex"
    capacity_factor: float = 4.0     # engine per-channel buffer slack (≥ 1)
    vertex_block: Optional[int] = None
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Walk-index geometry + scheduler device-program shapes.

    ``build_shards`` is the *build-time* partitioning of the index (it
    determines the per-shard key folding, hence the slab content);
    ``checkpoint_dir`` makes the service persist / reuse the index through
    ``checkpoint/`` atomic step dirs.

    The fault-supervision knobs govern the scheduler's wave supervisor
    (``query/scheduler.py``): a wave that raises a transient fault or
    exceeds ``wave_timeout_s`` is retried up to ``max_retries`` times with
    exponential backoff + jitter before failing over (mesh → fused
    single-device dispatch) or raising; a permanent shard fault instead
    evicts the shard and serves degraded waves with a widened
    ``epsilon_bound``.

    The wave-program knobs govern dispatch and compilation:
    ``sharded_dispatch`` picks the single-device sharded wave — ``"fused"``
    (one compiled program: ``lax.scan`` over stitch rounds against the
    stacked slab) or ``"loop"`` (the legacy S × rounds host loop, kept as
    the byte-identity reference). ``walk_buckets`` / ``query_buckets``
    override the AOT wave-program ladder (each wave runs at the smallest
    bucket ≥ its allocation; ``None`` = the cap and its halvings), and
    ``aot_warmup`` pre-compiles every ladder bucket at scheduler build so
    serving never traces mid-wave. ``donate_wave_buffers`` donates the
    per-wave walk-state operands to the executable (buffer reuse instead
    of fresh allocations every wave).
    """

    segments_per_vertex: int = 16    # R — endpoints stored per vertex
    segment_len: int = 4             # L — steps per precomputed segment
    build_shards: int = 8            # index-build partitioning
    max_walks: int = 8192            # walk slots per wave
    max_queries: int = 8             # query slots per wave
    max_steps: int = 32              # walk-truncation cap for query plans
    checkpoint_dir: Optional[str] = None
    wave_time_estimate_s: Optional[float] = None  # seeds the admission EMA
    wave_timeout_s: Optional[float] = None  # per-wave deadline (None = off)
    max_retries: int = 2             # bounded retry of a faulted wave
    backoff_base_s: float = 0.02     # exponential backoff: base · 2^(a−1)
    backoff_max_s: float = 0.5       # … clamped here (± jitter)
    sharded_dispatch: str = "fused"  # single-device sharded wave: fused | loop
    donate_wave_buffers: bool = True  # donate walk-state operands to XLA
    walk_buckets: Optional[Tuple[int, ...]] = None   # AOT ladder override
    query_buckets: Optional[Tuple[int, ...]] = None  # AOT ladder override
    aot_warmup: bool = False         # pre-compile the ladder at build time


_KERNEL = KernelConfig()
_SHARD = ShardConfig()
_SERVING = ServingConfig()


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """The one config the :class:`repro.service.FrogWildService` consumes.

    Walk-process parameters live at the top level; everything about *how*
    the process executes lives in the three layers. Derive the legacy
    per-subsystem views with :meth:`frogwild` / :meth:`engine` /
    :meth:`walk_index`.
    """

    num_frogs: int = DEFAULT_NUM_FROGS
    num_steps: int = DEFAULT_NUM_STEPS
    p_T: float = DEFAULT_P_T
    p_s: float = DEFAULT_P_S
    erasure: str = "none"            # none | independent | channel
    kernel: KernelConfig = _KERNEL
    runtime: ShardConfig = _SHARD
    serving: ServingConfig = _SERVING
    # Deterministic fault-injection schedule (repro.distributed.faults.
    # FaultPlan) threaded to the scheduler's wave supervisor; None = no
    # injection (the supervisor still handles real faults/timeouts).
    faults: Optional["FaultPlan"] = None

    # --- projections onto the legacy per-subsystem views -----------------

    def frogwild(self) -> "FrogWildConfig":
        return FrogWildConfig(
            num_frogs=self.num_frogs, num_steps=self.num_steps,
            p_T=self.p_T, p_s=self.p_s, erasure=self.erasure,
            num_shards=max(1, self.runtime.num_shards),
            draw=self.kernel.draw, step_impl=self.kernel.step_impl,
        )

    def engine(self) -> "EngineConfig":
        return EngineConfig(
            num_frogs=self.num_frogs, num_steps=self.num_steps,
            p_T=self.p_T, p_s=self.p_s,
            capacity_factor=self.runtime.capacity_factor,
            axis_name=self.runtime.axis_name,
            draw=self.kernel.draw, step_impl=self.kernel.step_impl,
        )

    def walk_index(self) -> "WalkIndexConfig":
        return WalkIndexConfig(
            segments_per_vertex=self.serving.segments_per_vertex,
            segment_len=self.serving.segment_len,
            num_shards=self.serving.build_shards,
            step_impl=self.kernel.step_impl,
            seed=self.runtime.seed,
        )

    # --- lifters from the legacy views (used by the deprecation shims) ---

    @classmethod
    def from_frogwild(cls, cfg: "FrogWildConfig") -> "RuntimeConfig":
        return cls(
            num_frogs=cfg.num_frogs, num_steps=cfg.num_steps, p_T=cfg.p_T,
            p_s=cfg.p_s, erasure=cfg.erasure,
            kernel=KernelConfig(draw=cfg.draw, step_impl=cfg.step_impl),
            runtime=ShardConfig(num_shards=cfg.num_shards),
        )

    @classmethod
    def from_engine(cls, cfg: "EngineConfig",
                    num_shards: int = 1) -> "RuntimeConfig":
        return cls(
            num_frogs=cfg.num_frogs, num_steps=cfg.num_steps, p_T=cfg.p_T,
            p_s=cfg.p_s, erasure="channel" if cfg.p_s < 1.0 else "none",
            kernel=KernelConfig(draw=cfg.draw, step_impl=cfg.step_impl),
            runtime=ShardConfig(num_shards=num_shards,
                                axis_name=cfg.axis_name,
                                capacity_factor=cfg.capacity_factor),
        )

    @classmethod
    def from_walk_index(cls, cfg: "WalkIndexConfig") -> "RuntimeConfig":
        return cls(
            kernel=KernelConfig(step_impl=cfg.step_impl),
            runtime=ShardConfig(seed=cfg.seed),
            serving=ServingConfig(
                segments_per_vertex=cfg.segments_per_vertex,
                segment_len=cfg.segment_len, build_shards=cfg.num_shards),
        )


# ---------------------------------------------------------------------------
# Legacy per-subsystem views. Field *sets* are frozen for back-compat; the
# shared-flag defaults reference the layer defaults above so each flag has
# exactly one definition. New code should construct a RuntimeConfig and use
# the service facade; these remain for the deprecation shims and tests.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FrogWildConfig:
    """Walker-oracle view (``core/frogwild.py``). ``num_shards`` here is the
    channel-erasure granularity (destination range shards)."""

    num_frogs: int = DEFAULT_NUM_FROGS
    num_steps: int = DEFAULT_NUM_STEPS
    p_T: float = DEFAULT_P_T
    p_s: float = DEFAULT_P_S
    erasure: str = "none"            # none | independent | channel
    num_shards: int = 16             # channel model: destination shards
    draw: str = _KERNEL.draw
    step_impl: str = _KERNEL.step_impl


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Distributed-engine view (``engine/gas.py``); the shard count comes
    from the mesh, not the config."""

    num_frogs: int = DEFAULT_NUM_FROGS
    num_steps: int = DEFAULT_NUM_STEPS
    p_T: float = DEFAULT_P_T
    p_s: float = DEFAULT_P_S
    capacity_factor: float = _SHARD.capacity_factor
    axis_name: str = _SHARD.axis_name
    draw: str = _KERNEL.draw
    step_impl: str = _KERNEL.step_impl
    # "stream"/"auto" need the blocked slabs
    # (build_distributed_graph(vertex_block=...)).


@dataclasses.dataclass(frozen=True)
class WalkIndexConfig:
    """Index-build view (``query/index.py``). ``num_shards`` is the build
    partitioning — it determines the per-shard key folding and therefore
    the slab content."""

    segments_per_vertex: int = _SERVING.segments_per_vertex
    segment_len: int = _SERVING.segment_len
    num_shards: int = _SERVING.build_shards
    step_impl: str = _KERNEL.step_impl
    seed: int = _SHARD.seed


def warn_deprecated(old: str, new: str) -> None:
    """One-liner for the legacy entry-point shims (stacklevel points at the
    caller of the deprecated function, not the shim)."""
    warnings.warn(
        f"{old} is deprecated; use {new} (see repro/service.py)",
        DeprecationWarning, stacklevel=3,
    )


__all__ = [
    "KernelConfig",
    "ShardConfig",
    "ServingConfig",
    "RuntimeConfig",
    "FrogWildConfig",
    "EngineConfig",
    "WalkIndexConfig",
]
