"""Shard-execution runtime: the one distributed layer under engine, index
build, and query serving.

Before this module existed the shard-execution pattern lived in three
copies: the ``shard_map`` plumbing in ``engine/gas.py``, the host shard loop
+ ``shard_map`` build in ``query/index.py``, and the gather-everything wave
program in ``query/scheduler.py``. Each reimplemented the same four moves:

  * **mesh acquisition** — build (or adopt) a 1-D mesh over a ``"vertex"``
    axis sized to the shard count;
  * **per-shard placement** — put stacked ``[S, ...]`` blocks on the mesh so
    device ``s`` holds exactly block ``s`` (``P(axis)``) and broadcast
    arguments replicated (``P()``);
  * **sharded-vs-single-device dispatch** — run a per-shard program either
    as one ``shard_map`` over the mesh, or as a host loop over shard ids
    when only one device is available (the two are the same program; only
    the reduction across shards moves from ``psum`` to the host);
  * **per-shard checkpoint round-trip** — persist / restore one atomic
    checkpoint dir per shard (``<dir>/shard_<s>/step_<k>/``) so a sharded
    job can crash/retry one shard at a time without exposing a torn
    artifact.

:class:`ShardRuntime` owns the first three; the module-level checkpoint
helpers own the fourth. ``engine/gas.py`` (superstep execution),
``query/index.py`` (sharded slab build + persistence) and
``query/scheduler.py`` (serving from per-shard slab blocks) are all built
on it — one execution layer, three workloads.

The runtime additionally owns the **AOT wave-program ladder cache**
(:class:`WaveProgramCache`, reached via :meth:`ShardRuntime.wave_cache`):
compiled wave programs keyed by their static geometry
(:class:`repro.query.engine.WaveSpec`), shared process-wide so every
scheduler/replica serving the same slab geometry reuses one executable —
and a trace counter (:func:`record_wave_trace` / :func:`wave_trace_count`)
incremented from *inside* the traced wave bodies, which is what lets tests
and the bench smoke assert "zero retraces after ladder warmup" directly.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import (CheckpointCorruptError, latest_step,
                              restore_checkpoint, save_checkpoint)

DEFAULT_AXIS = "vertex"


# --- AOT wave-program ladder cache ------------------------------------------


class WaveProgramCache:
    """Process-wide cache of compiled wave programs, keyed by static
    geometry (a hashable spec — :class:`repro.query.engine.WaveSpec`).

    One entry per (walk-slots, query-slots, shards, …) bucket shape: the
    scheduler pads each wave's operands up to the nearest ladder bucket, so
    an admission-driven change in the query mix resolves to a spec already
    in the cache instead of retracing mid-serving. Programs close over no
    per-scheduler state (slab and graph arrays are operands), so replicas
    with identical geometry share executables.
    """

    def __init__(self):
        self._programs: Dict[Any, Callable] = {}
        self.hits = 0
        self.misses = 0

    def get_or_build(self, spec, builder: Callable) -> Callable:
        try:
            fn = self._programs[spec]
            self.hits += 1
            return fn
        except KeyError:
            self.misses += 1
            fn = self._programs[spec] = builder(spec)
            return fn

    def __len__(self) -> int:
        return len(self._programs)

    def clear(self) -> None:
        self._programs.clear()


_WAVE_CACHE = WaveProgramCache()

# Traces of wave bodies, counted from inside the traced function (tracing
# executes the Python body; steady-state executions do not) — the direct
# "did serving retrace?" signal the recompile-count test and the bench
# smoke gate assert on.
_WAVE_TRACES = 0


def record_wave_trace(spec: Any = None) -> None:
    """Called at the top of every wave-program body; increments only while
    jax is *tracing* the body (compile), never on a steady-state call."""
    global _WAVE_TRACES
    _WAVE_TRACES += 1


def wave_trace_count() -> int:
    return _WAVE_TRACES


def reset_wave_trace_count() -> int:
    """Resets the counter and returns the value it had."""
    global _WAVE_TRACES
    prev, _WAVE_TRACES = _WAVE_TRACES, 0
    return prev


@dataclasses.dataclass(frozen=True)
class ShardRuntime:
    """Mesh + dispatch context for per-shard programs.

    ``mesh is None`` means single-device dispatch: the same per-shard body
    runs as a host loop over shard ids (:meth:`map_shards`) instead of one
    ``shard_map``; callers branch on :attr:`is_mesh` for the pieces that
    genuinely differ (a ``psum`` vs a host-side sum).
    """

    num_shards: int
    axis_name: str = DEFAULT_AXIS
    mesh: Optional[Mesh] = None

    # --- acquisition -----------------------------------------------------

    @classmethod
    def acquire(
        cls,
        num_shards: Optional[int] = None,
        axis_name: str = DEFAULT_AXIS,
        devices: Optional[Sequence[Any]] = None,
    ) -> "ShardRuntime":
        """Builds a runtime for ``num_shards`` shards.

        With enough devices the runtime carries a 1-D mesh over the first
        ``num_shards`` of them; otherwise it is a single-device (host-loop)
        runtime for the same shard count — callers get the same API either
        way, which is the whole point.
        """
        devs = list(devices if devices is not None else jax.devices())
        if num_shards is None:
            num_shards = len(devs)
        if num_shards < 1:
            raise ValueError(f"num_shards must be ≥ 1, got {num_shards}")
        if len(devs) >= num_shards > 1 or (num_shards == 1):
            mesh = Mesh(np.asarray(devs[:num_shards]), (axis_name,))
            return cls(num_shards=num_shards, axis_name=axis_name, mesh=mesh)
        return cls(num_shards=num_shards, axis_name=axis_name, mesh=None)

    @classmethod
    def for_mesh(cls, mesh: Mesh, axis_name: Optional[str] = None) -> "ShardRuntime":
        """Adopts an existing 1-D mesh (the engine entry point)."""
        ax = axis_name if axis_name is not None else mesh.axis_names[0]
        if ax not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} do not include {ax!r}")
        return cls(num_shards=int(mesh.shape[ax]), axis_name=ax, mesh=mesh)

    @property
    def is_mesh(self) -> bool:
        return self.mesh is not None

    def require_mesh(self) -> Mesh:
        if self.mesh is None:
            raise ValueError(
                f"this runtime dispatches {self.num_shards} shards on a "
                "single device (host loop); the caller needs a mesh — "
                "acquire one with ShardRuntime.acquire(num_shards) on a "
                "multi-device backend")
        return self.mesh

    # --- placement -------------------------------------------------------

    def sharding(self, replicated: bool = False) -> NamedSharding:
        """NamedSharding for a stacked ``[S, ...]`` block array (or a
        replicated argument)."""
        return NamedSharding(self.require_mesh(),
                             P() if replicated else P(self.axis_name))

    def place_sharded(self, arr) -> jnp.ndarray:
        """Puts a stacked ``[S, ...]`` array so device ``s`` holds only
        block ``s`` — on a single-device runtime this is a plain
        ``jnp.asarray`` (the host *is* the only shard holder)."""
        if not self.is_mesh:
            return jnp.asarray(arr)
        if arr.shape[0] != self.num_shards:
            raise ValueError(
                f"leading dim {arr.shape[0]} != num_shards {self.num_shards}")
        return jax.device_put(arr, self.sharding())

    # --- dispatch --------------------------------------------------------

    def shard_map_fn(
        self,
        body: Callable,
        num_sharded: int,
        num_replicated: int = 0,
        num_outputs: int = 1,
        check_vma: bool = True,
        replicated_out: bool = False,
    ) -> Callable:
        """Wraps a per-shard body as one ``shard_map`` over the mesh
        (unjitted — the dry-run path wants to control ``in_shardings``).

        The body sees its first ``num_sharded`` arguments as ``[1, ...]``
        per-shard blocks and the rest replicated; every output is a
        ``[1, ...]`` per-shard block (``P(axis)``), or, with
        ``replicated_out``, a value every device holds alike (``P()``).
        ``check_vma=False`` is for bodies that lower through ``pallas_call``
        (jax has no replication rule for it).
        """
        ax = self.axis_name
        in_specs = (P(ax),) * num_sharded + (P(),) * num_replicated
        out = P() if replicated_out else P(ax)
        out_specs = out if num_outputs == 1 else (out,) * num_outputs
        kwargs = {} if check_vma else {"check_vma": False}
        return jax.shard_map(body, mesh=self.require_mesh(),
                             in_specs=in_specs, out_specs=out_specs,
                             **kwargs)

    def sharded_call(self, body: Callable, num_sharded: int,
                     num_replicated: int = 0, num_outputs: int = 1,
                     check_vma: bool = True,
                     donate_argnums: Sequence[int] = (),
                     replicated_out: bool = False) -> Callable:
        """Jitted :meth:`shard_map_fn` — the common execution entry.

        ``donate_argnums`` forwards to ``jax.jit``: callers donate operands
        that are dead after the body's prologue (e.g. the wave scheduler's
        per-wave walk state) so XLA can reuse their buffers instead of
        allocating fresh ones every dispatch."""
        return jax.jit(self.shard_map_fn(
            body, num_sharded, num_replicated, num_outputs,
            check_vma=check_vma, replicated_out=replicated_out),
            donate_argnums=tuple(donate_argnums))

    def map_shards(self, program: Callable, *args, **kwargs) -> list:
        """Single-device dispatch: runs ``program(shard_id, *args)`` for
        every shard id in order and returns the per-shard results — the
        host-loop twin of :meth:`sharded_call` for shard-parallel bodies
        (no collectives; cross-shard reductions happen on the host)."""
        return [program(s, *args, **kwargs) for s in range(self.num_shards)]

    # --- per-shard randomness -------------------------------------------

    @staticmethod
    def shard_key(key_data: jnp.ndarray, axis_name: str) -> jax.Array:
        """Inside a shard body: rebuild the PRNG key and fold in the shard
        id, so each shard draws an independent, mesh-shape-reproducible
        stream. ``key_data`` is the raw uint32 data (keys cannot cross the
        shard_map boundary as opaque key arrays on jax 0.4)."""
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        return jax.random.fold_in(key, jax.lax.axis_index(axis_name))

    @staticmethod
    def key_data(key: jax.Array) -> jnp.ndarray:
        return jax.random.key_data(key)

    # --- AOT wave-program ladder ----------------------------------------

    @staticmethod
    def wave_cache() -> WaveProgramCache:
        """The process-wide :class:`WaveProgramCache`. A staticmethod on the
        (frozen, hashable) runtime rather than a field: the cache is shared
        across runtimes by design — two schedulers over the same slab
        geometry must hit the same compiled program."""
        return _WAVE_CACHE


# --- per-shard checkpoint round-trip ----------------------------------------
#
# Layout: <directory>/shard_<s>/step_<k>/ — one atomic checkpoint/ step dir
# per shard, so a sharded job persists (and crash/retries) one shard at a
# time and a reader can detect a partial write (missing shards) instead of
# silently consuming a torn artifact.


def shard_dir(directory: str, shard: int) -> str:
    return os.path.join(directory, f"shard_{shard:04d}")


def list_shard_dirs(directory: str) -> list:
    """Sorted shard subdirectories under ``directory`` (empty if none —
    i.e. the directory holds a monolithic checkpoint or nothing)."""
    if not os.path.isdir(directory):
        return []
    return sorted(d for d in os.listdir(directory) if d.startswith("shard_"))


def save_shard_checkpoint(directory: str, shard: int, tree: Any,
                          step: int = 0) -> str:
    """Atomic save of one shard's tree under ``<dir>/shard_<s>/step_<k>/``."""
    return save_checkpoint(shard_dir(directory, shard), step, tree)


def load_checkpoint_tree(directory: str, step: Optional[int] = None) -> dict:
    """Self-describing restore: the template comes from the checkpoint's
    own ``tree.json`` metadata, so callers need not know shapes up front.

    A missing checkpoint raises :class:`FileNotFoundError`; a present but
    torn / corrupt one raises :class:`~repro.checkpoint.
    CheckpointCorruptError` naming the step dir — both actionable,
    neither a bare ``KeyError`` or shape mismatch.
    """
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory!r}")
    step_dir = os.path.join(directory, f"step_{step:08d}")
    meta_path = os.path.join(step_dir, "tree.json")
    if not os.path.isfile(meta_path):
        raise CheckpointCorruptError(
            f"checkpoint {step_dir!r} has no tree.json — partial or torn "
            f"write; quarantine and rebuild this shard dir")
    with open(meta_path) as f:
        try:
            meta = json.load(f)
        except ValueError as e:
            raise CheckpointCorruptError(
                f"checkpoint {step_dir!r} has unreadable tree.json: "
                f"{e}") from e
    like = {
        path: np.zeros(shape, dtype=np.dtype(dtype))
        for path, shape, dtype in zip(
            meta["paths"], meta["shapes"], meta["dtypes"])
    }
    return restore_checkpoint(directory, step, like)


def quarantine_shard_dir(directory: str, shard: int) -> str:
    """Moves a corrupt shard checkpoint dir aside (``quarantine.shard_<s>``
    — invisible to :func:`list_shard_dirs`) so a rebuild can atomically
    write a fresh one in its place. Returns the quarantine path."""
    src = shard_dir(directory, shard)
    dst = os.path.join(directory, f"quarantine.shard_{shard:04d}")
    k = 0
    while os.path.exists(dst):
        k += 1
        dst = os.path.join(directory, f"quarantine.shard_{shard:04d}.{k}")
    os.rename(src, dst)
    return dst


def load_shard_checkpoints(
    directory: str, step: Optional[int] = None, on_error: str = "raise"
) -> Dict[int, dict]:
    """Restores every shard checkpoint under ``directory``.

    Returns ``{shard_index_from_dirname: tree}``; shard-content validation
    (consistent metadata, no missing shards) belongs to the caller, which
    knows what the trees mean.

    ``on_error="raise"`` (default) propagates the first corrupt / partial
    shard; ``on_error="collect"`` instead maps each failing shard to its
    exception in the result (``{shard: tree_or_exception}``) so callers
    like :func:`repro.query.index.load_or_repair_walk_index` can
    quarantine and rebuild exactly the broken shards.
    """
    if on_error not in ("raise", "collect"):
        raise ValueError(f"on_error must be 'raise' or 'collect', "
                         f"got {on_error!r}")
    dirs = list_shard_dirs(directory)
    if not dirs:
        raise FileNotFoundError(f"no shard checkpoints under {directory!r}")
    out: Dict[int, dict] = {}
    for d in dirs:
        shard = int(d.split("_")[1])
        try:
            out[shard] = load_checkpoint_tree(os.path.join(directory, d),
                                              step)
        except (CheckpointCorruptError, FileNotFoundError) as e:
            if on_error == "raise":
                raise
            out[shard] = e
    return out
