"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would refuse
(misaligned blocks, unsupported kernel ops, programs that do not fit the
device's memory). Nothing runs, so these tests say nothing about results or
times. They cover the served wave program, the index-build walker and the
batch walker at LiveJournal's size, every Pallas kernel that compiles for
v5e, and the kernels Mosaic refuses (``ops.TPU_REFUSED``), so that list
stays true.

Code that asks ``jax.default_backend()`` sees the CPU here; the tests that
need compiled Pallas kernels make ``ops.resolve_interpret`` see a TPU.
"""
import functools
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import FrogWildConfig
from repro.configs.frogwild_graphs import LIVEJOURNAL_FULL
from repro.kernels import ops
from repro.query.engine import WaveSpec, build_wave_program
from repro.query.index import _row_walk_program, segment_mask_block_size
from repro.core.frogwild import _walks_program

N_LJ = LIVEJOURNAL_FULL.n
NNZ_LJ = round(N_LJ * LIVEJOURNAL_FULL.avg_out_deg)
R, L, W, Q = 16, 4, 8192, 8          # the serving defaults (config.py)
BUILD_SHARDS = 8
DEVICE_BYTES = 16 * 1024 ** 3        # one v5e chip's HBM


@pytest.fixture(scope="module")
def topo():
    """The described topology, with JAX's persistent compile cache off (a
    compile for a described chip is written to it but cannot be read back)
    and the TPU compiler's logs off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        desc = None
        reason = f"no v5e:2x2 topology can be described here: {e}"
    try:
        if desc is None:
            pytest.skip(reason)
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` — an argument shape placed on one chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture
def on_tpu(monkeypatch):
    """Makes the backend-resolved Pallas mode compile, as on the chip."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)


def _has_mosaic_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_served_wave_program_compiles_at_livejournal_size(spec, on_tpu):
    """The served wave program (fused, dense slab, stitch_impl="xla") at
    n = 4,847,571, R=16, W=8192, Q=8 fits one chip, runs no Pallas kernel,
    and hands back only the walks' endpoints: 4·W bytes, whatever n."""
    wave = build_wave_program(WaveSpec(
        n=N_LJ, R=R, L=L, q_max=32 // L, S=1, sz=N_LJ, W=W, Q=Q, p_T=0.15,
        impl="xla", donate=True))
    compiled = wave.lower(
        spec((N_LJ * R,)), spec((N_LJ + 1,)), spec((NNZ_LJ,)),
        spec((N_LJ,)), spec((W,)), spec((W,), jnp.bool_), spec((W,)),
        spec((W,)), spec((2,), jnp.uint32), spec((1,), jnp.bool_)).compile()
    assert _device_bytes(compiled) < DEVICE_BYTES
    assert compiled.memory_analysis().output_size_in_bytes == 4 * W
    assert not _has_mosaic_kernel(compiled)


def test_index_build_walker_compiles_for_one_build_shard(spec):
    """The walk-index row walker over one of the 8 build shards of a
    LiveJournal-sized graph (the single-chip build's device program)."""
    shard = -(-N_LJ // BUILD_SHARDS)
    n_pad = shard * BUILD_SHARDS
    run = _row_walk_program(n_pad, "xla", R, L,
                            segment_mask_block_size(N_LJ))
    compiled = run.lower(spec((n_pad + 1,)), spec((NNZ_LJ,)), spec((n_pad,)),
                         spec((shard,)), spec((2,), jnp.uint32)).compile()
    assert _device_bytes(compiled) < DEVICE_BYTES


@pytest.mark.parametrize("erasure", ["none", "channel"])
def test_batch_walker_compiles_with_the_graph_as_operands(spec, erasure):
    """``FrogWildService.pagerank``'s single-device walker at LiveJournal's
    size, with the CSR (and, under channel erasure at p_s < 1, the graph's
    channel layout) passed in rather than baked into the executable."""
    S = 4
    cfg = FrogWildConfig(num_frogs=4445, num_steps=32, erasure=erasure,
                         p_s=1.0 if erasure == "none" else 0.7,
                         num_shards=S, draw="rejection", step_impl="xla")
    if erasure == "none":
        keys, layouts = (), ()
    else:
        keys = (("channel_layout", S),)
        layouts = ((spec((NNZ_LJ,)), spec((N_LJ, S)), spec((N_LJ, S))),)
    compiled = _walks_program.lower(
        spec((N_LJ + 1,)), spec((NNZ_LJ,)), spec((N_LJ,)), layouts, None,
        spec((2,), jnp.uint32), n=N_LJ, cfg=cfg,
        layout_keys=keys).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 2 ** 26
    assert _device_bytes(compiled) < DEVICE_BYTES


@pytest.mark.parametrize("N,n,vertex_block,frog_block", [
    (8192, 65_536, 1024, 1024),
    (8192, 65_536, 512, 1024),        # rounded up to the 1024 tile
    (5000, 70_001, 2048, 256),
    (100, 1000, 1024, 1024),          # one block covers each array
    (W, (Q + 1) * N_LJ, 1024, 1024),  # the wave tally's bins
])
def test_frog_count_kernel_compiles(spec, on_tpu, N, n, vertex_block,
                                    frog_block):
    count = functools.partial(ops.frog_count, n=n, impl="pallas",
                              vertex_block=vertex_block,
                              frog_block=frog_block)
    compiled = jax.jit(count).lower(spec((N,))).compile()
    assert _has_mosaic_kernel(compiled)


def test_auto_choices_compile(spec, on_tpu):
    """No ``impl="auto"`` picks a kernel the chip's compiler refuses."""
    n, nnz, N = 4096, 40_000, 2048
    step = functools.partial(ops.frog_step, n=n, impl="auto")
    jax.jit(step).lower(spec((N,)), spec((N,)), spec((N,)), spec((n + 1,)),
                        spec((nnz,)), spec((n,))).compile()
    for bins, kernel in ((4096, True), (50_000_000, False)):  # pallas, sort
        count = functools.partial(ops.frog_count, n=bins, impl="auto")
        compiled = jax.jit(count).lower(spec((65_536,))).compile()
        assert _has_mosaic_kernel(compiled) == kernel


def _kernel(module: str, name: str):
    return getattr(importlib.import_module(f"repro.kernels.{module}"), name)


def _refused_cases():
    n, N, R_, BV, BF = 65_536, 8192, 16, 1024, 1024
    nvb = n // BV
    cases = {
        "frog_step": lambda s, rng: (
            functools.partial(_kernel("frog_step", "frog_step"), n_pad=n,
                              vertex_block=BV, frog_block=BF,
                              interpret=False, use_device_rng=rng),
            [s((N,)), s((N,)), s((1,) if rng else (N,)), s((n + 1,)),
             s((8 * n,)), s((n,))]),
        "frog_step_stream": lambda s, rng: (
            functools.partial(_kernel("frog_step_stream",
                                      "frog_step_stream_sorted"),
                              num_fb=N // BF, vertex_block=BV, frog_block=BF,
                              interpret=False, use_device_rng=rng),
            [s((N,)), s((N,)), s((1,) if rng else (N,)), s((N // BF,)),
             s((nvb, BV)), s((nvb, BV)), s((nvb, 16 * BV))]),
        "stitch_step": lambda s, rng: (
            functools.partial(_kernel("stitch", "stitch_step"), R=R_,
                              n_pad=n, vertex_block=BV, walk_block=BF,
                              interpret=False, use_device_rng=rng),
            [s((N,)), s((N,)), s((1,) if rng else (N,)), s((n * R_,))]),
        "stitch_gather": lambda s, rng: (
            functools.partial(_kernel("stitch", "stitch_gather"), R=R_,
                              walk_block=BF, interpret=False,
                              use_device_rng=rng),
            [s((N,)), s((1,) if rng else (N,)), s((n * R_,))]),
        "stitch_step_local": lambda s, rng: (
            functools.partial(_kernel("stitch", "stitch_step_local"), R=R_,
                              shard_size=n, sz_pad=n, vertex_block=BV,
                              walk_block=BF, interpret=False,
                              use_device_rng=rng),
            [s((N,)), s((N,)), s((1,) if rng else (N,)), s((1,)),
             s((n * R_,))]),
        "stitch_gather_local": lambda s, rng: (
            functools.partial(_kernel("stitch", "stitch_gather_local"),
                              R=R_, shard_size=n, walk_block=BF,
                              interpret=False, use_device_rng=rng),
            [s((N,)), s((1,) if rng else (N,)), s((1,)), s((n * R_,))]),
        "spmv_ell": lambda s, rng: (
            functools.partial(_kernel("spmv_ell", "spmv_ell_slab"),
                              row_block=128, interpret=False),
            [s((n, 32)), s((n, 32), jnp.float32), s((n,), jnp.float32)]),
    }
    assert set(cases) == ops.TPU_REFUSED
    return [pytest.param(cases[k], rng, id=f"{k}-rng_{rng}")
            for k in sorted(cases)
            for rng in ((False,) if k == "spmv_ell" else (False, True))]


@pytest.mark.parametrize("case,device_rng", _refused_cases())
def test_refused_kernels_are_refused_by_mosaic(spec, case, device_rng):
    """Every kernel in ``ops.TPU_REFUSED`` is still refused, for the
    recorded reason. With the in-kernel device RNG (``rng="device"``, which
    exists only compiled) Mosaic stops one op earlier, at the 1-D
    ``pltpu.bitcast`` of the drawn bits."""
    fn, args = case(spec, device_rng)
    if device_rng:
        error, reason = ValueError, "Not implemented: bitcast 1D"
    else:
        error, reason = NotImplementedError, ops.TPU_REFUSAL.split(": ", 1)[1]
    with pytest.raises(error, match=reason):
        jax.jit(fn).lower(*args).compile()
