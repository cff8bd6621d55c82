"""Jitted public wrappers over the Pallas kernels.

These handle padding, hybrid spill application, and backend dispatch
(``impl="pallas"`` → interpret-mode kernel on CPU / compiled kernel on TPU,
``impl="ref"`` → pure-jnp oracle). Model code and the engine call these, so
swapping implementations is a config flag, not a code change.

The Pallas execution mode follows the backend (:func:`resolve_interpret`):
every ``interpret`` argument defaults to ``None``, which means interpreted
on the CPU backend and compiled on TPU. Kernels that the TPU compiler
refuses (:data:`TPU_REFUSED`) raise with the compiler's reason when asked
for explicitly in compiled mode, and no ``impl="auto"`` choice picks them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.partition import EllGraph
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.frog_scatter import frog_count as _frog_count
from repro.kernels.frog_step import frog_step as _frog_step
from repro.kernels.frog_step_stream import (BlockedCSR, block_csr,
                                            frog_step_stream_sorted)
from repro.kernels.spmv_ell import spmv_ell_slab
from repro.kernels.stitch import stitch_gather as _stitch_gather
from repro.kernels.stitch import stitch_gather_local as _stitch_gather_local
from repro.kernels.stitch import stitch_step as _stitch_step
from repro.kernels.stitch import stitch_step_local as _stitch_step_local

# Kernels that Mosaic (the TPU compiler) refuses for v5e. Each gathers from
# a VMEM table at per-walk dynamic indices (``jnp.take``), and Mosaic lowers
# no 1-D dynamic gather. tests/test_tpu_compile.py re-checks every entry
# against the compiler, so this table cannot drift from it.
TPU_REFUSED = frozenset({
    "frog_step", "frog_step_stream", "stitch_step", "stitch_gather",
    "stitch_step_local", "stitch_gather_local", "spmv_ell",
})
TPU_REFUSAL = "NotImplementedError: Only 2D gather is supported"

# XLA lays a 1-D 32-bit array out in 1024-element tiles on TPU; a Pallas
# block over such an array must be a multiple of it (or the whole array).
TPU_1D_TILE = 1024


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The Pallas execution mode for this process's backend.

    ``None`` resolves to ``True`` (the Pallas interpreter) on the CPU
    backend and to ``False`` (compiled by Mosaic) on TPU; any other backend
    is an error. An explicit ``True`` on TPU is refused: it would run the
    interpreter on the host while the chip idles. An explicit ``False`` is
    always honoured — compile rehearsals lower kernels for a described TPU
    from a CPU process.
    """
    backend = jax.default_backend()
    if interpret is None:
        if backend == "cpu":
            return True
        if backend == "tpu":
            return False
        raise RuntimeError(
            f"Pallas kernels run compiled on TPU or interpreted on CPU; "
            f"backend {backend!r} is neither")
    if interpret and backend == "tpu":
        raise ValueError(
            "interpret=True on a TPU backend would run the Pallas "
            "interpreter on the host; leave interpret=None to compile")
    return bool(interpret)


def _compiles(kernel: str, interpret: bool) -> bool:
    """Whether ``kernel`` can run in the resolved mode: everything runs in
    the interpreter, and compiled mode excludes :data:`TPU_REFUSED`."""
    return interpret or kernel not in TPU_REFUSED


def _require_compiles(kernel: str, interpret: bool) -> None:
    if not _compiles(kernel, interpret):
        raise NotImplementedError(
            f"Pallas kernel {kernel!r} does not compile for TPU: Mosaic "
            f"refuses its in-kernel gather ({TPU_REFUSAL}). Use impl='xla' "
            f"or impl='ref' on this backend")


def _tpu_block(block: int, size: int) -> int:
    """A 1-D block length Mosaic accepts over a ``size``-long array: the
    whole array, or a multiple of :data:`TPU_1D_TILE`."""
    return min(size, -(-block // TPU_1D_TILE) * TPU_1D_TILE)


# VMEM the resident frog_step kernel may spend on its graph block before
# impl="auto" switches to the HBM-streaming kernel (half a 16 MB core,
# leaving room for the frog tiles and double buffers).
STREAM_VMEM_BUDGET = 8 * 1024 * 1024


def resident_graph_bytes(n: int, nnz: int) -> int:
    """VMEM bytes the resident ``frog_step`` kernel pins for the graph
    (row_ptr + col_idx + deg, int32)."""
    return 4 * ((n + 1) + nnz + n)


def step_streams(impl: str, n: int, nnz: int,
                 vmem_budget: int = STREAM_VMEM_BUDGET) -> bool:
    """Whether :func:`frog_step` with ``impl`` over an ``(n, nnz)`` graph
    given a ``BlockedCSR`` runs the streamed kernel (and so reads it)."""
    return impl == "stream" or (
        impl == "auto" and resident_graph_bytes(n, nnz) > vmem_budget)


def _rng_mode(rng: str, interpret: bool, seed):
    """Resolves the kernel RNG mode → ``(use_device_rng, seed_arr | None)``.

    ``rng="device"`` swaps the caller-supplied bits stream for the
    in-kernel ``pltpu.prng_random_bits`` draw (the bits operand becomes a
    scalar seed). That primitive only lowers on real TPU — interpret mode
    keeps the seeded-bits path as the determinism contract, so requesting
    both is a configuration error, not a silent fallback. The seed is
    mandatory and must be **fresh per call** (e.g. fold in the superstep /
    stitch-round index): the kernels are deterministic in it, so reusing a
    seed replays the identical bit stream and correlates every draw.
    """
    if rng == "caller":
        return False, None
    if rng != "device":
        raise ValueError(f"unknown rng mode {rng!r}")
    if interpret:
        raise ValueError(
            'rng="device" draws slot bits with pltpu.prng_random_bits, '
            "which lowers only on TPU hardware; interpret mode keeps the "
            'caller-supplied bits path (rng="caller") for byte-for-byte '
            "determinism tests")
    if seed is None:
        raise ValueError(
            'rng="device" needs an explicit per-call seed= (fold in the '
            "step index; a reused seed replays the same bit stream and "
            "biases iterated walks)")
    return True, jnp.asarray([seed], jnp.int32)


def _pad_to(x: jnp.ndarray, m: int, axis: int = 0, value=0):
    size = x.shape[axis]
    pad = (-size) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def spmv(ell: EllGraph, x: jnp.ndarray, impl: str = "pallas",
         interpret: Optional[bool] = None,
         row_block: int = 128) -> jnp.ndarray:
    """Hybrid-ELL SpMV: y = P @ x (slab kernel + COO spill tail).

    ``x`` must have length ≥ max referenced vertex id; output has
    ``ell.n_rows`` entries (callers slice to the true n).
    """
    idx = _pad_to(ell.idx, row_block)
    w = _pad_to(ell.weight, row_block)
    if impl == "pallas":
        interpret = resolve_interpret(interpret)
        _require_compiles("spmv_ell", interpret)
        y = spmv_ell_slab(idx, w, x, row_block=row_block, interpret=interpret)
        y = y[: ell.n_rows]
    elif impl == "ref":
        y = kref.spmv_ref(ell.idx, ell.weight, x)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    if ell.spill_nnz:
        y = y + kref.spill_ref(ell.spill_src, ell.spill_dst, ell.spill_w, x,
                               ell.n_rows)
    return y


def frog_count(dest: jnp.ndarray, n: int, impl: str = "pallas",
               interpret: Optional[bool] = None, vertex_block: int = 1024,
               frog_block: int = 1024,
               assume_sorted: bool = False) -> jnp.ndarray:
    """Histogram of frog destinations into n vertex bins (int32).

    * ``pallas`` — compare-and-reduce tile kernel (O(N · n/vertex_block)
      one-hot work; wins when n is small and the VPU eats the tiles).
    * ``sort``   — sort + searchsorted segment counts (O((N+n) log N); the
      scalable path when n is large). With ``assume_sorted=True`` the sort
      is skipped — callers that already hold sorted destinations (e.g. the
      streamed superstep's block-sorted frogs) pay only the O(n log N)
      searchsorted pass.
    * ``ref``    — XLA scatter-add oracle.
    * ``auto``   — picks by the work model: one-hot tile work
      ``N · ⌈n/vertex_block⌉`` vs sort work ``(N+n) · ⌈log₂N⌉`` (always
      ``sort`` when the input is already sorted).

    Both block lengths are rounded up to a multiple of :data:`TPU_1D_TILE`
    (or the whole array), the blocking Mosaic accepts; interpreted and
    compiled runs share it.
    """
    if impl == "auto":
        N = dest.shape[0]
        onehot_work = N * -(-n // vertex_block)
        sort_work = (N + n) * max(1, int(np.ceil(np.log2(max(N, 2)))))
        impl = ("sort" if assume_sorted or onehot_work > sort_work
                else "pallas")
    if impl == "ref":
        return kref.frog_count_ref(dest, n)
    if impl == "sort":
        return kref.frog_count_sort(dest, n, assume_sorted=assume_sorted)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    interpret = resolve_interpret(interpret)
    vertex_block = _tpu_block(vertex_block, n)
    frog_block = _tpu_block(frog_block, max(8, dest.shape[0]))
    n_pad = ((n + vertex_block - 1) // vertex_block) * vertex_block
    dest_p = _pad_to(dest, frog_block, value=-1)  # -1 never matches a bin
    counts = _frog_count(dest_p, n_pad, vertex_block=vertex_block,
                         frog_block=frog_block, interpret=interpret)
    return counts[:n]


def _frog_step_stream(
    pos, die, bits, blocked: BlockedCSR, n: int, frog_block: int,
    interpret: bool, seed_arr: Optional[jnp.ndarray] = None,
):
    """Stream-path prologue/epilogue: sort frogs by vertex block, pad each
    block's segment to a ``frog_block`` multiple with inert frogs, run the
    scalar-prefetch streamed kernel, unsort. With ``seed_arr`` set the
    kernel draws its own bits (device RNG) and no bits stream is sorted."""
    N = pos.shape[0]
    bv, num_vb = blocked.vertex_block, blocked.num_blocks
    fb = min(frog_block, max(8, N))
    order = jnp.argsort(pos)            # by vertex ⇒ by vertex block
    pos_s, die_s = pos[order], die[order]
    bits_s = None if seed_arr is not None else bits[order]
    # Per-block frog counts from the sorted positions (the sort is reused by
    # the in-kernel segment-sum tally — no second histogram pass).
    starts = jnp.searchsorted(
        pos_s, jnp.arange(num_vb + 1, dtype=pos.dtype) * bv, side="left"
    ).astype(jnp.int32)
    cnt = starts[1:] - starts[:-1]
    pad_cnt = ((cnt + fb - 1) // fb) * fb
    pad_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(pad_cnt)])
    # Static worst case: at most min(num_vb, N) blocks can be nonempty
    # (each needs ≥ 1 frog) and only nonempty blocks get padded, by at most
    # fb − 1 slots each — keeps the padded arrays ∝ N, not num_vb, in the
    # sparse-frog regime.
    p_pad = int(np.ceil((N + min(num_vb, N) * (fb - 1)) / fb) * fb)
    blk_s = (pos_s // bv).astype(jnp.int32)
    dst = pad_off[blk_s] + jnp.arange(N, dtype=jnp.int32) - starts[blk_s]
    # Slot → owning vertex block (trailing unused slots ride with the last
    # block). Inert slots sit on their block's last vertex — keeps every
    # tile sorted and in-block — and never die, so they tally nothing and
    # their next position is discarded by the unsort below.
    slot_vid = jnp.clip(
        jnp.searchsorted(pad_off, jnp.arange(p_pad, dtype=jnp.int32),
                         side="right").astype(jnp.int32) - 1,
        0, num_vb - 1)
    pos_p = ((slot_vid + 1) * bv - 1).at[dst].set(pos_s)
    die_p = jnp.zeros((p_pad,), jnp.int32).at[dst].set(die_s)
    bits_p = (seed_arr if seed_arr is not None
              else jnp.zeros((p_pad,), jnp.int32).at[dst].set(bits_s))
    blk_vid = slot_vid[::fb]
    nxt_p, counts = frog_step_stream_sorted(
        pos_p, die_p, bits_p, blk_vid,
        blocked.row_off, blocked.deg, blocked.col,
        num_fb=p_pad // fb, vertex_block=bv, frog_block=fb,
        interpret=interpret, use_device_rng=seed_arr is not None,
    )
    # Count blocks the grid never visited hold uninitialized memory.
    counts = jnp.where((cnt > 0)[:, None],
                       counts.reshape(num_vb, bv), 0).reshape(-1)
    nxt = jnp.zeros((N,), jnp.int32).at[order].set(nxt_p[dst])
    return nxt, counts[:n]


def frog_step(
    pos: jnp.ndarray,
    die: jnp.ndarray,
    bits: Optional[jnp.ndarray],
    row_ptr: jnp.ndarray,
    col_idx: jnp.ndarray,
    deg: jnp.ndarray,
    n: int,
    impl: str = "pallas",
    interpret: Optional[bool] = None,
    vertex_block: int = 512,
    frog_block: int = 1024,
    blocked: Optional[BlockedCSR] = None,
    vmem_budget: int = STREAM_VMEM_BUDGET,
    rng: str = "caller",
    seed: Optional[int] = None,
):
    """Fused plain walker superstep → ``(next_pos[N], death_counts[n])``.

    * ``pallas`` — the VMEM-resident fused kernel (interpret mode on CPU);
      assumes the whole graph block fits VMEM.
    * ``stream`` — the HBM-streaming kernel: frogs sorted by vertex block,
      per-block CSR slabs DMA'd through VMEM once per superstep, tally by
      sort-compacted segment sum. Needs a :class:`BlockedCSR` — pass
      ``blocked=`` when the graph arrays are traced; otherwise it is built
      (and folded into the trace) from the concrete arrays.
    * ``ref``    — pure-jnp oracle.
    * ``auto``   — ``pallas`` while ``resident_graph_bytes(n, nnz)`` fits
      ``vmem_budget``, else ``stream`` (falling back to ``pallas`` when no
      ``blocked`` layout is available from traced arrays); ``ref`` where
      the chosen kernel does not compile (both, on TPU).

    ``rng="device"`` (compiled TPU only) draws the slot bits in-kernel with
    ``pltpu.prng_random_bits`` seeded from ``seed`` — ``bits`` may then be
    ``None``; ``rng="caller"`` (default) keeps the deterministic
    caller-supplied bits path.

    Handles all padding here so callers pass natural shapes.
    """
    die = die.astype(jnp.int32)
    interpret = resolve_interpret(interpret)
    use_device_rng, seed_arr = _rng_mode(rng, interpret, seed)
    if not use_device_rng:
        bits = jnp.abs(bits).astype(jnp.int32)
    if impl == "auto":
        traced = blocked is None and isinstance(row_ptr, jax.core.Tracer)
        streams = step_streams("auto", n, col_idx.shape[0], vmem_budget)
        impl = "stream" if streams and not traced else "pallas"
        kernel = "frog_step" if impl == "pallas" else "frog_step_stream"
        if not _compiles(kernel, interpret):
            impl = "ref"
    if impl == "ref":
        if use_device_rng:
            raise ValueError('rng="device" has no jnp oracle (impl="ref")')
        return kref.frog_step_ref(pos, die, bits, row_ptr, col_idx, deg, n)
    if impl == "stream":
        _require_compiles("frog_step_stream", interpret)
        if blocked is None:
            if isinstance(row_ptr, jax.core.Tracer):
                raise ValueError(
                    "impl='stream' needs a prebuilt BlockedCSR (blocked=) "
                    "when the graph arrays are traced — the slab width is a "
                    "static shape (see kernels/frog_step_stream.block_csr)")
            blocked = block_csr(row_ptr, col_idx, deg, n,
                                vertex_block=vertex_block)
        return _frog_step_stream(pos, die, bits, blocked, n,
                                 frog_block=frog_block, interpret=interpret,
                                 seed_arr=seed_arr)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    _require_compiles("frog_step", interpret)
    N = pos.shape[0]
    vertex_block = min(vertex_block, max(8, n))
    n_pad = ((n + vertex_block - 1) // vertex_block) * vertex_block
    frog_block = min(frog_block, max(8, N))
    # padded frogs: parked on vertex 0, not dying, slot bits 0 — their next
    # position is discarded by the slice below and they tally nothing.
    pos_p = _pad_to(pos, frog_block)
    die_p = _pad_to(die, frog_block)
    bits_p = seed_arr if use_device_rng else _pad_to(bits, frog_block)
    nxt, counts = _frog_step(
        pos_p, die_p, bits_p, row_ptr, col_idx, deg, n_pad,
        vertex_block=vertex_block, frog_block=frog_block,
        interpret=interpret, use_device_rng=use_device_rng,
    )
    return nxt[:N], counts[:n]


def stitch_step(
    pos: jnp.ndarray,
    stop: jnp.ndarray,
    bits: Optional[jnp.ndarray],
    endpoints: jnp.ndarray,  # int32[n, R] — walk-segment endpoint slab
    n: int,
    impl: str = "pallas",
    interpret: Optional[bool] = None,
    vertex_block: int = 512,
    walk_block: int = 1024,
    rng: str = "caller",
    seed: Optional[int] = None,
    tally: bool = True,
):
    """Fused query stitch round → ``(next_pos[W], stop_counts[n])``.

    One round replaces ``segment_len`` walker supersteps: gather a uniformly
    chosen precomputed segment endpoint per walk and tally the walks whose
    budget ran out. ``pallas`` runs the VMEM-resident fused kernel
    (interpret mode on CPU); ``ref`` is the pure-jnp oracle.
    ``rng="device"`` (compiled TPU only) draws the slot bits in-kernel from
    ``seed`` instead of the caller's ``bits`` stream. Padding is handled
    here so callers pass natural shapes.

    ``tally=False`` runs the gather-only variant and returns
    ``(next_pos[W], None)`` — for callers that defer the histogram to one
    pass over the wave's final positions (the scheduler's fused
    ``lax.scan`` wave, where a per-round counts output would just fatten
    the scan carry to be thrown away). ``next_pos`` is byte-identical to
    the tallying kernel's.
    """
    stop = stop.astype(jnp.int32)
    interpret = resolve_interpret(interpret)
    use_device_rng, seed_arr = _rng_mode(rng, interpret, seed)
    if not use_device_rng:
        bits = jnp.abs(bits).astype(jnp.int32)
    if impl == "ref":
        if use_device_rng:
            raise ValueError('rng="device" has no jnp oracle (impl="ref")')
        if not tally:
            R = endpoints.shape[1]
            return endpoints[pos, bits % R].astype(jnp.int32), None
        return kref.stitch_step_ref(pos, stop, bits, endpoints, n)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    _require_compiles("stitch_step" if tally else "stitch_gather", interpret)
    W = pos.shape[0]
    R = endpoints.shape[1]
    if not tally:
        wb = min(walk_block, max(8, W))
        pos_p = _pad_to(pos, wb)
        bits_p = seed_arr if use_device_rng else _pad_to(bits, wb)
        nxt = _stitch_gather(pos_p, bits_p, endpoints.reshape(-1), R,
                             walk_block=wb, interpret=interpret,
                             use_device_rng=use_device_rng)
        return nxt[:W], None
    vertex_block = min(vertex_block, max(8, n))
    n_pad = ((n + vertex_block - 1) // vertex_block) * vertex_block
    walk_block = min(walk_block, max(8, W))
    # padded walks: parked on vertex 0, not stopping, slot bits 0 — their
    # next position is discarded by the slice below and they tally nothing.
    pos_p = _pad_to(pos, walk_block)
    stop_p = _pad_to(stop, walk_block)
    bits_p = seed_arr if use_device_rng else _pad_to(bits, walk_block)
    nxt, counts = _stitch_step(
        pos_p, stop_p, bits_p, endpoints.reshape(-1), R, n_pad,
        vertex_block=vertex_block, walk_block=walk_block,
        interpret=interpret, use_device_rng=use_device_rng,
    )
    return nxt[:W], counts[:n]


def stitch_step_local(
    pos: jnp.ndarray,
    stop: jnp.ndarray,
    bits: Optional[jnp.ndarray],
    block: jnp.ndarray,      # int32[shard_size, R] — one shard's slab block
    base,                    # int — first global vertex this shard owns
    impl: str = "pallas",
    interpret: Optional[bool] = None,
    vertex_block: int = 512,
    walk_block: int = 1024,
    rng: str = "caller",
    seed: Optional[int] = None,
    tally: bool = True,
):
    """Per-shard stitch round against a local ``[shard_size, R]`` slab block.

    Returns ``(next_contrib[W], stop_counts[shard_size])``: owned walks
    (``pos ∈ [base, base + shard_size)``) gather their next endpoint from
    the local block and are tallied into shard-local bins; all other walks
    contribute 0 — so summing the outputs over shards (``psum`` on a mesh,
    host sum on one device) reproduces :func:`stitch_step` exactly, while
    every device holds only ``4·n·R/S`` bytes of slab.

    ``tally=False`` → ``(next_contrib[W], None)``, the gather-only variant
    (see :func:`stitch_step`): byte-identical contributions, no per-round
    counts — the wave histograms once over its final positions.
    """
    stop = stop.astype(jnp.int32)
    interpret = resolve_interpret(interpret)
    use_device_rng, seed_arr = _rng_mode(rng, interpret, seed)
    if not use_device_rng:
        bits = jnp.abs(bits).astype(jnp.int32)
    base_arr = jnp.asarray(base, jnp.int32).reshape((1,))
    if impl == "ref":
        if use_device_rng:
            raise ValueError('rng="device" has no jnp oracle (impl="ref")')
        if not tally:
            sz, R = block.shape
            local = pos - base_arr[0]
            owned = (local >= 0) & (local < sz)
            li = jnp.clip(local, 0, sz - 1)
            nxt = jnp.where(owned, block[li, bits % R], 0)
            return nxt.astype(jnp.int32), None
        return kref.stitch_step_local_ref(pos, stop, bits, block, base_arr)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    _require_compiles(
        "stitch_step_local" if tally else "stitch_gather_local", interpret)
    W = pos.shape[0]
    sz, R = block.shape
    if not tally:
        wb = min(walk_block, max(8, W))
        pos_p = _pad_to(pos, wb)
        bits_p = seed_arr if use_device_rng else _pad_to(bits, wb)
        nxt = _stitch_gather_local(pos_p, bits_p, base_arr,
                                   block.reshape(-1), R, sz, walk_block=wb,
                                   interpret=interpret,
                                   use_device_rng=use_device_rng)
        return nxt[:W], None
    vertex_block = min(vertex_block, max(8, sz))
    sz_pad = ((sz + vertex_block - 1) // vertex_block) * vertex_block
    walk_block = min(walk_block, max(8, W))
    pos_p = _pad_to(pos, walk_block)
    stop_p = _pad_to(stop, walk_block)
    bits_p = seed_arr if use_device_rng else _pad_to(bits, walk_block)
    nxt, counts = _stitch_step_local(
        pos_p, stop_p, bits_p, base_arr, block.reshape(-1), R, sz, sz_pad,
        vertex_block=vertex_block, walk_block=walk_block,
        interpret=interpret, use_device_rng=use_device_rng,
    )
    return nxt[:W], counts[:sz]


def attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    soft_cap: Optional[float] = None,
    impl: str = "jnp_flash",
    interpret: Optional[bool] = None,
    block_q: int = 128,
    block_k: int = 128,
    chunk: int = 512,
) -> jnp.ndarray:
    """GQA attention, dispatching between three implementations.

    * ``jnp_flash`` — chunked online-softmax in pure jnp (memory-bounded,
      XLA-compilable anywhere). Default: what the models lower in dry-runs.
    * ``pallas``    — the flash TPU kernel (target hardware implementation;
      interpret mode on CPU).
    * ``ref``       — O(S²)-memory oracle, tests only.
    """
    if impl == "ref":
        return kref.attention_ref(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, logit_soft_cap=soft_cap)
    if impl == "jnp_flash":
        return kref.attention_chunked(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset, logit_soft_cap=soft_cap,
                                      chunk=chunk)
    interpret = resolve_interpret(interpret)
    Sq, Skv = q.shape[2], k.shape[2]
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    qp = _pad_to(q, bq, axis=2)
    kp = _pad_to(k, bk, axis=2)
    vp = _pad_to(v, bk, axis=2)
    out = _flash(qp, kp, vp, causal=causal, window=window, q_offset=q_offset,
                 block_q=bq, block_k=bk, soft_cap=soft_cap, interpret=interpret)
    return out[:, :, :Sq]
