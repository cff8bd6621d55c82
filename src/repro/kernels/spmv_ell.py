"""Pallas TPU kernel: hybrid-ELL sparse matrix–vector product.

This is the compute hot-spot of the GraphLab-PR baseline (power iteration
x ← Qx touches every edge, every iteration) and of the engine's count-vector
superstep. The graph's regular part is stored as an ELL slab
(``idx/weight: [rows, K]``, DESIGN.md §2); power-law hub rows spill to a COO
tail applied by the ops wrapper.

TPU mapping
-----------
* The dense vector ``x`` is pinned **whole in VMEM** (one BlockSpec covering
  the array): PageRank vectors are f32[n]; a 4M-vertex shard is 16 MB — the
  per-shard vertex range is sized so x fits (launch/mesh.py picks shard
  counts accordingly). This is the TPU-native replacement for the GPU
  "texture-cache gather" SpMV: HBM→VMEM once per superstep, then K·rows
  VMEM-random-access gathers, which the VPU does at register speed.
* The slab is processed in ``(ROW_BLOCK, K)`` tiles; K is padded to a
  multiple of 8 (f32 sublane) and ROW_BLOCK to 128 (lanes) so the
  gather+multiply+row-sum vectorizes cleanly.
* Weights encode validity (weight == 0 on padded lanes), so no mask tile.

Validated in interpret mode against ``ref.spmv_ref`` (tests/test_kernels.py
sweeps rows, K, dtypes, degree skews).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_ROW_BLOCK = 128


def _spmv_kernel(x_ref, idx_ref, w_ref, y_ref):
    """One (ROW_BLOCK, K) tile: y = Σ_k w[:, k] · x[idx[:, k]]."""
    x = x_ref[...]                                    # [n_pad] — whole vector in VMEM
    idx = idx_ref[...]                                # [BR, K]
    w = w_ref[...]                                    # [BR, K]
    gathered = jnp.take(x, idx.reshape(-1), axis=0).reshape(idx.shape)
    y_ref[...] = (gathered.astype(jnp.float32) * w.astype(jnp.float32)).sum(
        axis=1
    ).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def spmv_ell_slab(
    idx: jnp.ndarray,        # int32[rows, K]
    weight: jnp.ndarray,     # f32[rows, K]
    x: jnp.ndarray,          # f32[n_pad]
    row_block: int = DEFAULT_ROW_BLOCK,
    *,
    interpret: bool,
) -> jnp.ndarray:
    rows, K = idx.shape
    if rows % row_block != 0:
        raise ValueError(f"rows={rows} must be a multiple of row_block={row_block}")
    grid = (rows // row_block,)
    return pl.pallas_call(
        _spmv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(x.shape, lambda i: (0,)),               # x: whole vector
            pl.BlockSpec((row_block, K), lambda i: (i, 0)),      # idx tile
            pl.BlockSpec((row_block, K), lambda i: (i, 0)),      # weight tile
        ],
        out_specs=pl.BlockSpec((row_block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((rows,), x.dtype),
        interpret=interpret,
    )(x, idx, weight)
