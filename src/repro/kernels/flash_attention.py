"""Pallas TPU kernel: causal GQA flash attention (+ sliding window).

The LM-side compute hot-spot: prefill attention at 32k context is the one
place the assigned architectures are quadratic. Standard online-softmax
blocked attention (Rabe–Staats / FlashAttention), restructured for the MXU:

* grid = (batch, q_heads, q_blocks, kv_blocks), kv innermost (sequential);
* q/out tiles ``(bq, D)`` and kv tiles ``(bk, D)`` sized so bq = bk = 128
  keeps every matmul MXU-shaped (128×D·D×128);
* GQA is expressed in the k/v BlockSpec index maps (q-head h reads kv-head
  h // group) — no repeated KV materialization, which is the point of GQA;
* running max/denominator kept in VMEM scratch across kv blocks;
* causal + sliding-window masks applied from absolute positions; fully-masked
  kv tiles short-circuit (``pl.when``) so the sliding-window case does
  O(S·W) work, not O(S²) — this is what makes gemma3/danube long-context
  prefill sub-quadratic.

Validated against ``ref.attention_ref`` over (B, Hq, Hkv, S, D, window,
causal, dtype) sweeps in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    block_q: int,
    block_k: int,
    soft_cap: Optional[float],
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_offset + iq * block_q + jnp.arange(block_q)          # [bq]
    k_pos = ik * block_k + jnp.arange(block_k)                     # [bk]

    # Tile-level skip: a kv tile is dead if entirely in the causal future or
    # entirely behind the sliding window.
    live = True
    if causal:
        live = (ik * block_k) <= (q_offset + iq * block_q + block_q - 1)
    if window is not None:
        live = live & ((ik * block_k + block_k - 1) > (q_offset + iq * block_q - window))

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale                # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                        # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)                        # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                          # [bq, bk]
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                        # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                            # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)                     # [bq, 1]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "block_q", "block_k", "soft_cap",
        "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,                    # [B, Hq, Sq, D]
    k: jnp.ndarray,                    # [B, Hkv, Skv, D]
    v: jnp.ndarray,                    # [B, Hkv, Skv, D]
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    soft_cap: Optional[float] = None,
    *,
    interpret: bool,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    if Sq % block_q or Skv % block_k:
        raise ValueError(
            f"Sq={Sq}, Skv={Skv} must be multiples of blocks ({block_q},{block_k})"
        )
    group = Hq // Hkv
    grid = (B, Hq, Sq // block_q, Skv // block_k)
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, soft_cap=soft_cap,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
