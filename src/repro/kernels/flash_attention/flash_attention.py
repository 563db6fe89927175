"""Pallas TPU flash attention (blockwise online softmax).

TPU-native tiling: the grid is (batch*heads, q_blocks, kv_blocks) with the
kv dimension innermost; the running (max, denom, accumulator) state lives in
VMEM scratch and is carried across kv iterations of the same q block (the
standard Pallas "revisiting" pattern).  Block sizes default to 128x128 —
MXU-aligned (the 128x128 systolic array) — and the full head_dim rides in
the minor-most dim so every dot hits the MXU without re-tiling.

Masking supports causal and sliding-window; masked-out blocks are computed
-but-masked (the grid is static).  `ops.py` skips fully-masked kv blocks by
clamping the kv grid when the window makes them dead.

VMEM footprint per program (defaults, D=128, f32 scratch):
  q (128x128 bf16) + k,v (128x128) + acc/m/l (128x128 + 2x128 f32) ~ 200 KiB
— comfortably inside the ~16 MiB/core VMEM budget, leaving room for
double-buffered pipelining.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, block_q: int, block_k: int, n_kv_blocks: int,
    causal: bool, window: Optional[int], softmax_scale: float, kv_len: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * softmax_scale     # (bq, D)
    k = k_ref[0].astype(jnp.float32)                     # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                    # (bq, bk)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    scores = jnp.where(mask, scores, NEG_INF)

    # Row statistics stay 2-D (bq, 1): Mosaic cannot reshape a 1-D vector
    # (least of all a bool one) into a column.
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    # A row with no valid key yet has m_prev == m_new == NEG_INF, so
    # alpha = exp(0) = 1 and the masked p below keeps it at zero.
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
    l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ik == n_kv_blocks - 1)
    def finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,  # (BH, S, D)
    k: jax.Array,  # (BH, T, D)
    v: jax.Array,  # (BH, T, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    kv_len: Optional[int] = None,
) -> jax.Array:
    bh, s, d = q.shape
    t = k.shape[1]
    if s % block_q or t % block_k:
        raise ValueError(f"S={s} / T={t} must be multiples of the block sizes")
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    n_kv_blocks = t // block_k

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_kv_blocks,
        causal=causal,
        window=window,
        softmax_scale=scale,
        kv_len=t if kv_len is None else kv_len,  # mask out padded keys
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, s // block_q, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32), # accumulator
        ],
        interpret=interpret,
    )(q, k, v)
