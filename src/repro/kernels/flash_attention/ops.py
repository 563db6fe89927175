"""Public entry for the flash-attention kernel.

Accepts model-layout tensors (B, S, H, D) with GQA kv heads, folds
batch*heads, pads sequences to block multiples, and dispatches to the
Pallas kernel.  ``interpret=True`` runs the same kernel body in the Pallas
interpreter (the CPU backend); the default compiles it for the TPU.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention_kernel,
)


def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, KV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    if h % kv:
        raise ValueError("q heads must be a multiple of kv heads")
    rep = h // kv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = fold(q), fold(k), fold(v)

    block_q_eff = min(block_q, s) if s < block_q else block_q
    block_k_eff = min(block_k, t) if t < block_k else block_k
    pad_q = (-s) % block_q_eff
    pad_k = (-t) % block_k_eff
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))

    out = flash_attention_kernel(
        qf,
        kf,
        vf,
        causal=causal,
        window=window,
        softmax_scale=softmax_scale,
        block_q=block_q_eff,
        block_k=block_k_eff,
        interpret=interpret,
        kv_len=t,
    )
    out = out[:, :s]
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
