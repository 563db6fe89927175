"""Pallas TPU kernel for the RWKV6 WKV recurrence (chunked-parallel form).

Recurrence per head (state S in R^{K x K}, data-dependent decay w_t):
    out_t = r_t . (diag(u) k_t^T v_t + S_{t-1})
    S_t   = diag(w_t) S_{t-1} + k_t^T v_t

TPU adaptation (vs. the CUDA kernel of the RWKV authors, which assigns one
thread per channel and steps sequentially): we use the chunked-parallel
formulation — within a chunk of C tokens the recurrence collapses into two
MXU matmuls on decay-scaled r/k plus a (C x C) masked score matrix, and only
the (K x K) state crosses chunk boundaries.  The grid is (B*H, n_chunks)
with the chunk dim innermost ("revisiting" pattern: the state scratch lives
in VMEM across chunk iterations).  C defaults to 64 and K = 64, so every
matmul is (64 x 64) x (64 x 64) — half-MXU tiles; K=128 heads would fill it.

Log-decays are clamped to [LOG_DECAY_MIN, 0] like the jnp reference
(models/rwkv6.py): the scaled-GEMM form computes k .* exp(-L) which would
overflow for unbounded decay.

VMEM per program: r/k/v/lw chunks 4x(64x64x4B) + state (64x64x4B) + score
(64x64) ~ 120 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

LOG_DECAY_MIN = -4.6  # matches models/rwkv6.py (see stability note there)


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_final_ref, state_ref,
                *, chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # (C, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = jnp.clip(lw_ref[0].astype(jnp.float32), LOG_DECAY_MIN, 0.0)
    u = u_ref[0].astype(jnp.float32)          # (1, K) block of (BH, 1, K)

    # L_t inclusive, as a lower-triangular matmul: Mosaic has no cumsum.
    # HIGHEST keeps the f32 log-decays exact through the MXU passes.
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    l_inc = jax.lax.dot_general(
        jnp.where(s_idx <= t_idx, 1.0, 0.0), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    l_prev = l_inc - lw                       # L_{t-1}
    l_end = l_inc[-1:, :]                     # (1, K)

    # Mid-point-normalized factored form (see models/rwkv6.py): bounds both
    # GEMM factors by exp(chunk*|LOG_DECAY_MIN|/2) — float32-safe.
    l_mid = 0.5 * l_end
    rr = r * jnp.exp(l_prev - l_mid)          # (C, K)
    kk = k * jnp.exp(l_mid - l_inc)
    scores = jax.lax.dot_general(
        rr, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                         # (C, C): scores[t, s]
    scores = jnp.where(s_idx < t_idx, scores, 0.0)  # strictly lower triangular

    diag = jnp.sum(r * u * k, axis=1)         # bonus term: r_t . (u . k_t)
    out = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    out = out + diag[:, None] * v
    out = out + jax.lax.dot_general(
        rr * jnp.exp(l_mid), state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = out.astype(o_ref.dtype)

    k_dec = k * jnp.exp(l_end - l_inc)        # (C, K)
    state_ref[...] = jnp.exp(l_end[0])[:, None] * state_ref[...] + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ic == n_chunks - 1)
    def finalize():
        s_final_ref[0] = state_ref[...].astype(s_final_ref.dtype)


def wkv_kernel(
    r: jax.Array,   # (BH, T, K)
    k: jax.Array,
    v: jax.Array,
    log_w: jax.Array,
    u: jax.Array,   # (BH, 1, K) per-head bonus (broadcast over batch upstream)
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """Returns (out (BH, T, K), final state (BH, K, K))."""
    bh, t, kk = r.shape
    if t % chunk:
        raise ValueError(f"T={t} must be a multiple of chunk={chunk}")
    n_chunks = t // chunk
    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            # (1, 1, K): the last two dims equal the array's, which the
            # (8, 128) tiling rule accepts for any K.
            pl.BlockSpec((1, 1, kk), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, kk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, kk, kk), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, kk), r.dtype),
            jax.ShapeDtypeStruct((bh, kk, kk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kk, kk), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u)
