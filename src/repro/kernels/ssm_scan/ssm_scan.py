"""Pallas TPU kernel for the Mamba-style selective scan (Hymba's SSM heads).

Recurrence per channel d and state n (d_state = 16):
    h_t[d, n] = exp(dt_t[d] * A[d, n]) * h_{t-1}[d, n] + dt_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n C_t[n] h_t[d, n]

TPU adaptation (vs. the CUDA selective-scan kernel, which maps channels to
threads and relies on warp shuffles): the grid is
(batch, d_inner blocks, time chunks) with time innermost; the state is VMEM
scratch carried across chunk iterations, held as (n, d_block) so channels
ride the 128-wide lanes and the 16 states the sublanes.  Within a chunk a
`fori_loop` advances the recurrence one time step at a time on the whole
(n, d_block) tile: the step's u/dt rows are read from the input refs and
its y row written straight into the output ref with `pl.ds` (Mosaic has no
dynamic_slice / dynamic_update_slice on values).  B and C arrive transposed,
(n, chunk), so the step's state column is a lane-masked reduction rather
than a transpose.  A d_block of 640 channels x 16 states x 4 B = 40 KiB of
state per program.

VMEM per program (C=128, d_block=640, n=16), double-buffered:
  u/dt/y (128x640x4) x 2 x 3 + b/c (16x128x4) x 2 x 2 + state ~ 2 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, bT_ref, cT_ref, logaT_ref, y_ref, h_final_ref, h_ref,
                *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = -jnp.exp(logaT_ref[...].astype(jnp.float32))      # (N, D)
    b_all = bT_ref[0].astype(jnp.float32)                 # (N, C)
    c_all = cT_ref[0].astype(jnp.float32)                 # (N, C)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, b_all.shape, 1)

    def step(t, h):
        u = u_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # (1, D)
        dt = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, D)
        at_t = lane_t == t
        b = jnp.sum(jnp.where(at_t, b_all, 0.0), axis=1, keepdims=True)  # (N, 1)
        c = jnp.sum(jnp.where(at_t, c_all, 0.0), axis=1, keepdims=True)  # (N, 1)
        h = jnp.exp(dt * a) * h + b * (dt * u)            # (N, D)
        y = jnp.sum(c * h, axis=0, keepdims=True)         # (1, D)
        y_ref[0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])

    @pl.when(ic == n_chunks - 1)
    def finalize():
        h_final_ref[0] = h_ref[...].astype(h_final_ref.dtype)


def ssm_scan_kernel(
    u: jax.Array,      # (B, T, D)
    dt: jax.Array,     # (B, T, D)
    b_t: jax.Array,    # (B, T, N)
    c_t: jax.Array,    # (B, T, N)
    log_a: jax.Array,  # (D, N)
    *,
    chunk: int = 64,
    d_block: int = 512,
    interpret: bool = False,
):
    """Returns (y (B, T, D), h_final (B, D, N))."""
    bsz, t, d = u.shape
    n = b_t.shape[-1]
    if t % chunk:
        raise ValueError(f"T={t} must be a multiple of chunk={chunk}")
    d_block = min(d_block, d)
    if d % d_block:
        raise ValueError(f"D={d} must be a multiple of d_block={d_block}")
    n_chunks = t // chunk
    kernel = functools.partial(_ssm_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_t = pl.pallas_call(
        kernel,
        grid=(bsz, d // d_block, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, chunk, d_block), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, n, chunk), lambda ib, id_, ic: (ib, 0, ic)),
            pl.BlockSpec((1, n, chunk), lambda ib, id_, ic: (ib, 0, ic)),
            pl.BlockSpec((n, d_block), lambda ib, id_, ic: (0, id_)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda ib, id_, ic: (ib, ic, id_)),
            pl.BlockSpec((1, n, d_block), lambda ib, id_, ic: (ib, 0, id_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, d), u.dtype),
            jax.ShapeDtypeStruct((bsz, n, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, d_block), jnp.float32)],
        interpret=interpret,
    )(u, dt, b_t.transpose(0, 2, 1), c_t.transpose(0, 2, 1), log_a.T)
    return y, h_t.transpose(0, 2, 1)
