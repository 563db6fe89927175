"""Public entry for the selective-scan kernel (pads T to chunk multiples)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssm_scan.ssm_scan import ssm_scan_kernel


def ssm_scan(u, dt, b_t, c_t, log_a, *, chunk: int = 64, d_block: int = 512,
             interpret: bool = False):
    bsz, t, d = u.shape
    c = min(chunk, t)
    pad = (-t) % c
    if pad:
        z2 = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        u, dt, b_t, c_t = z2(u), z2(dt), z2(b_t), z2(c_t)
    y, h = ssm_scan_kernel(
        u, dt, b_t, c_t, log_a, chunk=c, d_block=d_block, interpret=interpret
    )
    return y[:, :t], h
