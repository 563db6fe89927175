"""Public entry for the WKV kernel: model layout (B, T, H, K) + u (H, K)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_wkv.rwkv6_wkv import wkv_kernel


def wkv(r, k, v, log_w, u, *, chunk: int = 64, interpret: bool = False):
    """r/k/v/log_w: (B, T, H, K); u: (H, K).
    Returns (out (B, T, H, K), final state (B, H, K, K))."""
    b, t, h, kk = r.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, kk)
    uu = jnp.broadcast_to(u[None], (b, h, kk)).reshape(b * h, 1, kk)
    pad = (-t) % chunk
    args = [fold(r), fold(k), fold(v), fold(log_w)]
    if pad:
        args = [jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in args]
    out, s = wkv_kernel(*args, uu, chunk=min(chunk, t + pad), interpret=interpret)
    out = out[:, :t]
    return (
        out.reshape(b, h, t, kk).transpose(0, 2, 1, 3),
        s.reshape(b, h, kk, kk),
    )
