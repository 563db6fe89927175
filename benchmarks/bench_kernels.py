"""Kernel micro-benchmarks: interpret-mode wall time (correctness-path cost
on CPU — NOT TPU performance) + allclose deltas vs the jnp oracles."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, save_json, time_call
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rwkv6_wkv import wkv, wkv_ref
from repro.kernels.ssm_scan import ssm_ref, ssm_scan


def run() -> List[Row]:
    rows: List[Row] = []
    rng = jax.random.PRNGKey(0)
    payload = {}

    # flash attention
    B, S, H, D = 1, 256, 4, 64
    q = jax.random.normal(rng, (B, S, H, D))
    t_kernel = time_call(
        lambda: jax.block_until_ready(
            flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)
        )
    )
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    t_ref = time_call(lambda: jax.block_until_ready(attention_ref(fold(q), fold(q), fold(q))))
    out = flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(fold(q), fold(q), fold(q)).reshape(B, H, S, D).transpose(0, 2, 1, 3)
    err = float(jnp.abs(out - ref).max())
    rows.append(Row("kernel/flash_attention/interpret", t_kernel, f"err={err:.1e}"))
    rows.append(Row("kernel/flash_attention/jnp_ref", t_ref, ""))
    payload["flash_attention"] = {"err": err}

    # wkv
    T, Hh, K = 128, 4, 64
    ks = jax.random.split(rng, 5)
    r = jax.random.normal(ks[0], (1, T, Hh, K)) * 0.5
    k = jax.random.normal(ks[1], (1, T, Hh, K)) * 0.5
    v = jax.random.normal(ks[2], (1, T, Hh, K)) * 0.5
    lw = -jnp.exp(jax.random.normal(ks[3], (1, T, Hh, K)))
    u = jax.random.normal(ks[4], (Hh, K)) * 0.2
    t_kernel = time_call(lambda: jax.block_until_ready(wkv(r, k, v, lw, u, interpret=True)[0]))
    rows.append(Row("kernel/rwkv6_wkv/interpret", t_kernel, ""))

    # ssm scan
    Dd, N = 256, 16
    u_in = jax.random.normal(ks[0], (1, T, Dd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, T, Dd)))
    bt = jax.random.normal(ks[2], (1, T, N))
    ct = jax.random.normal(ks[3], (1, T, N))
    la = jax.random.normal(ks[4], (Dd, N)) * 0.5
    t_kernel = time_call(
        lambda: jax.block_until_ready(ssm_scan(u_in, dt, bt, ct, la, d_block=128, interpret=True)[0])
    )
    rows.append(Row("kernel/ssm_scan/interpret", t_kernel, ""))

    save_json("kernels", payload)
    return rows
