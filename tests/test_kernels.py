"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs the pure-jnp
oracles (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rwkv6_wkv import wkv, wkv_ref
from repro.kernels.ssm_scan import ssm_ref, ssm_scan

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=0.05, atol=0.05)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,t,h,kv,d,causal,window",
    [
        (2, 128, 128, 4, 4, 64, True, None),
        (1, 256, 256, 4, 2, 32, True, None),
        (2, 100, 100, 2, 2, 64, True, None),    # non-block-multiple (padding)
        (1, 256, 256, 4, 4, 64, True, 64),      # sliding window
        (2, 64, 192, 2, 2, 32, False, None),    # cross-attention lengths
        (1, 128, 128, 8, 2, 128, True, None),   # GQA rep 4, MXU-width head
    ],
)
def test_flash_attention_sweep(b, s, t, h, kv, d, causal, window, dtype):
    rng = jax.random.PRNGKey(42)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, t, kv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, block_q=64, block_k=64,
                          interpret=True)
    rep = h // kv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    ref = attention_ref(fold(q), fold(kr), fold(vr), causal=causal, window=window)
    ref = ref.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,t,h,k,chunk",
    [(2, 128, 4, 64, 32), (1, 96, 2, 32, 32), (2, 64, 4, 64, 16), (1, 40, 2, 64, 32)],
)
def test_wkv_sweep(b, t, h, k, chunk, dtype):
    rng = jax.random.PRNGKey(7)
    ks = jax.random.split(rng, 5)
    r = (jax.random.normal(ks[0], (b, t, h, k)) * 0.5).astype(dtype)
    kk = (jax.random.normal(ks[1], (b, t, h, k)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (b, t, h, k)) * 0.5).astype(dtype)
    lw = (-jnp.exp(jax.random.normal(ks[3], (b, t, h, k)))).astype(jnp.float32)
    u = jax.random.normal(ks[4], (h, k), jnp.float32) * 0.2
    out, s = wkv(r, kk, v, lw, u, chunk=chunk, interpret=True)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])
    uu = jnp.broadcast_to(u[None], (b, h, k)).reshape(b * h, k)
    oref, sref = wkv_ref(
        fold(r).astype(jnp.float32),
        fold(kk).astype(jnp.float32),
        fold(v).astype(jnp.float32),
        fold(lw),
        uu,
    )
    oref = oref.reshape(b, h, t, k).transpose(0, 2, 1, 3)
    sref = sref.reshape(b, h, k, k)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(oref, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(sref), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,t,d,n,chunk,d_block",
    [(2, 64, 128, 16, 32, 64), (1, 100, 64, 8, 32, 32), (2, 128, 256, 16, 64, 128)],
)
def test_ssm_scan_sweep(b, t, d, n, chunk, d_block, dtype):
    rng = jax.random.PRNGKey(3)
    ks = jax.random.split(rng, 5)
    u = jax.random.normal(ks[0], (b, t, d), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, d))).astype(dtype)
    bt = jax.random.normal(ks[2], (b, t, n), dtype)
    ct = jax.random.normal(ks[3], (b, t, n), dtype)
    la = (jax.random.normal(ks[4], (d, n)) * 0.5).astype(jnp.float32)
    y, h = ssm_scan(u, dt, bt, ct, la, chunk=chunk, d_block=d_block,
                    interpret=True)
    yr, hr = ssm_ref(
        u.astype(jnp.float32), dt.astype(jnp.float32),
        bt.astype(jnp.float32), ct.astype(jnp.float32), la,
    )
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-2, atol=2e-2)


def test_kernels_jit_compatible():
    """ops.py wrappers must be jittable (the production path)."""
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (1, 128, 2, 64))
    out = jax.jit(lambda q: flash_attention(q, q, q, interpret=True))(q)
    assert out.shape == q.shape


def test_kernel_model_paths_match_jnp():
    """use_kernel=True routes RWKV6/Hymba through the Pallas kernels
    (interpreted, since the backend here is the CPU); the model logits must
    match the jnp path (first-class kernel integration)."""
    import dataclasses

    from repro.configs import get_api

    for arch in ("rwkv6-7b", "hymba-1.5b"):
        api = get_api(arch, reduced=True)
        api_k = dataclasses.replace(
            api, cfg=dataclasses.replace(api.cfg, use_kernel=True)
        )
        params = api.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, api.cfg.vocab)
        base = api.logits(params, {"tokens": toks})
        kern = api_k.logits(params, {"tokens": toks})
        scale = max(float(jnp.abs(base).max()), 1.0)
        assert float(jnp.abs(base - kern).max()) / scale < 0.05
