"""Hymba (arXiv:2411.13676) — hybrid-head architecture: every layer runs
attention heads and Mamba (selective-SSM) heads *in parallel* on the same
input, then fuses the two normalized branch outputs.

Assigned config: 32L, d_model 1600, 25 attention heads (head_dim 64, GQA
kv=5), d_ff 5504, ssm_state 16, vocab 32001.

* Attention: sliding-window in all layers except {first, middle, last}
  (global layers), per the source paper.
* Mamba branch: in-proj to (x, z) of d_inner = 2*d_model, short causal
  depthwise conv, selective scan over state dim 16 (chunked
  associative-scan so full sequences never materialize (B,T,d_inner,16)),
  silu(z) gating, out-proj.
* Fusion: mean of per-branch RMS-normalized outputs (learnable scales).
* Meta tokens (learnable prefix) are supported for full-sequence forward
  (``n_meta_tokens``); the assigned config keeps 0 so train/decode shapes
  stay uniform — noted in DESIGN.md.

Decode state: ring KV cache (window) for SWA layers + full cache for global
layers (we allocate full length only when seq fits, else window; global
layers fall back to window in long_500k — noted), SSM state (d_inner, 16),
conv tail, O(1) per token.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import Param
from repro.sharding.context import constrain

__all__ = [
    "HymbaConfig",
    "schema",
    "init",
    "forward",
    "init_cache",
    "decode_step",
    "selective_scan",
    "selective_scan_ref",
]


@dataclasses.dataclass(frozen=True)
class HymbaConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 64
    ssm_state: int = 16
    d_inner: Optional[int] = None      # default 2*d_model
    conv_kernel: int = 4
    dt_rank: Optional[int] = None      # default ceil(d_model/16)
    window: int = 1024
    rope_theta: float = 10000.0
    n_meta_tokens: int = 0
    ssm_chunk: int = 64
    use_kernel: bool = False   # route the selective scan through Pallas
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    kv_chunk: int = 2048

    @property
    def family(self) -> str:
        return "hybrid"

    @property
    def inner(self) -> int:
        return self.d_inner or 2 * self.d_model

    @property
    def dtr(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    def global_layers(self) -> Tuple[int, ...]:
        return (0, self.n_layers // 2, self.n_layers - 1)


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def layer_schema(cfg: HymbaConfig) -> Dict[str, Any]:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    di, n, dtr = cfg.inner, cfg.ssm_state, cfg.dtr
    return {
        "attn": {
            "wq": Param((d, h, dh), ("embed", "heads", None)),
            "wk": Param((d, kv, dh), ("embed", "kv_heads", None)),
            "wv": Param((d, kv, dh), ("embed", "kv_heads", None)),
            "wo": Param((h, dh, d), ("heads", None, "embed")),
        },
        "ssm": {
            "w_in": Param((d, 2 * di), ("embed", "ssm_inner")),
            "conv_w": Param((cfg.conv_kernel, di), (None, "ssm_inner")),
            "conv_b": Param((di,), ("ssm_inner",), init="zeros"),
            "w_dt_in": Param((di, dtr), ("ssm_inner", None)),
            "w_dt_out": Param((dtr, di), (None, "ssm_inner")),
            "dt_bias": Param((di,), ("ssm_inner",), init="zeros"),
            "w_bc": Param((di, 2 * n), ("ssm_inner", None)),
            "log_a": Param((di, n), ("ssm_inner", None), init="zeros"),
            "d_skip": Param((di,), ("ssm_inner",), init="ones"),
            "w_out": Param((di, d), ("ssm_inner", "embed")),
        },
        "attn_scale": Param((d,), (None,), init="ones"),
        "ssm_scale": Param((d,), (None,), init="ones"),
        "in_norm": Param((d,), (None,), init="ones"),
        "mlp_norm": Param((d,), (None,), init="ones"),
        "mlp": {
            "w_gate": Param((d, cfg.d_ff), ("embed", "ff")),
            "w_up": Param((d, cfg.d_ff), ("embed", "ff")),
            "w_down": Param((cfg.d_ff, d), ("ff", "embed")),
        },
    }


def schema(cfg: HymbaConfig) -> Dict[str, Any]:
    s: Dict[str, Any] = {
        "embed": Param((cfg.vocab, cfg.d_model), ("vocab", None), init="embed"),
        "layers": common.stacked(layer_schema(cfg), cfg.n_layers),
        "final_norm": Param((cfg.d_model,), (None,), init="ones"),
        "lm_head": Param((cfg.d_model, cfg.vocab), ("embed", "vocab")),
    }
    if cfg.n_meta_tokens:
        s["meta_tokens"] = Param(
            (cfg.n_meta_tokens, cfg.d_model), (None, None), init="embed"
        )
    return s


def init(rng: jax.Array, cfg: HymbaConfig):
    return common.init_from_schema(rng, schema(cfg), cfg.param_dtype)


# ---------------------------------------------------------------------------
# Selective scan (Mamba-style SSM)
# ---------------------------------------------------------------------------


def selective_scan_ref(
    u: jax.Array, dt: jax.Array, log_a: jax.Array, b_t: jax.Array, c_t: jax.Array,
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Step-by-step oracle.  u/dt: (B,T,di); log_a: (di,n); b_t/c_t: (B,T,n).
    h_t = exp(dt_t*A) h_{t-1} + dt_t * B_t * u_t;  y_t = C_t . h_t.
    Returns (y (B,T,di), h_final (B,di,n))."""
    bsz, t, di = u.shape
    n = b_t.shape[-1]
    a = -jnp.exp(log_a.astype(jnp.float32))  # A < 0
    h = jnp.zeros((bsz, di, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        u_, dt_, b_, c_ = inp
        decay = jnp.exp(dt_.astype(jnp.float32)[..., None] * a[None])
        h = decay * h + (dt_ * u_).astype(jnp.float32)[..., None] * b_[:, None, :].astype(jnp.float32)
        y = jnp.einsum("bdn,bn->bd", h, c_.astype(jnp.float32))
        return h, y

    xs = (
        u.transpose(1, 0, 2), dt.transpose(1, 0, 2),
        b_t.transpose(1, 0, 2), c_t.transpose(1, 0, 2),
    )
    h, ys = jax.lax.scan(step, h, xs)
    return ys.transpose(1, 0, 2).astype(u.dtype), h


def selective_scan(
    u: jax.Array, dt: jax.Array, log_a: jax.Array, b_t: jax.Array, c_t: jax.Array,
    *, chunk: int = 64, h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked selective scan: outer lax.scan over chunks carrying (B,di,n)
    state; inner associative_scan within each chunk, so the (B,T,di,n)
    tensors exist only chunk-sized."""
    bsz, t, di = u.shape
    n = b_t.shape[-1]
    c = min(chunk, t)
    pad = (-t) % c
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_t = jnp.pad(b_t, ((0, 0), (0, pad), (0, 0)))
        c_t = jnp.pad(c_t, ((0, 0), (0, pad), (0, 0)))
    tp = t + pad
    nc = tp // c
    a = -jnp.exp(log_a.astype(jnp.float32))

    resh = lambda x: x.reshape(bsz, nc, c, x.shape[-1]).transpose(1, 0, 2, 3)
    uc, dtc, bc, cc = resh(u), resh(dt), resh(b_t), resh(c_t)

    h_init = jnp.zeros((bsz, di, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def chunk_body(h, inp):
        u_b, dt_b, b_b, c_b = inp  # (B,C,di)/(B,C,n)
        dtf = dt_b.astype(jnp.float32)
        decay = jnp.exp(dtf[..., None] * a[None, None])            # (B,C,di,n)
        inject = (dtf * u_b.astype(jnp.float32))[..., None] * b_b.astype(jnp.float32)[:, :, None, :]

        def combine(x, y):
            a1, b1 = x
            a2, b2 = y
            return a1 * a2, b1 * a2 + b2

        acc_a, acc_b = jax.lax.associative_scan(combine, (decay, inject), axis=1)
        h_t = acc_a * h[:, None] + acc_b                            # (B,C,di,n)
        y = jnp.einsum("bcdn,bcn->bcd", h_t, c_b.astype(jnp.float32))
        return h_t[:, -1], y

    body = jax.checkpoint(chunk_body)
    h_final, ys = jax.lax.scan(body, h_init, (uc, dtc, bc, cc))
    y = ys.transpose(1, 0, 2, 3).reshape(bsz, tp, di)[:, :t]
    return y.astype(u.dtype), h_final


# ---------------------------------------------------------------------------
# Branches
# ---------------------------------------------------------------------------


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array, tail: Optional[jax.Array] = None):
    """Depthwise causal conv.  x (B,T,di); w (K,di).  ``tail`` (B,K-1,di)
    supplies left context for decode; returns (y, new_tail)."""
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    segs = [
        jax.lax.dynamic_slice_in_dim(xp, i, x.shape[1], axis=1) * w[i][None, None]
        for i in range(k)
    ]
    y = sum(segs) + b[None, None]
    new_tail = xp[:, -(k - 1) :] if k > 1 else tail
    return y, new_tail


def ssm_d_block(d_inner: int) -> int:
    """Channel block for the selective-scan kernel: the largest multiple of
    128 lanes that divides ``d_inner`` and is at most 1024 (640 for Hymba's
    3200), which keeps a program's VMEM near 2 MiB; the whole of
    ``d_inner`` when no such block exists."""
    for blk in range(min(1024, d_inner) // 128 * 128, 0, -128):
        if d_inner % blk == 0:
            return blk
    return d_inner


def _ssm_branch(
    sp: Dict[str, Any],
    x: jax.Array,
    cfg: HymbaConfig,
    *,
    h0: Optional[jax.Array] = None,
    conv_tail: Optional[jax.Array] = None,
    single_step: bool = False,
):
    di, n = cfg.inner, cfg.ssm_state
    xz = jnp.einsum("btd,de->bte", x, sp["w_in"])
    u, z = xz[..., :di], xz[..., di:]
    u = constrain(u, ("batch", None, "ssm_inner"))
    z = constrain(z, ("batch", None, "ssm_inner"))
    u, new_tail = _causal_conv(u, sp["conv_w"], sp["conv_b"], conv_tail)
    u = jax.nn.silu(u)
    dt = jnp.einsum("btd,dr->btr", u, sp["w_dt_in"])
    dt = jax.nn.softplus(jnp.einsum("btr,rd->btd", dt, sp["w_dt_out"]) + sp["dt_bias"][None, None])
    bc = jnp.einsum("btd,dn->btn", u, sp["w_bc"])
    b_t, c_t = bc[..., :n], bc[..., n:]
    if single_step:
        y, h = selective_scan_ref(u, dt, sp["log_a"], b_t, c_t, h0=h0)
    elif cfg.use_kernel and h0 is None:
        from repro.kernels.ssm_scan import ssm_scan as ssm_kernel_op

        y, h = ssm_kernel_op(
            u.astype(jnp.float32), dt.astype(jnp.float32),
            b_t.astype(jnp.float32), c_t.astype(jnp.float32),
            sp["log_a"].astype(jnp.float32),
            chunk=cfg.ssm_chunk, d_block=ssm_d_block(di),
            interpret=jax.default_backend() == "cpu",
        )
        y = y.astype(cfg.compute_dtype)
    else:
        y, h = selective_scan(u, dt, sp["log_a"], b_t, c_t, chunk=cfg.ssm_chunk, h0=h0)
    y = y + sp["d_skip"][None, None] * u
    y = y * jax.nn.silu(z)
    return jnp.einsum("bte,ed->btd", y, sp["w_out"]), h, new_tail


def _attn_branch(
    ap: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    cfg: HymbaConfig,
    *,
    is_global: bool,
):
    q = jnp.einsum("bsd,dhk->bshk", x, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, ap["wv"])
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    if is_global:
        attn = common.full_attention(q, k, v, causal=True, kv_chunk=cfg.kv_chunk)
    else:
        attn = common.local_window_attention(q, k, v, window=cfg.window)
    return jnp.einsum("bshk,hkd->bsd", attn, ap["wo"])


def _fuse(lp: Dict[str, Any], attn_out: jax.Array, ssm_out: jax.Array) -> jax.Array:
    return 0.5 * (
        common.rms_norm(attn_out, lp["attn_scale"])
        + common.rms_norm(ssm_out, lp["ssm_scale"])
    )


def _mlp(lp, x):
    g = jnp.einsum("bsd,df->bsf", x, lp["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, lp["w_up"])
    return jnp.einsum("bsf,fd->bsd", common.swiglu(g, u), lp["w_down"])


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def forward(params: Dict[str, Any], cfg: HymbaConfig, tokens: jax.Array) -> jax.Array:
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.n_meta_tokens:
        meta = jnp.broadcast_to(
            params["meta_tokens"][None].astype(cfg.compute_dtype),
            (b, cfg.n_meta_tokens, cfg.d_model),
        )
        x = jnp.concatenate([meta, x], axis=1)
    s_tot = x.shape[1]
    positions = jnp.arange(s_tot)
    glob = jnp.zeros((cfg.n_layers,), bool).at[jnp.array(cfg.global_layers())].set(True)

    def body(x, layer):
        lp, is_global = layer
        h = common.rms_norm(x, lp["in_norm"])
        # Both window paths are lowered and selected at trace time via cond
        # on the per-layer flag (static shapes identical).
        attn_out = jax.lax.cond(
            is_global,
            lambda h: _attn_branch(lp["attn"], h, positions, cfg, is_global=True),
            lambda h: _attn_branch(lp["attn"], h, positions, cfg, is_global=False),
            h,
        )
        ssm_out, _, _ = _ssm_branch(lp["ssm"], h, cfg)
        x = x + _fuse(lp, attn_out, ssm_out)
        h = common.rms_norm(x, lp["mlp_norm"])
        x = x + _mlp(lp["mlp"], h)
        return x, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, (params["layers"], glob))
    if cfg.n_meta_tokens:
        x = x[:, cfg.n_meta_tokens :]
    x = common.rms_norm(x, params["final_norm"])
    return jnp.einsum(
        "btd,dv->btv", x, params["lm_head"].astype(cfg.compute_dtype)
    ).astype(jnp.float32)


def init_cache(cfg: HymbaConfig, batch: int, seq_len: int, dtype=None):
    """Ring KV cache of window size for every layer (global layers fall back
    to windowed context in decode — recorded in DESIGN.md), plus SSM state
    and conv tail."""
    if dtype is None:
        dtype = cfg.compute_dtype  # cache dtype must match decode K/V
    length = min(cfg.window, seq_len)
    kv = common.make_kv_cache(
        cfg.n_layers, batch, length, cfg.n_kv_heads, cfg.head_dim, dtype
    )
    return {
        "k": kv["k"],
        "v": kv["v"],
        "ssm": jnp.zeros((cfg.n_layers, batch, cfg.inner, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.conv_kernel - 1, cfg.inner), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(
    params: Dict[str, Any],
    cfg: HymbaConfig,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,
    pos: jax.Array,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    positions = jnp.full((1,), pos, jnp.int32)
    length = cache["k"].shape[2]

    def body(x, layer):
        lp, k_c, v_c, h_ssm, conv_tail = layer
        h = common.rms_norm(x, lp["in_norm"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"])
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
        k_c, v_c = common.cache_update(k_c, v_c, k, v, pos % length)
        attn = common.decode_attention(q, k_c, v_c, pos=pos, window=None)
        attn_out = jnp.einsum("bshk,hkd->bsd", attn, lp["attn"]["wo"])
        ssm_out, h_new, tail = _ssm_branch(
            lp["ssm"], h, cfg, h0=h_ssm, conv_tail=conv_tail, single_step=True
        )
        x = x + _fuse(lp, attn_out, ssm_out)
        h = common.rms_norm(x, lp["mlp_norm"])
        x = x + _mlp(lp["mlp"], h)
        return x, (k_c, v_c, h_new, tail)

    x, (k_c, v_c, ssm, conv) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], cache["ssm"], cache["conv"])
    )
    x = common.rms_norm(x, params["final_norm"])
    logits = jnp.einsum(
        "btd,dv->btv", x, params["lm_head"].astype(cfg.compute_dtype)
    ).astype(jnp.float32)
    return logits, {"k": k_c, "v": v_c, "ssm": ssm, "conv": conv, "pos": pos + 1}
