"""RWKV-6 "Finch" (arXiv:2404.05892) — attention-free RNN with
data-dependent per-channel decay.

Per layer:
  * time mixing — r/k/v/g projections of token-shift-lerped inputs; the WKV
    recurrence per head (state S in R^{dh x dh}):
        out_t = r_t . (diag(u) k_t^T v_t + S_{t-1})
        S_t   = diag(w_t) S_{t-1} + k_t^T v_t
    with decay w_t = exp(-exp(w0 + lora(x_t))) (data-dependent, the RWKV6
    novelty) and per-head bonus u.
  * channel mixing — token-shifted squared-ReLU MLP with sigmoid receptance.

Training uses the *chunked-parallel* WKV form (flash-linear-attention style):
within a chunk of C tokens the recurrence becomes two matmuls with
cumulative-decay-scaled r/k, and only one (dh x dh) state is carried between
chunks — this is the formulation the Pallas kernel (kernels/rwkv6_wkv)
implements on TPU; here it runs in plain jnp so the dry-run lowers it.
A step-by-step `wkv_scan_ref` is kept as the correctness oracle.

Decode carries (state S, shift token) per layer — O(1) per token, which is
why this arch runs the long_500k shape natively.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import Param
from repro.sharding.context import constrain

__all__ = [
    "RWKV6Config",
    "schema",
    "init",
    "forward",
    "init_cache",
    "decode_step",
    "wkv_chunked",
    "wkv_scan_ref",
]

# Per-step log-decay floor.  exp(-4.6) ~ 0.01/step: a channel at the floor
# forgets 4 orders of magnitude in two steps, so the truncation is ~1e-4
# relative.  The floor bounds the factored chunk form's exponent range to
# chunk*4.6/2 = 73.6 (chunk 32) after mid-point normalization — inside
# float32 (exp(73.6) ~ 1e32 << 3.4e38).
LOG_DECAY_MIN = -4.6


@dataclasses.dataclass(frozen=True)
class RWKV6Config:
    name: str
    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    head_size: int = 64
    decay_lora: int = 64
    wkv_chunk: int = 32
    use_kernel: bool = False   # route WKV through the Pallas kernel
                               # (interpreted on the CPU backend only)
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def family(self) -> str:
        return "ssm"

    @property
    def n_heads(self) -> int:
        return self.d_model // self.head_size


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def layer_schema(cfg: RWKV6Config) -> Dict[str, Any]:
    d, h, k = cfg.d_model, cfg.n_heads, cfg.head_size
    return {
        "time": {
            "mu_r": Param((d,), (None,), init="zeros"),
            "mu_k": Param((d,), (None,), init="zeros"),
            "mu_v": Param((d,), (None,), init="zeros"),
            "mu_w": Param((d,), (None,), init="zeros"),
            "mu_g": Param((d,), (None,), init="zeros"),
            "w0": Param((h, k), ("heads", None), init="zeros"),
            "w_lora_a": Param((d, cfg.decay_lora), ("embed", None)),
            "w_lora_b": Param((cfg.decay_lora, h, k), (None, "heads", None)),
            "u": Param((h, k), ("heads", None), init="zeros"),
            "w_r": Param((d, h, k), ("embed", "heads", None)),
            "w_k": Param((d, h, k), ("embed", "heads", None)),
            "w_v": Param((d, h, k), ("embed", "heads", None)),
            "w_g": Param((d, h, k), ("embed", "heads", None)),
            "w_o": Param((h, k, d), ("heads", None, "embed")),
            "ln_x": Param((h, k), ("heads", None), init="ones"),
        },
        "chan": {
            "mu_ck": Param((d,), (None,), init="zeros"),
            "mu_cr": Param((d,), (None,), init="zeros"),
            "w_ck": Param((d, cfg.d_ff), ("embed", "ff")),
            "w_cv": Param((cfg.d_ff, d), ("ff", "embed")),
            "w_cr": Param((d, d), ("embed", None)),
        },
        "time_norm": Param((d,), (None,), init="ones"),
        "chan_norm": Param((d,), (None,), init="ones"),
    }


def schema(cfg: RWKV6Config) -> Dict[str, Any]:
    return {
        "embed": Param((cfg.vocab, cfg.d_model), ("vocab", None), init="embed"),
        "layers": common.stacked(layer_schema(cfg), cfg.n_layers),
        "final_norm": Param((cfg.d_model,), (None,), init="ones"),
        "lm_head": Param((cfg.d_model, cfg.vocab), ("embed", "vocab")),
    }


def init(rng: jax.Array, cfg: RWKV6Config):
    return common.init_from_schema(rng, schema(cfg), cfg.param_dtype)


# ---------------------------------------------------------------------------
# WKV recurrence
# ---------------------------------------------------------------------------


def wkv_scan_ref(
    r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array, u: jax.Array,
    s0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Step-by-step oracle.  r/k/v/log_w: (B,T,H,K); u: (H,K).
    Returns (out (B,T,H,K), final state (B,H,K,K))."""
    b, t, h, kk = r.shape
    s = jnp.zeros((b, h, kk, kk), jnp.float32) if s0 is None else s0.astype(jnp.float32)

    def step(s, inp):
        r_t, k_t, v_t, lw_t = inp  # (B,H,K)
        kv = k_t[..., :, None].astype(jnp.float32) * v_t[..., None, :].astype(jnp.float32)
        out = jnp.einsum(
            "bhk,bhkv->bhv", r_t.astype(jnp.float32), u[None, :, :, None] * kv + s
        )
        s = jnp.exp(lw_t.astype(jnp.float32))[..., None] * s + kv
        return s, out

    xs = (
        r.transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2, 3),
        v.transpose(1, 0, 2, 3),
        log_w.transpose(1, 0, 2, 3),
    )
    s, outs = jax.lax.scan(step, s, xs)
    return outs.transpose(1, 0, 2, 3).astype(r.dtype), s


def wkv_chunked(
    r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array, u: jax.Array,
    *, chunk: int = 64, s0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked-parallel WKV.  Shapes as in `wkv_scan_ref`.

    Within a chunk, with L_t = sum_{j<=t} log w_j (inclusive cumsum):
      intra(t,s) = sum_d r_t[d] k_s[d] exp(L_{t-1} - L_s)   for s < t
                 = (r .* exp(L_prev)) @ (k .* exp(-L))^T    — two scaled GEMMs
      out_t      = intra @ v + (r_t . u . k_t) v_t + (r .* exp(L_prev)) S0
      S_end      = exp(L_C) . S0 + (k .* exp(L_C - L))^T V
    log-decays are clamped to [LOG_DECAY_MIN, 0] for the exp(-L) stability of
    the scaled-GEMM form (same clamp as the Pallas kernel).
    """
    b, t, h, kk = r.shape
    c = min(chunk, t)
    pad = (-t) % c
    if pad:
        z = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = z(r), z(k), z(v)
        log_w = jnp.pad(log_w, ((0, 0), (0, pad), (0, 0), (0, 0)))
    tp = t + pad
    nc = tp // c

    def reshape(x):
        return x.reshape(b, nc, c, h, kk).transpose(1, 0, 3, 2, 4)  # (nc,B,H,C,K)

    rc, kc, vc = reshape(r).astype(jnp.float32), reshape(k).astype(jnp.float32), reshape(v).astype(jnp.float32)
    lw = jnp.clip(reshape(log_w).astype(jnp.float32), LOG_DECAY_MIN, 0.0)

    s_init = (
        jnp.zeros((b, h, kk, kk), jnp.float32) if s0 is None else s0.astype(jnp.float32)
    )

    def chunk_body(s, inp):
        r_b, k_b, v_b, lw_b = inp  # (B,H,C,K)
        l_inc = jnp.cumsum(lw_b, axis=2)            # L_t inclusive
        l_prev = l_inc - lw_b                        # L_{t-1}
        l_end = l_inc[:, :, -1:, :]                  # L_C
        # Mid-point normalization: score(t,s) = exp(L_{t-1}-L_s) factors into
        # exp(L_{t-1}-L_mid) * exp(L_mid-L_s); each exponent is bounded by
        # |L_end|/2 <= chunk*|LOG_DECAY_MIN|/2, keeping the two GEMM factors
        # finite in float32 (the unnormalized form overflows exp(-L)).
        l_mid = 0.5 * l_end
        rr = r_b * jnp.exp(l_prev - l_mid)
        kk_ = k_b * jnp.exp(l_mid - l_inc)
        scores = jnp.einsum("bhtd,bhsd->bhts", rr, kk_)
        mask = jnp.tril(jnp.ones((c, c), bool), k=-1)
        scores = jnp.where(mask[None, None], scores, 0.0)
        diag = jnp.einsum("bhtd,bhtd->bht", r_b * u[None, :, None, :], k_b)
        out = jnp.einsum("bhts,bhsv->bhtv", scores, v_b)
        out = out + diag[..., None] * v_b
        # Inter-chunk term needs the unnormalized r * exp(L_prev); exp(L_prev)
        # only decays (<= 1) so underflow-to-zero is the correct limit.
        out = out + jnp.einsum("bhtd,bhdv->bhtv", rr * jnp.exp(l_mid), s)
        k_dec = k_b * jnp.exp(l_end - l_inc)
        s_new = jnp.exp(l_end[:, :, 0, :])[..., None] * s + jnp.einsum(
            "bhtd,bhtv->bhdv", k_dec, v_b
        )
        return s_new, out

    body = jax.checkpoint(chunk_body)
    s_final, outs = jax.lax.scan(body, s_init, (rc, kc, vc, lw))
    # (nc,B,H,C,K) -> (B,T,H,K)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, tp, h, kk)[:, :t]
    return out.astype(r.dtype), s_final


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _shift(x: jax.Array, prev: Optional[jax.Array] = None) -> jax.Array:
    """Token shift: returns previous token's features (zeros/`prev` at t=0)."""
    first = jnp.zeros_like(x[:, :1]) if prev is None else prev[:, None, :]
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def _decay(tp: Dict[str, Any], xw: jax.Array, cfg: RWKV6Config) -> jax.Array:
    """Data-dependent log-decay, (B,T,H,K)."""
    lora = jnp.einsum(
        "btd,dl->btl", xw, tp["w_lora_a"].astype(jnp.float32)
    )
    lora = jnp.einsum("btl,lhk->bthk", jnp.tanh(lora), tp["w_lora_b"].astype(jnp.float32))
    log_w = -jnp.exp(tp["w0"].astype(jnp.float32)[None, None] + lora)
    return jnp.clip(log_w, LOG_DECAY_MIN, 0.0)


def _time_mix(
    tp: Dict[str, Any],
    x: jax.Array,
    cfg: RWKV6Config,
    *,
    shift_prev: Optional[jax.Array] = None,
    state: Optional[jax.Array] = None,
    chunked: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    b, t, d = x.shape
    h, kk = cfg.n_heads, cfg.head_size
    xs = _shift(x, shift_prev)

    def mix(mu):
        return x + (xs - x) * mu[None, None]

    hd = ("batch", None, "heads", None)
    r = constrain(jnp.einsum("btd,dhk->bthk", mix(tp["mu_r"]), tp["w_r"]), hd)
    k = constrain(jnp.einsum("btd,dhk->bthk", mix(tp["mu_k"]), tp["w_k"]), hd)
    v = constrain(jnp.einsum("btd,dhk->bthk", mix(tp["mu_v"]), tp["w_v"]), hd)
    g = jax.nn.silu(jnp.einsum("btd,dhk->bthk", mix(tp["mu_g"]), tp["w_g"]))
    log_w = _decay(tp, mix(tp["mu_w"]).astype(jnp.float32), cfg)
    u = tp["u"].astype(jnp.float32)

    if cfg.use_kernel and t > 1 and state is None:
        from repro.kernels.rwkv6_wkv import wkv as wkv_kernel_op

        out, s_new = wkv_kernel_op(
            r.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            log_w, u, chunk=cfg.wkv_chunk,
            interpret=jax.default_backend() == "cpu",
        )
        out = out.astype(cfg.compute_dtype)
    elif chunked and t > 1:
        out, s_new = wkv_chunked(r, k, v, log_w, u, chunk=cfg.wkv_chunk, s0=state)
    else:
        out, s_new = wkv_scan_ref(r, k, v, log_w, u, s0=state)
    # Per-head LayerNorm (GroupNorm equivalent), then gate and project.
    out = common.layer_norm(out.astype(jnp.float32)) * tp["ln_x"].astype(jnp.float32)[None, None]
    out = (out.astype(cfg.compute_dtype) * g)
    return jnp.einsum("bthk,hkd->btd", out, tp["w_o"]), s_new


def _chan_mix(
    cp: Dict[str, Any], x: jax.Array, *, shift_prev: Optional[jax.Array] = None
) -> jax.Array:
    xs = _shift(x, shift_prev)
    xk = x + (xs - x) * cp["mu_ck"][None, None]
    xr = x + (xs - x) * cp["mu_cr"][None, None]
    k = common.relu2(jnp.einsum("btd,df->btf", xk, cp["w_ck"]))
    r = jax.nn.sigmoid(jnp.einsum("btd,de->bte", xr, cp["w_cr"]))
    return r * jnp.einsum("btf,fd->btd", k, cp["w_cv"])


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def forward(params: Dict[str, Any], cfg: RWKV6Config, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    x = common.constrain(x, ("batch", None, None))

    def body(x, lp):
        h = common.rms_norm(x, lp["time_norm"])
        t_out, _ = _time_mix(lp["time"], h, cfg)
        x = x + t_out
        h = common.rms_norm(x, lp["chan_norm"])
        x = x + _chan_mix(lp["chan"], h)
        return x, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["layers"])
    x = common.rms_norm(x, params["final_norm"])
    return jnp.einsum(
        "btd,dv->btv", x, params["lm_head"].astype(cfg.compute_dtype)
    ).astype(jnp.float32)


def init_cache(cfg: RWKV6Config, batch: int, seq_len: int, dtype=None):
    """O(1) state: WKV matrix + the two token-shift registers per layer."""
    if dtype is None:
        dtype = cfg.compute_dtype  # cache dtype must match decode K/V
    h, kk, d, L = cfg.n_heads, cfg.head_size, cfg.d_model, cfg.n_layers
    return {
        "wkv": jnp.zeros((L, batch, h, kk, kk), jnp.float32),
        "time_shift": jnp.zeros((L, batch, d), dtype),
        "chan_shift": jnp.zeros((L, batch, d), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(
    params: Dict[str, Any],
    cfg: RWKV6Config,
    cache: Dict[str, jax.Array],
    tokens: jax.Array,
    pos: jax.Array,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)  # (B,1,d)

    def body(x, layer):
        lp, s_wkv, t_shift, c_shift = layer
        h = common.rms_norm(x, lp["time_norm"])
        new_t_shift = h[:, 0]
        t_out, s_new = _time_mix(
            lp["time"], h, cfg, shift_prev=t_shift, state=s_wkv, chunked=False
        )
        x = x + t_out
        h = common.rms_norm(x, lp["chan_norm"])
        new_c_shift = h[:, 0]
        x = x + _chan_mix(lp["chan"], h, shift_prev=c_shift)
        return x, (s_new, new_t_shift, new_c_shift)

    x, (wkv, t_shift, c_shift) = jax.lax.scan(
        body, x, (params["layers"], cache["wkv"], cache["time_shift"], cache["chan_shift"])
    )
    x = common.rms_norm(x, params["final_norm"])
    logits = jnp.einsum(
        "btd,dv->btv", x, params["lm_head"].astype(cfg.compute_dtype)
    ).astype(jnp.float32)
    return logits, {
        "wkv": wkv,
        "time_shift": t_shift.astype(cache["time_shift"].dtype),
        "chan_shift": c_shift.astype(cache["chan_shift"].dtype),
        "pos": pos + 1,
    }
