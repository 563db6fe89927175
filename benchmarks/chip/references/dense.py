"""Plain float32 reference of a dense decoder-only transformer of OLMo's
kind, written from the published description (arXiv:2402.00838).

Pre-norm blocks: ``x + attn(norm(x))`` then ``x + mlp(norm(x))``, causal
multi-head attention with grouped key/value heads (query head ``j`` reads
key/value head ``j // (n_heads / n_kv_heads)``) and rotary positions on
adjacent dimension pairs, a final norm and an output head (the embedding's
transpose where ``tie_embeddings``).  The norm is OLMo's LayerNorm without
affine parameters (``nonparam_ln``, eps 1e-5 as OLMo's config states) and
the MLP is SwiGLU (``silu(x W_gate) * (x W_up)``).  The loss is the mean
next-token cross-entropy over the rows given.

The parameter layout is the one the program under test takes (stacked
layers along a leading axis), so one set of weights, made here from the
seed, feeds both.  Nothing here imports the program.

``precision`` is ``"f32"`` (every matmul in float32 at the highest
precision) or ``"fp8"``: each matmul operand is rounded to float8 e4m3
first, the control that a comparison must refuse.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# Tokens of logits computed at once in the loss: bounds the (tokens, vocab)
# float32 block, so the reference fits beside the program's state.
LOSS_CHUNK = 1024
LN_EPS = 1e-5


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``{name: (shape, std)}`` nested like the parameters."""
    if cfg["act"] != "swiglu" or cfg["norm"] != "nonparam_ln":
        raise ValueError(f"{cfg['name']}: this reference has SwiGLU and nonparam_ln only")
    L, d, h, kv = cfg["n_layers"], cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh, ff, V = cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    layers: Dict[str, Any] = {
        "attn": {
            "wq": ((L, d, h, dh), 1 / math.sqrt(d)),
            "wk": ((L, d, kv, dh), 1 / math.sqrt(d)),
            "wv": ((L, d, kv, dh), 1 / math.sqrt(d)),
            "wo": ((L, h, dh, d), 1 / math.sqrt(h * dh)),
        },
        "mlp": {
            "w_gate": ((L, d, ff), 1 / math.sqrt(d)),
            "w_up": ((L, d, ff), 1 / math.sqrt(d)),
            "w_down": ((L, ff, d), 1 / math.sqrt(ff)),
        },
    }
    out: Dict[str, Any] = {"embed": ((V, d), 0.02), "layers": layers}
    if not cfg["tie_embeddings"]:
        out["lm_head"] = ((d, V), 1 / math.sqrt(d))
    return out


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init(cfg: Dict[str, Any], key: jax.Array):
    """Seeded weights in the configuration's parameter type (jit this)."""
    spec = layout(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    dtype = DTYPES[cfg["param_dtype"]]
    arrays = [
        (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        for k, (shape, std) in zip(keys, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, arrays)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that enter a matmul for every token: every layer weight
    and the output head, not the embedding lookup."""
    spec = layout(cfg)
    n = 0
    for path, (shape, std) in jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=_is_spec
    )[0]:
        name = jax.tree_util.keystr(path)
        if "embed" in name:
            continue
        n += int(np.prod(shape))
    if cfg["tie_embeddings"]:
        n += cfg["vocab"] * cfg["d_model"]
    return n


def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(
        spec, _round(a, precision), _round(b, precision),
        precision=jax.lax.Precision.HIGHEST,
    )


def _norm(x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS)


def _rope(x, theta: float):
    """x (B, S, H, D): rotate adjacent pairs (2i, 2i+1) by pos * theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _layer(x, lp, cfg, precision):
    h_, kv = cfg["n_heads"], cfg["n_kv_heads"]
    a = _norm(x)
    q = _rope(_mm("bsd,dhk->bshk", a, lp["attn"]["wq"], precision), cfg["rope_theta"])
    k = _rope(_mm("bsd,dhk->bshk", a, lp["attn"]["wk"], precision), cfg["rope_theta"])
    v = _mm("bsd,dhk->bshk", a, lp["attn"]["wv"], precision)
    k = jnp.repeat(k, h_ // kv, axis=2)
    v = jnp.repeat(v, h_ // kv, axis=2)
    s = x.shape[1]
    scores = _mm("bshk,bthk->bhst", q, k, precision) / math.sqrt(cfg["head_dim"])
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhst,bthk->bshk", p, v, precision)
    x = x + _mm("bshk,hkd->bsd", o, lp["attn"]["wo"], precision)
    m = _norm(x)
    g = _mm("bsd,df->bsf", m, lp["mlp"]["w_gate"], precision)
    u = _mm("bsd,df->bsf", m, lp["mlp"]["w_up"], precision)
    hid = jax.nn.silu(g) * u
    return x + _mm("bsf,fd->bsd", hid, lp["mlp"]["w_down"], precision)


def loss(params, cfg: Dict[str, Any], tokens, labels, precision: str = "f32"):
    """Mean next-token cross-entropy of ``tokens`` (B, S) against ``labels``,
    every parameter taken in float32."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = jnp.take(p["embed"], tokens, axis=0)

    def body(x, lp):
        return _layer(x, lp, cfg, precision), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, p["layers"])
    x = _norm(x)
    head = p["embed"].T if cfg["tie_embeddings"] else p["lm_head"]
    b, s, d = x.shape
    n = b * s
    chunk = min(LOSS_CHUNK, n)
    pad = (-n) % chunk
    xs = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0))).reshape(-1, chunk, d)
    ys = jnp.pad(labels.reshape(n), (0, pad)).reshape(-1, chunk)
    ws = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad)).reshape(-1, chunk)

    def ce(total, blk):
        xb, yb, wb = blk
        logits = _mm("td,dv->tv", xb, head, precision)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, yb[:, None], -1
        )[:, 0]
        return total + (nll * wb).sum(), None

    total, _ = jax.lax.scan(jax.checkpoint(ce), jnp.float32(0.0), (xs, ys, ws))
    return total / n
