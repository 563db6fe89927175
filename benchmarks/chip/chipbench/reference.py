"""The reference's three training steps and the numbers compared with the
program's.

``RefTrainer`` follows the step the paper defines, in plain float32: each
node's mean-loss gradient over its own rows, the Eq. 9 aggregate
``g = sum_i (b_i / B) g_i``, clipping to global norm 1, SGD with momentum
0.9 (momentum kept in float32) and the parameters stored back in the
configuration's type.  The model is a family module from ``references/``.

``fault`` plants one fault in the reference put in the program's place, to
read what a broken program would give: ``"half_batch"``, each node's loss
and gradient over the first half of its rows only.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
MOMENTUM = 0.9
MAX_GRAD_NORM = 1.0
# A leaf whose reference gradient norm is under this share of the median
# leaf's moves under SGD by rounding alone: its change is not compared.
STILL_LEAF = 1e-3


def family(name: str):
    """The reference module ``references/<name>.py``."""
    return spec.module("references", name)


def weight_seed(seed: int) -> int:
    """A 31-bit seed drawn from every bit of ``seed``: ``PRNGKey`` keeps only
    the low 32 bits of what it is given."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0] >> 1)


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(weight_seed(seed))


def _stacked(path) -> bool:
    return "layers" in jax.tree_util.keystr(path)


def leaf_names(params) -> List[str]:
    """Compared leaves in order: each layer's slice of a stacked weight is a
    leaf of its own."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if _stacked(path):
            out.extend(f"{name}[{i}]" for i in range(leaf.shape[0]))
        else:
            out.append(name)
    return out


def _norms(tree):
    parts = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = leaf.astype(jnp.float32)
        if _stacked(path):
            parts.append(jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))))
        else:
            parts.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(parts)


leaf_norms = jax.jit(_norms)
change_norms = jax.jit(
    lambda a, b: _norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
)


class RefTrainer:
    def __init__(self, cfg: Dict[str, Any], *, precision: str = "f32",
                 fault: Optional[str] = None, device=None):
        self.cfg = cfg
        self.mod = family(cfg["reference"])
        self.precision = precision
        self.fault = fault
        self.device = device

        def node_grad(params, tokens, labels):
            p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
            return jax.value_and_grad(
                lambda p: self.mod.loss(p, cfg, tokens, labels, precision)
            )(p32)

        def accumulate(agg, g, r):
            sq = sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(g))
            return jax.tree_util.tree_map(lambda a, x: a + r * x, agg, g), sq

        def update(params, mom, agg, lr):
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(agg)))
            scale = jnp.minimum(1.0, MAX_GRAD_NORM / jnp.maximum(norm, 1e-9))
            mom = jax.tree_util.tree_map(lambda m, g: MOMENTUM * m + g * scale, mom, agg)
            params = jax.tree_util.tree_map(
                lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype), params, mom
            )
            return params, mom

        self._init = jax.jit(lambda key: self.mod.init(cfg, key))
        self._grad = jax.jit(node_grad)
        self._acc = jax.jit(accumulate, donate_argnums=(0,))
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))

    def init(self, seed: int):
        """The seed's weights, in one program on the device."""
        return self._init(seed_key(seed))

    def _on_device(self):
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def run(self, seed: int, batches: Sequence[Dict[str, np.ndarray]],
            split: Sequence[int], lr: float) -> Dict[str, Any]:
        """Three (or ``len(batches)``) steps from the seed's weights over the
        given global batches.  Returns each step's loss and per-node
        gradient square norms, the leaf norms of the momentum after the
        first step, the leaf norms of the first aggregate gradient before
        clipping, and the leaf norms of the change of the parameters."""
        with self._on_device():
            params = self.init(seed)
            mom_host = None
            losses, sqs = [], []
            grad_norms = mom1 = None
            total = float(sum(split))
            for step, batch in enumerate(batches):
                agg = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                loss, sq_i, lo = 0.0, [], 0
                for i, b in enumerate(split):
                    rows = slice(lo, lo + b)
                    lo += b
                    used = max(1, b // 2) if self.fault == "half_batch" else b
                    tok = jnp.asarray(batch["tokens"][rows][:used])
                    lab = jnp.asarray(batch["labels"][rows][:used])
                    li, g = self._grad(params, tok, lab)
                    agg, sq = self._acc(agg, g, jnp.float32(b / total))
                    del g
                    loss += (b / total) * float(li)
                    sq_i.append(float(sq))
                losses.append(loss)
                sqs.append(sq_i)
                if step == 0:
                    grad_norms = np.asarray(leaf_norms(agg))
                mom = (jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                       if mom_host is None else jax.tree_util.tree_map(jnp.asarray, mom_host))
                params, mom = self._update(params, mom, agg, jnp.float32(lr))
                if step == 0:
                    mom1 = np.asarray(leaf_norms(mom))
                mom_host = jax.device_get(mom)
                del mom
            p0 = self.init(seed)
            change = np.asarray(change_norms(params, p0))
            names = leaf_names(params)
            del params, p0
        return {
            "losses": losses,
            "sq_i": sqs,
            "grad_norms": grad_norms,
            "mom1_norms": mom1,
            "change_norms": change,
            "leaves": names,
        }


def _gap(prog: np.ndarray, ref: np.ndarray, keep: Optional[np.ndarray] = None):
    """Worst leaf's ``|prog - ref| / max(ref, median(ref))`` and its index."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    den = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.where(den > 0, den, 1.0)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The numbers compared.  ``prog`` holds the program's readings under
    the keys ``RefTrainer.run`` returns (without ``grad_norms``)."""
    lp = np.asarray(prog["losses"], np.float64)
    lr_ = np.asarray(ref["losses"], np.float64)
    sp = np.asarray(prog["sq_i"], np.float64)
    sr = np.asarray(ref["sq_i"], np.float64)
    grad = np.asarray(ref["grad_norms"], np.float64)
    moving = grad >= STILL_LEAF * np.median(grad)
    g1, i1 = _gap(prog["mom1_norms"], ref["mom1_norms"])
    gc, ic = _gap(prog["change_norms"], ref["change_norms"], moving)
    names = ref["leaves"]
    return {
        "loss": {"value": float(np.max(np.abs(lp - lr_) / np.abs(lr_)))},
        "node_sqnorm": {"value": float(np.max(np.abs(sp - sr) / np.maximum(sr, 1e-30)))},
        "first_grad_leaf": {"value": g1, "leaf": names[i1]},
        "change_leaf": {"value": gc, "leaf": names[ic],
                        "still_leaves": int((~moving).sum())},
    }
