"""Plain float64 references of the controller's answers, and the numbers
compared with the plans the window ran.

Gradient noise scale (Cannikin §4.4).  For a step with node batches b_i
(B = sum b_i), node gradient square norms |g_i|^2 and the Eq. 9
aggregate's |g|^2, each node gives unbiased estimates of |G|^2 and
tr(Sigma):

    G_i = (B |g|^2 - b_i |g_i|^2) / (B - b_i)
    S_i = b_i B (|g_i|^2 - |g|^2) / (B - b_i)

combined with the minimum-variance weights of Theorem 4.1, whose closed
form for these covariances is w_i = (B - b_i) / ((n - 1) B).  G and S are
smoothed over steps by exponential moving averages with the mix's
``gns_decay``, and the estimate is max(S/G, 0); a step in which the anomaly
guard excluded a node is skipped.

Plan (§3, §4).  Node i at local batch b takes a(b) = q b + s and
P(b) = k b + m; with the comm model (t_o, t_u, gamma) a step of the
cluster lasts max_i max(a + P + t_u, a + gamma P + t_o + t_u).  For a total
B the best split is the integer split (1 <= b_i <= the per-node cap)
with the least step time, found by trying every one; the goodput of B is
E(B) B / T*(B), with E(B) = (phi + B0) / (phi + B) at the estimate phi.
The node models are the mix's own, the ones the simulated cluster times.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, Sequence

import numpy as np

MAX_SPLITS = 2_000_000


def gns_trajectory(steps: Sequence[Dict[str, Any]], decay: float,
                   dtype=np.float64, weights: str = "theorem") -> List[float]:
    """The estimate after each step of ``steps`` (``local_sqnorms``,
    ``global_sqnorm``, ``batches``, ``valid``).  ``dtype`` and ``weights``
    (``"plain"``: every node weighs 1/n) exist for the control and faults."""
    ema_g = ema_s = dtype(0.0)
    count = 0
    out = []
    for st in steps:
        b = np.asarray(st["batches"], dtype)
        big = b.sum()
        usable = all(st["valid"]) and bool(np.all(b > 0)) and bool(np.all(b < big))
        if usable:
            sq = np.asarray(st["local_sqnorms"], dtype)
            gsq = dtype(st["global_sqnorm"])
            g_i = (big * gsq - b * sq) / (big - b)
            s_i = b * big * (sq - gsq) / (big - b)
            n = b.size
            w = (big - b) / ((n - 1) * big) if weights == "theorem" else np.full(n, 1 / n, dtype)
            ema_g = dtype(decay) * ema_g + dtype(1 - decay) * (w @ g_i)
            ema_s = dtype(decay) * ema_s + dtype(1 - decay) * (w @ s_i)
            count += 1
        if count == 0 or ema_g <= 0:
            out.append(float("inf"))
        else:
            out.append(max(float(ema_s / ema_g), 0.0))
    return out


def step_time(mix: Dict[str, Any], splits: np.ndarray) -> np.ndarray:
    """Step time of the cluster at each row of ``splits`` (..., n)."""
    b = np.asarray(splits, np.float64)
    nodes = mix["nodes"]
    q, s, k, m = (np.array([nd[x] for nd in nodes], np.float64) for x in "qskm")
    c = mix["comm"]
    a = q * b + s
    p = k * b + m
    node = np.maximum(a + p + c["t_u"], a + c["gamma"] * p + c["t_o"] + c["t_u"])
    return node.max(axis=-1)


def best_split_time(mix: Dict[str, Any], total: int) -> float:
    """Least step time of any integer split of ``total``."""
    n, cap = len(mix["nodes"]), int(mix["max_local"])
    lo, hi = max(1, total - (n - 1) * cap), min(cap, total - (n - 1))
    if (hi - lo + 1) ** (n - 1) > MAX_SPLITS:
        raise ValueError("too many splits to try")
    head = np.array(list(itertools.product(range(lo, hi + 1), repeat=n - 1)), np.int64)
    last = total - head.sum(axis=1)
    ok = (last >= 1) & (last <= cap)
    splits = np.concatenate([head[ok], last[ok, None]], axis=1)
    return float(step_time(mix, splits).min())


def goodput(mix: Dict[str, Any], total: int, phi: float, split_time: float) -> float:
    b0 = float(mix["ref_batch"])
    eff = 1.0 if not np.isfinite(phi) else (max(phi, 0.0) + b0) / (max(phi, 0.0) + total)
    return eff * total / split_time


def compare_plans(mix: Dict[str, Any], record: Dict[str, Any],
                  gns_dtype=np.float64, gns_weights: str = "theorem") -> Dict[str, Dict[str, Any]]:
    """The numbers compared.  ``record`` holds ``epochs``: every epoch the
    controller planned, in order, each with ``batches``, ``total``,
    ``b_noise`` (the program's estimate after the epoch), ``window`` and
    ``steps`` (the epoch's gradient observations)."""
    epochs = record["epochs"]
    steps, ends = [], []
    for e in epochs:
        steps.extend(e["steps"])
        ends.append(len(steps))
    traj = gns_trajectory(steps, mix["gns_decay"], gns_dtype, gns_weights)
    phis = [traj[i - 1] if i else float("inf") for i in ends]
    gns_gap = 0.0
    for e, phi in zip(epochs, phis):
        prog = float(e["b_noise"])
        if prog != phi:
            gap = abs(prog - phi) / phi if 0 < phi < np.inf else np.inf
            gns_gap = max(gns_gap, gap)
    cands = sorted({int(b) for b in mix["candidates"]})
    best_t = {b: best_split_time(mix, b) for b in cands}
    split_gap = total_gap = 0.0
    worst = {}
    for i, e in enumerate(epochs):
        if not e["window"]:
            continue
        phi = phis[i - 1] if i else float("inf")  # the estimate the plan used
        total = int(e["total"])
        t_best = best_t[total] if total in best_t else best_split_time(mix, total)
        sg = float(step_time(mix, np.asarray(e["batches"])[None])[0]) / t_best - 1.0
        best = max(goodput(mix, b, phi, best_t[b]) for b in cands)
        tg = 1.0 - goodput(mix, total, phi, t_best) / best
        if sg > split_gap:
            split_gap, worst["split"] = sg, list(e["batches"])
        if tg > total_gap:
            total_gap, worst["total"] = tg, total
    return {
        "gns_gap": {"value": float(gns_gap)},
        "split_gap": {"value": float(split_gap), "split": worst.get("split")},
        "total_gap": {"value": float(total_gap), "total": worst.get("total")},
    }
