"""Model FLOPs of one trained token, from a configuration's shapes.

PaLM (Chowdhery et al. 2022, appendix B): 6 N + 12 L S H Q, with N the
parameters that enter a matmul for every token (every layer weight and the
output head; not the embedding lookup or norm weights), L layers, S the
sequence length, H heads of size Q.  Forward and backward only: recompute,
padding rows and any second forward pass are not counted, so the same
work counts the same whatever program does it.
"""
from __future__ import annotations

from typing import Any, Dict


def flops_per_token(cfg: Dict[str, Any], seq_len: int, matmul_params: int) -> float:
    attn = 12 * cfg["n_layers"] * seq_len * cfg["n_heads"] * cfg["head_dim"]
    return float(6 * matmul_params + attn)
