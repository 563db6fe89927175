"""The program's dense decoder (``repro.models.dense``) at a configuration's
sizes.

``program_api(cfg, init)`` starts from the program's own architecture
``cfg["program_arch"]`` and sets every size the configuration file states,
so the program runs the configuration as written.  ``init`` replaces the
model's initialiser: the benchmark makes the weights, from the seed.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Any, Callable, Dict

# DenseConfig fields the configuration file sets, by the file's key.
PROGRAM_FIELDS = (
    "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab",
    "act", "norm", "rope_theta", "tie_embeddings",
)


def program_api(cfg: Dict[str, Any], init: Callable):
    import jax.numpy as jnp

    from repro.configs import ARCHS
    from repro.models.registry import build_api

    base = ARCHS[cfg["program_arch"]].config()
    fields = {k: cfg[k] for k in PROGRAM_FIELDS}
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    fields["param_dtype"] = dtypes[cfg["param_dtype"]]
    fields["compute_dtype"] = dtypes[cfg["compute_dtype"]]
    dcfg = dataclasses.replace(base, **fields)
    if dcfg.qk_norm or dcfg.window is not None:
        raise ValueError(f"{cfg['name']}: the program's model has parts the reference lacks")
    api = copy.copy(build_api(cfg["program_arch"], dcfg))
    api.init = init
    return api
