"""Where the single-device step's reported loss comes from.

A dense family's loss is composed from the per-node mean losses the
vmapped backward already computes (``composed_loss_terms``), so the step
runs no second, full-batch forward; an MoE family's load-balance and
z-loss terms are whole-batch statistics, so its step keeps the full-batch
forward under the ``loss_forward`` scope."""
import re

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

jax = pytest.importorskip("jax")

from repro.runtime.backend import (  # noqa: E402
    SCOPE_BACKWARD,
    SCOPE_LOSS,
    RealBackendConfig,
    node_step_body,
)

N, B_MAX, SEQ = 3, 8, 16
ROWS = (7, 3, 0)          # uneven, and node 2 is all padding
DENSE, MOE = "olmo-1b", "mixtral-8x7b"


def _step_and_inputs(arch: str):
    backend = RealBackendConfig(arch=arch, reduced=True, seq_len=SEQ).build(seed=0)
    api = backend.api
    step = jax.jit(
        node_step_body(api, backend.optimizer, outlier_factor=backend.outlier_factor)
    )
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, api.cfg.vocab, (N, B_MAX, SEQ)).astype(np.int32)
    labels = rng.integers(0, api.cfg.vocab, (N, B_MAX, SEQ)).astype(np.int32)
    mask = np.zeros((N, B_MAX), np.float32)
    for i, rows in enumerate(ROWS):
        mask[i, :rows] = 1.0
    r = np.asarray(ROWS, np.float32) / sum(ROWS)
    args = (
        backend.params, backend.opt_state, tokens, labels, mask, r,
        np.float32(1.0), np.ones((N,), np.float32),
    )
    return api, step, args


@pytest.mark.parametrize("arch", [DENSE, MOE])
def test_step_loss_equals_the_full_batch_loss(arch):
    api, step, args = _step_and_inputs(arch)
    params, _, tokens, labels, mask = args[:5]
    want, _ = jax.jit(api.loss)(
        params,
        {
            "tokens": tokens.reshape(N * B_MAX, SEQ),
            "labels": labels.reshape(N * B_MAX, SEQ),
            "weights": mask.reshape(-1),
        },
    )
    loss = step(*args)[2]
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)


def _scoped_matmuls(hlo_text: str, scope: str) -> int:
    """Dot and convolution instructions whose ``op_name`` names ``scope``."""
    instr = re.compile(r"^\s*(ROOT\s+)?%?[\w.\-]+\s*=\s*[^=]*?\s(dot|convolution)\(")
    scoped = re.compile(r'op_name="[^"]*/' + re.escape(scope) + r'/')
    return sum(
        1 for line in hlo_text.splitlines()
        if instr.search(line) and scoped.search(line)
    )


@pytest.mark.parametrize("arch", [DENSE, MOE])
def test_compiled_step_runs_a_second_forward_only_for_moe(arch):
    _, step, args = _step_and_inputs(arch)
    text = step.lower(*args).compile().as_text()
    assert _scoped_matmuls(text, SCOPE_BACKWARD) > 0
    loss_matmuls = _scoped_matmuls(text, SCOPE_LOSS)
    if arch == MOE:
        assert loss_matmuls > 0
    else:
        assert loss_matmuls == 0
