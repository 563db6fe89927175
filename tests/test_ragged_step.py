"""The single-device step at per-node widths.

``node_step_body(..., widths=...)`` runs one vmapped backward per group of
equal widths, each at its own width, and drops the rows past a node's
width (they carry zero weight).  On the same padded inputs it must give
what the uniform step over the whole ``(n, b_max)`` layout gives: the
same loss, node square norms, guard verdicts, aggregate and update.  With
one width it must trace to the uniform step's program."""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

jax = pytest.importorskip("jax")

from repro.runtime.backend import (  # noqa: E402
    RealBackendConfig,
    node_step_body,
    width_groups,
)

N, B_MAX, SEQ = 3, 16, 16
RTOL = 1e-5

# name: (rows per node, widths, poison factors)
CASES = {
    "split_14_7_3": ((14, 7, 3), (16, 8, 8), (1.0, 1.0, 1.0)),
    "node2_poisoned": ((14, 7, 3), (16, 8, 8), (1.0, 1.0, float("nan"))),
    "all_padding_node": ((14, 7, 0), (16, 8, 8), (1.0, 1.0, 1.0)),
    "non_monotone": ((7, 14, 3), (8, 16, 8), (1.0, 1.0, 1.0)),
}


@pytest.fixture(scope="module")
def dense():
    backend = RealBackendConfig(arch="olmo-1b", reduced=True, seq_len=SEQ).build(seed=0)
    return backend, {}


def _body(backend, widths=None):
    return node_step_body(
        backend.api, backend.optimizer,
        outlier_factor=backend.outlier_factor, widths=widths,
    )


def _inputs(backend, rows, poison):
    rng = np.random.default_rng(11)
    vocab = backend.api.cfg.vocab
    # Pad rows hold tokens too: only their zero weight may make them inert.
    tokens = rng.integers(0, vocab, (N, B_MAX, SEQ)).astype(np.int32)
    labels = rng.integers(0, vocab, (N, B_MAX, SEQ)).astype(np.int32)
    mask = np.zeros((N, B_MAX), np.float32)
    for i, b in enumerate(rows):
        mask[i, :b] = 1.0
    r = np.asarray(rows, np.float32) / sum(rows)
    return (
        backend.params, backend.opt_state, tokens, labels, mask, r,
        np.float32(1.0), np.asarray(poison, np.float32),
    )


def _step(backend, cache, widths):
    if widths not in cache:
        cache[widths] = jax.jit(_body(backend, widths))
    return cache[widths]


def _assert_trees_close(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape
        scale = np.linalg.norm(w)
        assert np.linalg.norm(g - w) <= RTOL * max(scale, 1e-30), (scale, np.linalg.norm(g - w))


@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_step_matches_the_uniform_step(dense, case):
    backend, cache = dense
    rows, widths, poison = CASES[case]
    args = _inputs(backend, rows, poison)
    want = _step(backend, cache, None)(*args)
    got = _step(backend, cache, widths)(*args)
    (p_w, o_w, loss_w, sq_i_w, sq_g_w, valid_w) = want
    (p_g, o_g, loss_g, sq_i_g, sq_g_g, valid_g) = got
    np.testing.assert_array_equal(np.asarray(valid_g), np.asarray(valid_w))
    # sq_i stays in node order (NaN where a node is poisoned).
    np.testing.assert_allclose(np.asarray(sq_i_g), np.asarray(sq_i_w), rtol=RTOL)
    np.testing.assert_allclose(float(loss_g), float(loss_w), rtol=RTOL)
    np.testing.assert_allclose(float(sq_g_g), float(sq_g_w), rtol=RTOL)
    _assert_trees_close(p_g, p_w)
    _assert_trees_close(o_g.momentum, o_w.momentum)
    if case == "node2_poisoned":
        assert list(np.asarray(valid_g)) == [True, True, False]
    else:
        assert np.asarray(valid_g).all()


def test_ragged_moe_step_computes_each_node_at_its_own_width():
    # An expert router counts every row it is given, pad rows too (its
    # capacity and load-balance statistics), so an expert family's node
    # gradient follows its width: each node's square norm is the uniform
    # step's over that node's own group at that width.  The reported loss
    # is still the full-batch forward over the whole layout.
    backend = RealBackendConfig(arch="mixtral-8x7b", reduced=True, seq_len=SEQ).build(seed=0)
    params, opt_state, tokens, labels, mask, r, lr, poison = _inputs(
        backend, (14, 7, 3), (1.0, 1.0, 1.0)
    )
    got = jax.jit(_body(backend, (16, 8, 8)))(
        params, opt_state, tokens, labels, mask, r, lr, poison
    )
    want = jax.jit(_body(backend))(params, opt_state, tokens, labels, mask, r, lr, poison)
    np.testing.assert_allclose(float(got[2]), float(want[2]), rtol=RTOL)
    sq_i = np.asarray(got[3])
    for idx, width in (([0], 16), ([1, 2], 8)):
        sub = jax.jit(_body(backend))(
            params, opt_state, tokens[idx, :width], labels[idx, :width],
            mask[idx, :width], r[idx] / r[idx].sum(), lr, poison[idx],
        )
        np.testing.assert_allclose(sq_i[idx], np.asarray(sub[3]), rtol=RTOL)


@pytest.mark.parametrize("b", [8, 16])
def test_uniform_widths_trace_to_the_uniform_program(dense, b):
    backend, _ = dense
    args = _inputs(backend, (b, b // 2, 1), (1.0, 1.0, 1.0))
    args = args[:2] + tuple(a[:, :b] for a in args[2:5]) + args[5:]
    plain = jax.make_jaxpr(_body(backend))(*args)
    uniform = jax.make_jaxpr(_body(backend, (b,) * N))(*args)
    assert str(uniform) == str(plain)


def test_ragged_step_traces_one_backward_per_width(dense):
    backend, _ = dense
    args = _inputs(backend, (14, 7, 3), (1.0, 1.0, 1.0))
    plain = str(jax.make_jaxpr(_body(backend))(*args))
    ragged = str(jax.make_jaxpr(_body(backend, (16, 8, 8)))(*args))
    # The model's layer scan runs forward and backward once per group.
    assert ragged.count("scan[") == 2 * plain.count("scan[")


def test_width_groups_keep_first_appearance_order():
    assert width_groups((16, 8, 8)) == ((16, (0,)), (8, (1, 2)))
    assert width_groups((8, 16, 8)) == ((8, (0, 2)), (16, (1,)))
    assert width_groups((8, 8, 8)) == ((8, (0, 1, 2)),)


@pytest.mark.parametrize("widths", [(16, 8), (16, 8, 24)])
def test_widths_that_do_not_fit_the_layout_are_refused(dense, widths):
    backend, _ = dense
    args = _inputs(backend, (14, 7, 3), (1.0, 1.0, 1.0))
    with pytest.raises(ValueError, match="do not fit"):
        jax.make_jaxpr(_body(backend, widths))(*args)
