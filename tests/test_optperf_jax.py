"""JAX on-device OptPerf engine: jax-vs-batched-vs-scalar equivalence across
cluster sizes, warm-seeded device brackets, device-coefficient caching, and
selector/controller integration."""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

jax = pytest.importorskip("jax")

from repro.core.goodput import BatchSizeSelector, _array_sweep_solver  # noqa: E402
from repro.core.optperf import (  # noqa: E402
    solve_optperf_batch,
    solve_optperf_waterfill,
)
from repro.core.optperf_jax import (  # noqa: E402
    device_coeffs,
    solve_optperf_batch_jax,
)
from repro.core.perf_model import ClusterPerfModel, CommModel, NodePerfModel  # noqa: E402


def random_model(rng: np.random.Generator, n: int) -> ClusterPerfModel:
    nodes = tuple(
        NodePerfModel(
            q=float(rng.uniform(1e-4, 8e-3)),
            s=float(rng.uniform(0.0, 0.02)),
            k=float(rng.uniform(1e-4, 8e-3)),
            m=float(rng.uniform(0.0, 0.02)),
        )
        for _ in range(n)
    )
    comm = CommModel(
        t_o=float(10.0 ** rng.uniform(-4, -1)),
        t_u=float(rng.uniform(0.0, 0.02)),
        gamma=float(rng.uniform(0.02, 0.6)),
    )
    return ClusterPerfModel(nodes=nodes, comm=comm)


def test_array_sweep_solver_picks_engine():
    """engine="jax" always selects the on-device sweep (no silent NumPy
    fallback); any other engine name selects the NumPy batched sweep."""
    assert _array_sweep_solver("jax") is solve_optperf_batch_jax
    assert _array_sweep_solver("batched") is solve_optperf_batch


@pytest.mark.parametrize("n", [2, 16, 64, 256])
def test_jax_vs_batched_vs_scalar_equivalence_x64(n):
    """Acceptance: under x64 the on-device sweep, the NumPy batched engine,
    and the scalar water-fill oracle agree within 1e-6 relative opt_perf
    (they actually agree to ~1e-9) for n in {2, 16, 64, 256}."""
    with jax.enable_x64(True):
        for seed in range(5):
            rng = np.random.default_rng(1000 * n + seed)
            model = random_model(rng, n)
            cands = np.unique(np.round(rng.uniform(8, 8192, size=5)))
            jx = solve_optperf_batch_jax(model, cands)
            batched = solve_optperf_batch(model, cands)
            for j, b in enumerate(cands):
                wf = solve_optperf_waterfill(model, float(b))
                assert jx.opt_perfs[j] == pytest.approx(wf.opt_perf, rel=1e-6)
                assert jx.opt_perfs[j] == pytest.approx(
                    float(batched.opt_perfs[j]), rel=1e-6
                )
                assert jx.batches[j].sum() == pytest.approx(b, rel=1e-9)
                assert jx.batches[j].min() >= 0.0


@pytest.mark.parametrize("n", [2, 16, 64])
def test_jax_float32_within_1e5_of_oracle(n):
    """The default (float32) device sweep stays within the 1e-5 gate."""
    for seed in range(5):
        rng = np.random.default_rng(2000 * n + seed)
        model = random_model(rng, n)
        cands = np.unique(np.round(rng.uniform(8, 8192, size=5)))
        jx = solve_optperf_batch_jax(model, cands)
        for j, b in enumerate(cands):
            wf = solve_optperf_waterfill(model, float(b))
            assert jx.opt_perfs[j] == pytest.approx(wf.opt_perf, rel=1e-5)


def test_jax_warm_start_matches_cold():
    rng = np.random.default_rng(5)
    model = random_model(rng, 32)
    cands = np.unique(np.round(np.geomspace(64, 16384, 16)))
    cold = solve_optperf_batch_jax(model, cands)
    warm = solve_optperf_batch_jax(model, cands, warm_start=cold.t_stars)
    np.testing.assert_allclose(warm.opt_perfs, cold.opt_perfs, rtol=1e-5)
    assert warm.method == "waterfill/jax+warm"
    # Stale/garbage warm seeds are re-validated: lows reset on device, highs
    # clamped to the single-node optimum ceiling, and the warm while_loop
    # keeps halving until converged — so even wild seeds stay correct.
    for garbage in (np.zeros(cands.shape), np.full(cands.shape, 1e9)):
        stale = solve_optperf_batch_jax(model, cands, warm_start=garbage)
        np.testing.assert_allclose(stale.opt_perfs, cold.opt_perfs, rtol=1e-4)


def test_jax_warm_start_shape_mismatch_raises():
    rng = np.random.default_rng(6)
    model = random_model(rng, 4)
    with pytest.raises(ValueError):
        solve_optperf_batch_jax(model, [64.0, 128.0], warm_start=np.zeros(3))


def test_device_coeffs_cached_and_dtype_keyed():
    rng = np.random.default_rng(7)
    model = random_model(rng, 6)
    a = device_coeffs(model)
    b = device_coeffs(model)
    assert a is b                       # cached per (model, dtype)
    assert a.alphas.shape == (6,)
    assert a.alphas.dtype == np.float32 or a.alphas.dtype == np.float64
    with jax.enable_x64(True):
        c = device_coeffs(model)
    assert c.alphas.dtype == np.float64  # x64 flip gets its own cache entry
    np.testing.assert_allclose(
        np.asarray(c.alphas), model.coeffs.alphas, rtol=1e-12
    )


def test_selector_jax_engine_matches_batched_plans():
    rng = np.random.default_rng(23)
    for trial in range(5):
        model = random_model(rng, int(rng.integers(2, 24)))
        cands = tuple(int(b) for b in (64, 128, 256, 512, 1024))
        b_noise = float(rng.uniform(50, 5000))
        sel_j = BatchSizeSelector(candidates=cands, ref_batch=64, engine="jax")
        sel_b = BatchSizeSelector(candidates=cands, ref_batch=64, engine="batched")
        got_j = sel_j.select(model, b_noise)
        got_b = sel_b.select(model, b_noise)
        # Winners are re-solved scalar, so plans are engine-identical.
        assert got_j[0] == got_b[0]
        assert got_j[1].batches == got_b[1].batches
        assert got_j[2] == pytest.approx(got_b[2], rel=1e-9)


def test_degenerate_beta_node_on_device():
    """q = 0, gamma = 0 makes beta = 0 (syncStart flat in b): the device
    kernel's +-inf handling must match the host engine."""
    model = ClusterPerfModel(
        nodes=(
            NodePerfModel(q=0.0, s=0.01, k=2e-3, m=0.01),
            NodePerfModel(q=1e-3, s=0.0, k=1e-3, m=0.0),
        ),
        comm=CommModel(t_o=0.02, t_u=0.005, gamma=0.0),
    )
    cands = np.asarray([64.0, 512.0])
    jx = solve_optperf_batch_jax(model, cands)
    ref = solve_optperf_batch(model, cands)
    np.testing.assert_allclose(jx.opt_perfs, ref.opt_perfs, rtol=1e-5)


def test_inplace_refresh_trips_model_stamp():
    """Regression (stale-cache fix): a model whose node coefficients were
    refitted in place -- bypassing the frozen-dataclass contract, as an
    online refit over persistent node objects does -- must not be served
    the device export recorded before the refresh, even when
    ``evict_device_coeffs`` was forgotten.  The content stamp recorded at
    export time is re-checked on every solve and trips the rebuild."""
    rng = np.random.default_rng(11)
    model = random_model(rng, 5)
    cands = np.asarray([64.0, 256.0, 1024.0])
    before = solve_optperf_batch_jax(model, cands)
    stale = device_coeffs(model)

    # In-place refit: every node 2x slower.  The refit refreshes the
    # memoized host views (pops the cached slots) but "forgets" to
    # invalidate the device export.
    for node in model.nodes:
        object.__setattr__(node, "q", node.q * 2.0)
        object.__setattr__(node, "k", node.k * 2.0)
    for slot in ("coeffs", "_optperf_problem", "_validated"):
        model.__dict__.pop(slot, None)

    after = solve_optperf_batch_jax(model, cands)
    assert device_coeffs(model) is not stale   # stamp forced a re-export
    oracle = solve_optperf_batch(model, cands)
    np.testing.assert_allclose(after.opt_perfs, oracle.opt_perfs, rtol=1e-5)
    # The refresh really changed the answers (a stale export would not).
    assert float(np.min(after.opt_perfs / before.opt_perfs)) > 1.3


def test_warm_sweep_no_recompile_across_epochs():
    """The donated-bracket warm sweep compiles once, then re-drives the same
    executable for 10 drifting-model epochs at fixed (C, n) shapes with zero
    jit cache misses -- the controller's epoch-over-epoch resolve (and the
    fused epoch program built on the same kernels) relies on this."""
    from repro.core import optperf_jax

    optperf_jax._device_sweep.cache_clear()
    cands = np.linspace(64.0, 2048.0, 8)
    t_seed = solve_optperf_batch_jax(
        random_model(np.random.default_rng(500), 6), cands
    ).t_stars
    fn = optperf_jax._device_sweep(64, True)
    assert fn._cache_size() == 0
    for epoch in range(10):
        model = random_model(np.random.default_rng(501 + epoch), 6)
        sol = solve_optperf_batch_jax(model, cands, warm_start=t_seed)
        t_seed = sol.t_stars
    assert fn._cache_size() == 1  # one trace, ten warm epochs
