"""Stacked on-device OptPerf engine: stacked-jax vs NumPy-stacked vs the
scalar water-fill oracle across seeded ragged padded clusters, warm-seeded
device brackets, device-export caching/invalidation on in-place coefficient
refresh, and the jax scheduler engine."""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

jax = pytest.importorskip("jax")

from repro.core.optperf import (  # noqa: E402
    solve_optperf_stacked,
    solve_optperf_waterfill,
)
from repro.core.optperf_jax import (  # noqa: E402
    solve_optperf_stacked_jax,
    stacked_device_coeffs,
)
from repro.core.perf_model import (  # noqa: E402
    ClusterPerfModel,
    CommModel,
    NodePerfModel,
    StackedClusterModel,
)
from repro.core.scheduler import Scheduler, allocate, random_jobs  # noqa: E402


def random_cluster(rng: np.random.Generator, n: int) -> ClusterPerfModel:
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


def ragged_stack(rng: np.random.Generator, rows: int):
    """A padded stack of ``rows`` independent clusters with ragged sizes
    drawn log-uniformly from [2, 256]."""
    sizes = np.unique(
        np.round(2.0 ** rng.uniform(1.0, 8.0, size=rows)).astype(int)
    )
    rng.shuffle(sizes)
    models = [random_cluster(rng, int(n)) for n in sizes[:rows]]
    while len(models) < rows:
        models.append(random_cluster(rng, int(rng.integers(2, 17))))
    totals = rng.uniform(32.0, 8192.0, size=rows)
    return models, StackedClusterModel.from_models(models), totals


def test_stacked_solver_picks_engine():
    """engine="jax" always selects the stacked on-device kernel (no silent
    NumPy fallback); any other engine name selects the NumPy stacked path."""
    from repro.core.scheduler import _stacked_solver

    assert _stacked_solver("jax") is solve_optperf_stacked_jax
    assert _stacked_solver("batched") is solve_optperf_stacked


def test_stacked_jax_parity_100_seeded_ragged_clusters():
    """Acceptance: over 100 seeded padded clusters (ragged row sizes,
    n in [2, 256]) the stacked jax engine matches the NumPy stacked path and
    the scalar water-fill oracle to <= 1e-5 relative opt_perf in the default
    float32 device sweep, with exact-sum padded partitions."""
    checked = 0
    for seed in range(10):
        rng = np.random.default_rng(9000 + seed)
        models, stack, totals = ragged_stack(rng, rows=10)
        jx = solve_optperf_stacked_jax(stack, totals)
        np_sol = solve_optperf_stacked(stack, totals)
        rel = np.abs(jx.opt_perfs - np_sol.opt_perfs) / np_sol.opt_perfs
        assert float(rel.max()) <= 1e-5
        for r, model in enumerate(models):
            wf = solve_optperf_waterfill(model, float(totals[r]))
            assert jx.opt_perfs[r] == pytest.approx(wf.opt_perf, rel=1e-5)
            sol = jx.solution(r)
            assert len(sol.batches) == model.n  # padding dropped
            assert sum(sol.batches) == pytest.approx(totals[r], rel=1e-9)
            assert min(sol.batches) >= 0.0
            checked += 1
    assert checked == 100


def test_stacked_jax_warm_start_matches_cold():
    rng = np.random.default_rng(77)
    _, stack, totals = ragged_stack(rng, rows=8)
    cold = solve_optperf_stacked_jax(stack, totals)
    warm = solve_optperf_stacked_jax(stack, totals, warm_start=cold.t_stars)
    np.testing.assert_allclose(warm.opt_perfs, cold.opt_perfs, rtol=1e-5)
    assert warm.method == "waterfill/stacked-jax+warm"
    # Stale/garbage seeds are re-validated on device (lows reset, highs
    # clamped to the masked best-single-node ceiling) and stay correct.
    for garbage in (np.zeros(totals.shape), np.full(totals.shape, 1e9)):
        stale = solve_optperf_stacked_jax(stack, totals, warm_start=garbage)
        np.testing.assert_allclose(stale.opt_perfs, cold.opt_perfs, rtol=1e-4)


def test_stacked_jax_warm_shape_mismatch_raises():
    rng = np.random.default_rng(78)
    _, stack, totals = ragged_stack(rng, rows=4)
    with pytest.raises(ValueError):
        solve_optperf_stacked_jax(stack, totals, warm_start=np.zeros(3))


def test_stacked_device_coeffs_cached_and_invalidated():
    """The device export is cached per stack instance; an in-place
    coefficient refresh (the scheduler's OLS-refit path) must route through
    ``invalidate_device_cache`` or the solvers keep reading the old regime —
    the regression this guards: a stale export after refresh silently
    reusing old-regime brackets."""
    rng = np.random.default_rng(79)
    _, stack, totals = ragged_stack(rng, rows=4)
    a = stacked_device_coeffs(stack)
    assert stacked_device_coeffs(stack) is a  # cached per instance
    before = solve_optperf_stacked_jax(stack, totals)

    # Refresh coefficients in place (every node 2x slower) WITHOUT copying
    # the stack, as a per-epoch refit over persistent buffers would.
    mutable = {
        name: np.array(getattr(stack, name))
        for name in ("alphas", "cs", "betas", "ds", "ks", "ms")
    }
    refreshed = StackedClusterModel(
        t_o=stack.t_o, t_u=stack.t_u, gamma=stack.gamma, mask=stack.mask,
        **mutable,
    )
    ref_before = solve_optperf_stacked_jax(refreshed, totals)
    np.testing.assert_allclose(ref_before.opt_perfs, before.opt_perfs, rtol=1e-6)
    for name in ("alphas", "cs", "betas", "ds"):
        mutable[name] *= 2.0
    refreshed.invalidate_device_cache()
    after = solve_optperf_stacked_jax(refreshed, totals)
    fresh = solve_optperf_stacked_jax(
        StackedClusterModel(
            t_o=stack.t_o, t_u=stack.t_u, gamma=stack.gamma, mask=stack.mask,
            **mutable,
        ),
        totals,
    )
    np.testing.assert_allclose(after.opt_perfs, fresh.opt_perfs, rtol=1e-6)
    # The refresh really changed the answers (the old export would not).
    assert float(np.min(after.opt_perfs / before.opt_perfs)) > 1.5


@pytest.mark.parametrize("seed", range(4))
def test_jax_scheduler_engine_matches_scalar_oracle(seed):
    jobs = random_jobs(4, 12, seed)
    a_j = allocate(jobs, 12, engine="jax")
    a_s = allocate(jobs, 12, engine="scalar")
    assert a_j.assignment == a_s.assignment
    for name in a_j.goodputs:
        assert a_j.goodputs[name] == pytest.approx(a_s.goodputs[name], rel=1e-12)


def test_jax_scheduler_incremental_matches_full():
    jobs = random_jobs(5, 12, 3)
    sched = Scheduler(12, engine="jax")
    for job in jobs[:4]:
        sched.add_job(job)
    inc = sched.add_job(jobs[4])
    full = allocate(jobs, 12, engine="jax")
    assert inc.assignment == full.assignment
    for name in full.goodputs:
        assert inc.goodputs[name] == pytest.approx(full.goodputs[name], rel=1e-12)


def test_inplace_refresh_without_invalidate_trips_stack_stamp():
    """Regression (stale-cache fix): an in-place coefficient refresh that
    forgets ``invalidate_device_cache()`` must no longer serve stale device
    coefficients -- the content stamp recorded at export time is re-checked
    on every solve."""
    rng = np.random.default_rng(83)
    _, stack, totals = ragged_stack(rng, rows=4)
    mutable = {
        name: np.array(getattr(stack, name))
        for name in ("alphas", "cs", "betas", "ds", "ks", "ms")
    }
    refreshed = StackedClusterModel(
        t_o=stack.t_o, t_u=stack.t_u, gamma=stack.gamma, mask=stack.mask,
        **mutable,
    )
    before = solve_optperf_stacked_jax(refreshed, totals)
    stale = stacked_device_coeffs(refreshed)
    for name in ("alphas", "cs", "betas", "ds"):
        mutable[name] *= 2.0
    # NO invalidate_device_cache() here -- the stamp must catch it.
    after = solve_optperf_stacked_jax(refreshed, totals)
    assert stacked_device_coeffs(refreshed) is not stale
    fresh = solve_optperf_stacked_jax(
        StackedClusterModel(
            t_o=stack.t_o, t_u=stack.t_u, gamma=stack.gamma, mask=stack.mask,
            **{k: np.array(v) for k, v in mutable.items()},
        ),
        totals,
    )
    np.testing.assert_allclose(after.opt_perfs, fresh.opt_perfs, rtol=1e-6)
    assert float(np.min(after.opt_perfs / before.opt_perfs)) > 1.5
