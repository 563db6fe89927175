"""Multi-job heterogeneity-aware scheduler + elastic controller tests
(paper §6 future-work items, implemented as beyond-paper extensions)."""
import numpy as np
import pytest

from repro.core.controller import CannikinController
from repro.core.perf_model import CommModel
from repro.core.scheduler import (
    Allocation,
    JobSpec,
    Scheduler,
    allocate,
    random_jobs,
)
from repro.core.simulator import GPU_CATALOG, SimulatedCluster, cluster_B


def make_job(name, node_names, total_batch, b_noise, scale=1.0, min_nodes=1):
    models = tuple(
        GPU_CATALOG[n].scaled(1.0 / scale).model() for n in node_names
    )
    return JobSpec(
        name=name,
        node_models=models,
        comm=CommModel(t_o=0.04 * scale, t_u=0.008 * scale, gamma=0.15),
        total_batch=total_batch,
        b_noise=b_noise,
        ref_batch=64,
        min_nodes=min_nodes,
    )


NODES = ["a100"] * 4 + ["v100"] * 4 + ["rtx6000"] * 8


def test_allocation_covers_cluster_and_jobs():
    jobs = [
        make_job("big", NODES, total_batch=1024, b_noise=2000.0, scale=2.0),
        make_job("small", NODES, total_batch=128, b_noise=200.0, scale=0.2),
    ]
    alloc = allocate(jobs, len(NODES))
    assigned = [n for ids in alloc.assignment.values() for n in ids]
    assert sorted(assigned) == sorted(set(assigned))  # disjoint
    assert all(len(ids) >= 1 for ids in alloc.assignment.values())
    assert all(g > 0 for g in alloc.goodputs.values())
    assert 0 < alloc.aggregate_fraction <= 2.0 + 1e-9


def test_big_job_gets_more_nodes():
    jobs = [
        make_job("big", NODES, total_batch=2048, b_noise=5000.0, scale=2.0),
        make_job("tiny", NODES, total_batch=64, b_noise=100.0, scale=0.1),
    ]
    alloc = allocate(jobs, len(NODES))
    assert len(alloc.assignment["big"]) > len(alloc.assignment["tiny"])


def test_greedy_beats_random_split():
    rng = np.random.default_rng(0)
    jobs = [
        make_job("a", NODES, total_batch=512, b_noise=1500.0, scale=1.0),
        make_job("b", NODES, total_batch=512, b_noise=1500.0, scale=1.0),
    ]
    alloc = allocate(jobs, len(NODES))
    greedy = alloc.aggregate_fraction
    # random disjoint splits
    worst_gap = 0.0
    for _ in range(10):
        perm = rng.permutation(len(NODES))
        half = len(NODES) // 2
        f = (
            jobs[0].goodput(tuple(perm[:half])) / max(jobs[0].solo_goodput(), 1e-12)
            + jobs[1].goodput(tuple(perm[half:])) / max(jobs[1].solo_goodput(), 1e-12)
        )
        assert greedy >= f - 1e-6


def test_min_nodes_respected():
    jobs = [
        make_job("needs4", NODES, total_batch=512, b_noise=1000.0, min_nodes=4),
        make_job("any", NODES, total_batch=256, b_noise=500.0),
    ]
    alloc = allocate(jobs, len(NODES))
    # min_nodes gates goodput to zero below the floor, so the greedy loop
    # keeps feeding the job until it produces goodput.
    assert len(alloc.assignment["needs4"]) >= 4 or alloc.goodputs["needs4"] == 0.0


# ---------------------------------------------------------------------------
# batched (stacked) allocation engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_batched_allocate_matches_scalar(seed):
    """The stacked per-round marginal evaluation emits the same assignment
    and the same goodputs as the per-(job, node) scalar loop (the job mix is
    the same seeded generator the benchmark gates use)."""
    jobs = random_jobs(4, 12, seed)
    a_b = allocate(jobs, 12, engine="batched")
    a_s = allocate(jobs, 12, engine="scalar")
    assert a_b.assignment == a_s.assignment
    for name in a_b.goodputs:
        assert a_b.goodputs[name] == pytest.approx(a_s.goodputs[name], rel=1e-12)
    assert a_b.aggregate_fraction == pytest.approx(a_s.aggregate_fraction, rel=1e-12)


def test_batched_allocate_min_nodes_and_identical_nodes():
    """Exact-tie rows (identical node models) and min_nodes floors break the
    same way in both engines."""
    jobs = [
        make_job("needs4", NODES, total_batch=512, b_noise=1000.0, min_nodes=4),
        make_job("any", NODES, total_batch=256, b_noise=500.0),
    ]
    a_b = allocate(jobs, len(NODES), engine="batched")
    a_s = allocate(jobs, len(NODES), engine="scalar")
    assert a_b.assignment == a_s.assignment


def test_nan_fit_job_degrades_gracefully_in_both_engines():
    """A job whose OLS fit produced a NaN coefficient must score goodput 0.0
    (validation rejects it with ValueError) — not crash the round — in the
    batched engine exactly like the scalar one."""
    from repro.core.perf_model import NodePerfModel as NPM

    bad_node_sets = {
        "nan-q": tuple(NPM(q=float("nan"), s=0.0, k=1e-3, m=0.0) for _ in range(4)),
        # k <= 0 with alpha = q + k still positive: only a k-specific check
        # catches it, exactly like the per-node NodePerfModel validation.
        "neg-k": tuple(NPM(q=1e-2, s=0.0, k=-1e-4, m=0.0) for _ in range(4)),
        # q < 0 with alpha and beta still positive: only the q-specific
        # (alpha - k >= 0) check catches it.
        "neg-q": tuple(NPM(q=-5e-3, s=0.0, k=1e-1, m=0.0) for _ in range(4)),
    }
    ok_job = make_job("ok", ["a100"] * 4, total_batch=128, b_noise=500.0)
    for label, models in bad_node_sets.items():
        bad_job = JobSpec(
            name="broken",
            node_models=models,
            comm=CommModel(t_o=0.02, t_u=0.005, gamma=0.1),
            total_batch=128,
            b_noise=500.0,
            ref_batch=64,
        )
        allocs = {
            engine: allocate([bad_job, ok_job], 4, engine=engine)
            for engine in ("batched", "scalar")
        }
        for engine, alloc in allocs.items():
            assert alloc.goodputs["broken"] == 0.0, (label, engine)
            assert alloc.goodputs["ok"] > 0.0, (label, engine)
        assert allocs["batched"].assignment == allocs["scalar"].assignment, label


def test_allocate_unknown_engine_raises():
    with pytest.raises(ValueError):
        allocate([], 4, engine="vectorised")


def test_allocate_empty_jobs():
    assert allocate([], 8).assignment == {}


# ---------------------------------------------------------------------------
# incremental Scheduler (add/remove/update_job)
# ---------------------------------------------------------------------------


def _goodputs_equal(a: Allocation, b: Allocation) -> None:
    assert a.assignment == b.assignment
    for name in b.goodputs:
        assert a.goodputs[name] == pytest.approx(b.goodputs[name], rel=1e-12)


@pytest.mark.parametrize("seed", range(4))
def test_scheduler_add_job_matches_full_reallocation(seed):
    """Incremental arrival: the warm/cached re-run emits the same assignment
    and the same (scalar-path) goodputs as a cold full allocate."""
    jobs = random_jobs(5, 14, seed)
    sched = Scheduler(14)
    for job in jobs[:4]:
        sched.add_job(job)
    _goodputs_equal(sched.allocation, allocate(jobs[:4], 14))
    inc = sched.add_job(jobs[4])
    _goodputs_equal(inc, allocate(jobs, 14))


@pytest.mark.parametrize("seed", range(4))
def test_scheduler_remove_job_matches_full_reallocation(seed):
    jobs = random_jobs(5, 14, 100 + seed)
    sched = Scheduler(14)
    for job in jobs:
        sched.add_job(job)
    gone = jobs[seed % len(jobs)].name
    inc = sched.remove_job(gone)
    kept = [j for j in jobs if j.name != gone]
    _goodputs_equal(inc, allocate(kept, 14))
    assert gone not in inc.assignment


def test_scheduler_incremental_reuses_cached_rows():
    """A replayed trajectory hits the per-(job, node-set) row cache: the
    second identical reallocate solves (almost) nothing, and an arrival
    re-solves far fewer rows than the full run did."""
    jobs = random_jobs(4, 12, 11)
    sched = Scheduler(12)
    for job in jobs[:3]:
        sched.add_job(job)
    solved_before = sched.solved_rows
    sched.reallocate()  # identical job set: full cache replay
    assert sched.solved_rows == solved_before
    assert sched.cached_rows > 0
    sched.add_job(jobs[3])
    arrival_solved = sched.solved_rows - solved_before
    assert 0 < arrival_solved < solved_before
    assert sched.warm_rounds > 0  # diverged rounds re-solve warm-seeded


def test_scheduler_update_job_invalidates_stale_caches():
    """Satellite regression: a coefficient refresh (per-epoch OLS refit)
    must invalidate the refreshed job's cached rows/goodputs — serving the
    old-regime values would emit a stale allocation."""
    jobs = random_jobs(3, 10, 21)
    sched = Scheduler(10)
    for job in jobs:
        sched.add_job(job)
    # Refit job0 4x slower: same name, refreshed coefficients.
    slow = JobSpec(
        name=jobs[0].name,
        node_models=tuple(
            type(m)(q=m.q * 4, s=m.s * 4, k=m.k * 4, m=m.m * 4)
            for m in jobs[0].node_models
        ),
        comm=jobs[0].comm,
        total_batch=jobs[0].total_batch,
        b_noise=jobs[0].b_noise,
        ref_batch=jobs[0].ref_batch,
        min_nodes=jobs[0].min_nodes,
    )
    updated = sched.update_job(slow)
    _goodputs_equal(updated, allocate([slow, jobs[1], jobs[2]], 10))
    # The refresh really changed the outcome vs the stale spec.
    stale = allocate(jobs, 10)
    assert (
        updated.assignment != stale.assignment
        or updated.goodputs[slow.name] != pytest.approx(stale.goodputs[slow.name])
    )


def test_scheduler_update_unknown_or_duplicate_job_raises():
    jobs = random_jobs(2, 6, 31)
    sched = Scheduler(6)
    sched.add_job(jobs[0])
    with pytest.raises(ValueError):
        sched.add_job(jobs[0])
    with pytest.raises(KeyError):
        sched.update_job(jobs[1])
    with pytest.raises(KeyError):
        sched.remove_job("nope")


def test_scheduler_empty_and_scalar_engine():
    sched = Scheduler(8, engine="scalar")
    assert sched.reallocate().assignment == {}
    jobs = random_jobs(2, 8, 41)
    for job in jobs:
        sched.add_job(job)
    _goodputs_equal(sched.allocation, allocate(jobs, 8, engine="scalar"))
    with pytest.raises(ValueError):
        Scheduler(8, engine="vectorised")


def test_allocate_rejects_duplicate_job_names():
    jobs = random_jobs(2, 6, 51)
    dup = [jobs[0], jobs[0]]
    with pytest.raises(ValueError):
        allocate(dup, 6)


# ---------------------------------------------------------------------------
# aggregate guards (empty / zero-node / NaN allocations)
# ---------------------------------------------------------------------------


def test_aggregate_guards_empty_and_nan():
    """Empty and NaN-poisoned allocations aggregate to finite values, never
    NaN — a zero-node or garbage-normalized job must not break the trace
    summary arithmetic."""
    empty = Allocation({}, {}, {})
    assert empty.aggregate_fraction == 0.0
    assert empty.aggregate_goodput == 0.0

    nan = float("nan")
    poisoned = Allocation(
        assignment={"ok": (0, 1), "broken": ()},
        goodputs={"ok": 10.0, "broken": nan},
        fractions={"ok": 0.5, "broken": nan},
    )
    assert poisoned.aggregate_fraction == pytest.approx(0.5)
    assert poisoned.aggregate_goodput == pytest.approx(10.0)
    from repro.core.scheduler import aggregate_goodput

    assert aggregate_goodput([], poisoned) == pytest.approx(10.0)
    assert aggregate_goodput([], empty) == 0.0


# ---------------------------------------------------------------------------
# node availability (down/drained nodes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["batched", "scalar"])
def test_allocate_unavailable_nodes_excluded(engine):
    jobs = random_jobs(3, 10, seed=61)
    alloc = allocate(jobs, 10, engine=engine, unavailable=[0, 9])
    assigned = {n for ids in alloc.assignment.values() for n in ids}
    assert not assigned & {0, 9}
    # Equivalent to the same jobs on the available sub-pool: masked engines
    # still agree with each other.
    other = allocate(jobs, 10, engine="scalar" if engine == "batched" else "batched",
                     unavailable=[0, 9])
    assert alloc.assignment == other.assignment
    # Out-of-range ids must raise identically in every engine (negative ids
    # would alias real rows in the array engine but not the scalar one).
    for bad in ([-1], [10]):
        with pytest.raises(ValueError):
            allocate(jobs, 10, engine=engine, unavailable=bad)


def test_scheduler_node_leave_join_incremental_and_correct():
    """node_leave/node_join re-allocate incrementally (row layout and caches
    preserved) and match a cold allocate with the same availability."""
    jobs = random_jobs(3, 8, seed=71)
    sched = Scheduler(8)
    for job in jobs:
        sched.add_job(job)
    solved_before = sched.solved_rows

    left = sched.node_leave([7])
    assert all(7 not in ids for ids in left.assignment.values())
    assert sched.down_nodes == (7,)
    assert sched.available_nodes == 7
    _goodputs_equal(left, allocate(jobs, 8, unavailable=[7]))
    # Incremental: the leave re-run cost less than the three arrivals did.
    assert sched.solved_rows - solved_before < solved_before
    assert sched.cached_rows > 0

    back = sched.node_join([7])
    assert sched.down_nodes == ()
    _goodputs_equal(back, allocate(jobs, 8))
    with pytest.raises(ValueError):
        sched.node_leave([8])


# ---------------------------------------------------------------------------
# bounded per-job cache eviction (FIFO) under long churn
# ---------------------------------------------------------------------------


def test_scheduler_cache_eviction_fifo_under_long_churn():
    """Long arrival/departure sequences overflow the bounded per-job caches;
    FIFO eviction must keep every cache at/under its limit while allocations
    stay identical to a cold reallocate() — evicted entries are a perf
    matter, never a correctness one."""
    n_nodes, limit = 6, 4
    pool = random_jobs(7, n_nodes, seed=81)
    sched = Scheduler(n_nodes, cache_limit=limit)
    live = {}
    saw_full_cache = False
    for step in range(40):
        job = pool[step % len(pool)]
        if job.name in live:
            sched.remove_job(job.name)
            del live[job.name]
        else:
            sched.add_job(job)
            live[job.name] = job
        for cache in list(sched._gain_cache.values()) + list(sched._take_cache.values()):
            assert len(cache) <= limit
            saw_full_cache = saw_full_cache or len(cache) == limit
        if live and step % 5 == 0:
            cold = allocate(list(live.values()), n_nodes)
            assert sched.allocation.assignment == cold.assignment
            for name in cold.goodputs:
                assert sched.allocation.goodputs[name] == pytest.approx(
                    cold.goodputs[name], rel=1e-12
                )
    # The churn really overflowed the bound (otherwise this test proves
    # nothing about eviction).
    assert saw_full_cache
    assert live
    final_cold = allocate(list(live.values()), n_nodes)
    assert sched.reallocate().assignment == final_cold.assignment
    with pytest.raises(ValueError):
        Scheduler(4, cache_limit=0)


def test_scheduler_cache_fifo_evicts_oldest_first():
    """The bounded insert is FIFO: once a per-job cache is full, the oldest
    trajectory key is the one dropped.  A solo job's greedy run inserts its
    trajectory prefixes in take order — (), (a), (a, b), ... — so with a
    limit of 2 only the two *longest* prefixes may survive."""
    jobs = random_jobs(1, 6, seed=91)
    sched = Scheduler(6, cache_limit=2)
    sched.add_job(jobs[0])
    cache = sched._gain_cache[jobs[0].name]
    assert len(cache) == 2
    lens = sorted(len(key) for key in cache)
    assert lens[1] == lens[0] + 1   # the two most recent prefixes, in order
    assert () not in cache          # the oldest (empty-set) key went first


# ---------------------------------------------------------------------------
# elastic controller
# ---------------------------------------------------------------------------


def _learn(ctrl, sim, epochs=3, steps=4):
    for _ in range(epochs):
        plan = ctrl.plan_epoch()
        _, ms = sim.run_epoch(list(plan.batches), steps)
        ctrl.observe_epoch(ms)
    return plan


def test_remove_nodes_keeps_models():
    profiles, comm = cluster_B()
    sim = SimulatedCluster(profiles, comm, noise=0.005, seed=0)
    ctrl = CannikinController(sim.n, batch_candidates=[256], ref_batch=256,
                             adaptive=False)
    _learn(ctrl, sim, epochs=4)
    assert ctrl.last_plan.phase == "optperf"
    # Scheduler takes the 8 rtx nodes away.
    ctrl.remove_nodes(list(range(8, 16)))
    plan = ctrl.plan_epoch()
    assert plan.phase == "optperf"  # no re-bootstrap: models retained
    assert len(plan.batches) == 8
    assert sum(plan.batches) == 256
    # Remaining nodes are the (faster) a100/v100s: predicted time must beat
    # the LB-BSP-style even split over them.
    sub = SimulatedCluster(profiles[:8], comm, noise=0.0, seed=0)
    even = sub.run_batch([32] * 8).batch_time
    opt = sub.run_batch(list(plan.batches)).batch_time
    assert opt <= even * 1.02


def test_add_nodes_triggers_bootstrap():
    profiles, comm = cluster_B()
    sim = SimulatedCluster(profiles, comm, noise=0.005, seed=0)
    ctrl = CannikinController(sim.n, batch_candidates=[256], ref_batch=256,
                             adaptive=False)
    _learn(ctrl, sim, epochs=4)
    ctrl.add_nodes(2)
    plan = ctrl.plan_epoch()
    assert plan.phase == "bootstrap"  # two re-learning epochs (paper §6)
    assert len(plan.batches) == 18
    # After the new nodes see two distinct batch sizes, optperf resumes.
    profiles2 = list(profiles) + [profiles[0], profiles[1]]
    sim2 = SimulatedCluster(profiles2, comm, noise=0.005, seed=1)
    for _ in range(3):
        _, ms = sim2.run_epoch(list(plan.batches), 4)
        ctrl.observe_epoch(ms)
        plan = ctrl.plan_epoch()
    assert plan.phase == "optperf"
    assert len(plan.batches) == 18


@pytest.mark.parametrize("change", ["remove", "add"])
def test_membership_change_evicts_device_coeff_export(change):
    """Satellite regression: add_nodes/remove_nodes must evict the current
    model's cached device-coefficient export — the orphaned membership's
    stack must neither stay pinned on the device nor be reusable."""
    pytest.importorskip("jax")
    from repro.core import optperf_jax

    profiles, comm = cluster_B()
    sim = SimulatedCluster(profiles, comm, noise=0.005, seed=0)
    ctrl = CannikinController(
        sim.n, batch_candidates=[256], ref_batch=256, adaptive=False,
        sweep_engine="jax",
    )
    _learn(ctrl, sim, epochs=3)
    model = ctrl.cluster_model()  # prefetches the device export (jax engine)
    assert any(key[0] == model for key in optperf_jax._DEVICE_COEFFS)
    if change == "remove":
        ctrl.remove_nodes([sim.n - 1])
    else:
        ctrl.add_nodes(1)
    assert not any(key[0] == model for key in optperf_jax._DEVICE_COEFFS)
