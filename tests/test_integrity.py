"""Integrity-hardened real path: anomaly guards, checkpoint generations,
watchdog, invariant checker.

Covers the PR-7 acceptance criteria: the Eq. (9) gradient anomaly guard
excludes non-finite/outlier nodes and is bitwise-invisible on clean steps;
checkpoint generations are checksummed, pruned, and roll back to the
newest valid file; the numerical-health channel quarantines through the
PR-6 state machine; the deadline watchdog feeds the solver degradation
chain; the runtime invariant checker flags hand-broken state; and the
quarantine state machine stays live under random seeded fault plans
(satellite 3).
"""
import math
import os

import numpy as np
import pytest

from repro.core.aggregation import ANOMALY_OUTLIER_FACTOR, guard_weights
from repro.core.perf_model import CommModel
from repro.core.scheduler import JobSpec, random_jobs
from repro.core.simulator import GPU_CATALOG
from repro.runtime import (
    CannikinPolicy,
    CheckpointCorruption,
    ClusterRuntime,
    DeadlineExceeded,
    FaultInjector,
    FaultPlan,
    GradientPoison,
    HealthConfig,
    HealthMonitor,
    NodeState,
    RealBackendConfig,
    SolverStall,
    Straggler,
    Watchdog,
)
from repro.runtime.trace import TraceReport
from repro.train import checkpoint as ckpt


# ---------------------------------------------------------------------------
# Eq. (9) gradient anomaly guard
# ---------------------------------------------------------------------------


def _jnp():
    jnp = pytest.importorskip("jax.numpy")
    return jnp


def test_guard_weights_clean_step_returns_weights_bitwise():
    jnp = _jnp()
    r = jnp.asarray([0.5, 0.3, 0.2], dtype=jnp.float32)
    sq = jnp.asarray([1.0, 1.3, 0.8], dtype=jnp.float32)
    w, valid = guard_weights(sq, r)
    assert bool(jnp.all(valid))
    # Bit-identity on the all-valid path: the ORIGINAL weights, not a
    # renormalized reconstruction of them.
    np.testing.assert_array_equal(np.asarray(w), np.asarray(r))


@pytest.mark.parametrize("bad", [float("nan"), float("inf")])
def test_guard_weights_excludes_nonfinite_and_renormalizes(bad):
    jnp = _jnp()
    r = jnp.asarray([0.5, 0.3, 0.2], dtype=jnp.float32)
    sq = jnp.asarray([1.0, bad, 0.8], dtype=jnp.float32)
    w, valid = guard_weights(sq, r)
    assert [bool(v) for v in valid] == [True, False, True]
    w = np.asarray(w)
    assert w[1] == 0.0
    # Surviving weights are renormalized to a convex combination.
    assert w.sum() == pytest.approx(1.0, abs=1e-6)
    assert w[0] / w[2] == pytest.approx(0.5 / 0.2, rel=1e-5)


def test_guard_weights_excludes_norm_outlier():
    jnp = _jnp()
    r = jnp.asarray([1 / 3, 1 / 3, 1 / 3], dtype=jnp.float32)
    huge = 10.0 * ANOMALY_OUTLIER_FACTOR**2  # median sq-norm is 1.0
    sq = jnp.asarray([1.0, huge, 1.0], dtype=jnp.float32)
    w, valid = guard_weights(sq, r)
    assert [bool(v) for v in valid] == [True, False, True]
    assert np.asarray(w)[1] == 0.0


def test_guard_weights_all_invalid_yields_zero_update():
    jnp = _jnp()
    r = jnp.asarray([0.5, 0.5], dtype=jnp.float32)
    sq = jnp.asarray([float("nan"), float("inf")], dtype=jnp.float32)
    w, valid = guard_weights(sq, r)
    assert not bool(jnp.any(valid))
    np.testing.assert_array_equal(np.asarray(w), np.zeros(2, np.float32))


def test_poison_factor_values():
    def poison(mode):
        return GradientPoison(node=0, at_epoch=0, duration=1, mode=mode)

    assert math.isnan(poison("nan").factor_value())
    assert math.isinf(poison("inf").factor_value())
    assert poison("scale").factor_value() == 1e6
    with pytest.raises(ValueError):
        poison("mayhem").factor_value()


def test_injector_poison_factors_window():
    plan = FaultPlan(
        poisons=(GradientPoison(node=1, at_epoch=1, duration=2, mode="nan"),)
    )
    inj = FaultInjector(plan)
    inj.begin_epoch(0)
    np.testing.assert_array_equal(
        inj.poison_factors((0, 1, 2)), np.ones(3, np.float32)
    )
    inj.begin_epoch(1)
    f = inj.poison_factors((0, 1, 2))
    assert f[0] == 1.0 and f[2] == 1.0 and np.isnan(f[1])
    inj.begin_epoch(3)  # window [1, 3) closed again
    np.testing.assert_array_equal(
        inj.poison_factors((0, 1, 2)), np.ones(3, np.float32)
    )


# ---------------------------------------------------------------------------
# checksummed checkpoint generations + rollback
# ---------------------------------------------------------------------------


def _tree(fill: float):
    return {
        "w": np.full(8, fill, dtype=np.float32),
        "step": np.int64(int(fill)),
    }


def _tamper(path: str) -> None:
    """Modify a payload entry while keeping the stored digest — the
    minimal silent-corruption model the digest must catch."""
    data = dict(np.load(path, allow_pickle=False))
    key = next(k for k in data if not k.startswith("__"))
    arr = np.asarray(data[key])
    data[key] = arr + (1 if np.issubdtype(arr.dtype, np.integer) else 1.0)
    with open(path, "wb") as f:
        np.savez(f, **data)


def test_checkpoint_digest_and_generation_roundtrip(tmp_path):
    path = str(tmp_path / "job.ckpt.npz")
    ckpt.save(path, _tree(7.0), generation=5)
    assert ckpt.verify_checkpoint(path)
    assert ckpt.checkpoint_generation(path) == 5
    restored = ckpt.restore(path, _tree(0.0))
    np.testing.assert_array_equal(restored["w"], _tree(7.0)["w"])
    assert restored["step"] == 7


def test_checkpoint_tamper_detected_and_restore_refuses(tmp_path):
    path = str(tmp_path / "job.ckpt.npz")
    ckpt.save(path, _tree(7.0))
    _tamper(path)
    assert not ckpt.verify_checkpoint(path)
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.restore(path, _tree(0.0))


def test_injector_byte_flips_invalidate_checkpoint(tmp_path):
    path = str(tmp_path / "job.ckpt.npz")
    ckpt.save(path, _tree(3.0))
    inj = FaultInjector(
        FaultPlan(corruptions=(CheckpointCorruption(write_index=1, n_bytes=24),))
    )
    assert inj.corrupt_checkpoint(path) is True
    assert inj.corrupted_paths == [path]
    assert not ckpt.verify_checkpoint(path)
    # Only the scheduled write is corrupted; later writes pass through.
    ckpt.save(path, _tree(4.0))
    assert inj.corrupt_checkpoint(path) is False
    assert ckpt.verify_checkpoint(path)


def test_checkpoint_manager_generations_prune_and_rollback(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path), "job", keep=3)
    assert mgr.latest_generation == 0 and mgr.latest_path is None
    paths = [mgr.save(_tree(float(g))) for g in range(1, 5)]
    # Retention: only the newest `keep` generations survive on disk.
    assert [g for g, _ in mgr.generations()] == [2, 3, 4]
    assert not os.path.exists(paths[0])
    assert mgr.latest_generation == 4 and mgr.latest_path == paths[3]
    assert ckpt.checkpoint_generation(paths[3]) == 4

    # Corrupt the newest generation: restore rolls back to gen 3.
    _tamper(paths[3])
    tree, gen, path = mgr.restore(_tree(0.0))
    assert gen == 3 and path == paths[2]
    np.testing.assert_array_equal(tree["w"], _tree(3.0)["w"])
    assert mgr.rollbacks == 1
    assert mgr.corrupt_generations == [paths[3]]


def test_checkpoint_manager_all_generations_corrupt_raises(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path), "job", keep=2)
    for g in (1.0, 2.0):
        _tamper(mgr.save(_tree(g)))
    with pytest.raises(ckpt.CheckpointCorruptError):
        mgr.restore(_tree(0.0))
    assert mgr.rollbacks == 2  # walked past both before giving up


# ---------------------------------------------------------------------------
# numerical-health channel (detection -> quarantine state machine)
# ---------------------------------------------------------------------------


def _mon():
    return HealthMonitor(
        HealthConfig(backoff_initial=2, numeric_suspect_epochs=2)
    )


def test_numeric_streak_trips_quarantine_and_clean_epoch_resets():
    mon = _mon()
    mon.observe_numerics("j", 0, [0, 1], [1, 0])   # breach #1 on node 0
    assert mon.node(0).state == NodeState.HEALTHY
    assert [d["kind"] for d in mon.detections] == ["numeric"]
    mon.observe_numerics("j", 1, [0, 1], [0, 0])   # clean epoch: streak reset
    mon.observe_numerics("j", 2, [0, 1], [2, 0])   # breach #1 again
    assert mon.node(0).state == NodeState.HEALTHY
    mon.observe_numerics("j", 3, [0, 1], [1, 0])   # consecutive -> trip
    assert mon.node(0).state == NodeState.QUARANTINED
    assert mon.node(1).state == NodeState.HEALTHY
    kinds = [d["kind"] for d in mon.detections]
    assert kinds.count("numeric") == 2 and kinds.count("quarantine") == 1
    actions = mon.poll()
    assert [type(a).__name__ for a in actions] == ["QuarantineNode"]


def test_numeric_probation_retrip_doubles_backoff():
    mon = _mon()
    for e in (0, 1):
        mon.observe_numerics("j", e, [0], [1])
    h = mon.node(0)
    assert h.state == NodeState.QUARANTINED and h.backoff == 2
    mon.tick(h.release_epoch)
    assert h.state == NodeState.PROBATION
    # One anomalous epoch during probation re-quarantines immediately.
    mon.observe_numerics("j", h.release_epoch, [0], [1])
    assert h.state == NodeState.QUARANTINED and h.backoff == 4


def test_numeric_quarantined_nodes_are_not_re_observed():
    mon = _mon()
    for e in (0, 1):
        mon.observe_numerics("j", e, [0], [3])
    n_detections = len(mon.detections)
    mon.observe_numerics("j", 2, [0], [3])  # still quarantined: ignored
    assert len(mon.detections) == n_detections
    assert mon.node(0).backoff == 2


# ---------------------------------------------------------------------------
# satellite 3: quarantine liveness under random seeded fault plans
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(15))
def test_quarantine_state_machine_liveness_under_random_plans(seed):
    """Property: whatever anomaly schedule a seeded plan (plus random
    flapping) produces, the state machine never deadlocks — a QUARANTINED
    node always has a future release epoch, backoff never exceeds the
    32-epoch cap, and once the faults stop every node leaves quarantine
    within one backoff window."""
    n = 8
    plan = FaultPlan.chaos_real(n, seed=seed)
    rng = np.random.default_rng(seed + 1000)
    mon = _mon()
    horizon = 30
    for epoch in range(horizon):
        counts = []
        for nid in range(n):
            poisoned = any(
                p.node == nid and p.at_epoch <= epoch < p.at_epoch + p.duration
                for p in plan.poisons
            )
            flap = int(rng.random() < 0.25) * int(rng.integers(1, 4))
            counts.append((2 if poisoned else 0) + flap)
        mon.observe_numerics("job", epoch, list(range(n)), counts)
        mon.tick(epoch)
        mon.poll()
        for nid in range(n):
            h = mon.node(nid)
            assert h.backoff <= mon.config.backoff_max
            if h.state == NodeState.QUARANTINED:
                assert h.release_epoch is not None
                assert h.release_epoch > epoch  # re-admission always pending
    # Faults stop: every quarantine must drain within backoff_max epochs.
    for epoch in range(horizon, horizon + mon.config.backoff_max + 2):
        mon.observe_numerics("job", epoch, list(range(n)), [0] * n)
        mon.tick(epoch)
        mon.poll()
    for nid in range(n):
        assert mon.node(nid).state != NodeState.QUARANTINED


# ---------------------------------------------------------------------------
# deadline watchdog -> solver degradation chain
# ---------------------------------------------------------------------------


def test_watchdog_passthrough_without_deadlines():
    wd = Watchdog()
    assert wd.guard_solve(lambda: 42) == 42
    assert wd.guard_execute(lambda: "ok") == "ok"
    assert wd.counters() == {
        "solver_timeouts": 0,
        "execute_deadline_misses": 0,
        "stalled_seconds": 0.0,
    }


def test_watchdog_stall_trips_solve_deadline():
    wd = Watchdog(solve_deadline=0.002, stall_hook=lambda: 0.02)
    with pytest.raises(DeadlineExceeded) as err:
        wd.guard_solve(lambda: 1)
    assert err.value.kind == "optperf-solve"
    assert wd.solver_timeouts == 1
    assert wd.stalled_seconds == pytest.approx(0.02)


def test_watchdog_execute_deadline_is_soft():
    import time

    wd = Watchdog(execute_deadline=0.001)
    out = wd.guard_execute(lambda: (time.sleep(0.01), "kept")[1])
    assert out == "kept"  # results preserved, breach only counted
    assert wd.execute_deadline_misses == 1


def test_policy_absorbs_solver_timeout_via_degradation_chain():
    calls = {"n": 0}

    def stall_once():
        calls["n"] += 1
        return 0.02 if calls["n"] == 1 else 0.0

    wd = Watchdog(solve_deadline=0.002, stall_hook=stall_once)
    pol = CannikinPolicy(8, engine="batched", watchdog=wd)
    spec = random_jobs(1, 8, seed=0)[0]
    alloc = pol.add_job(spec)
    assert wd.solver_timeouts == 1
    assert pol.engine_degradations >= 1        # timeout walked the chain
    assert alloc.assignment[spec.name]         # job still placed
    assert pol.counters()["solver_timeouts"] == 1


def test_runtime_builds_watchdog_from_stall_plan():
    plan = FaultPlan(solver_stalls=(SolverStall(at_epoch=0, delay=0.05),))
    rt = ClusterRuntime(4, faults=plan)
    assert rt.watchdog is not None
    assert rt.watchdog.solve_deadline == pytest.approx(0.025)
    # Explicit opt-out wins over the plan.
    assert ClusterRuntime(4, faults=plan, watchdog=False).watchdog is None
    # No stalls scheduled -> no watchdog by default.
    assert ClusterRuntime(4, faults=FaultPlan()).watchdog is None


# ---------------------------------------------------------------------------
# runtime invariant checker
# ---------------------------------------------------------------------------


def _sim_runtime():
    rt = ClusterRuntime(8, policy="cannikin", seed=0, health=True, invariants=True)
    for spec in random_jobs(2, 8, seed=0):
        rt.submit(spec, at=0.0)
    rt.run()
    rt.advance(epochs=1, steps=2)
    return rt


def test_invariant_checker_clean_on_healthy_runtime():
    rt = _sim_runtime()
    assert rt.invariant_checker is not None
    assert rt.invariant_checker.checks_run > 0
    rt.invariant_checker.assert_clean()
    assert rt.invariant_violations == []


def test_invariant_checker_flags_hand_broken_state():
    rt = _sim_runtime()
    checker = rt.invariant_checker
    names = list(rt.allocation.assignment)
    a, b = names[0], names[1]

    # Assign one of b's nodes to a as well: disjointness broken.
    stolen = rt.allocation.assignment[b][0]
    rt.allocation.assignment[a] = tuple(rt.allocation.assignment[a]) + (stolen,)
    found = checker.check("tamper:double-assign")
    assert any(v.invariant == "disjoint-assignment" for v in found)
    rt.allocation.assignment[a] = tuple(
        n for n in rt.allocation.assignment[a] if n != stolen
    )

    # Quarantine a node, then erase its re-admission: liveness broken.
    h = rt.health.node(0)
    h.transition(0, NodeState.QUARANTINED)
    h.release_epoch = None
    h.backoff = 2 * rt.health.config.backoff_max  # and blow the cap
    found = checker.check("tamper:quarantine")
    kinds = {v.invariant for v in found}
    assert "quarantine-liveness" in kinds and "backoff-cap" in kinds
    assert len(rt.invariant_violations) >= 3
    with pytest.raises(AssertionError):
        checker.assert_clean()


# ---------------------------------------------------------------------------
# NaN-safe goodput retention (satellite 2)
# ---------------------------------------------------------------------------


class _StubRuntime:
    def __init__(self, *sim_times):
        class _H:
            def __init__(self, t):
                self.sim_time = t

        self.handles = {f"j{i}": _H(t) for i, t in enumerate(sim_times)}


def _report(faulted_times, baseline_times):
    base = TraceReport(policy="p", records=[], runtime=_StubRuntime(*baseline_times))
    return TraceReport(
        policy="p", records=[], runtime=_StubRuntime(*faulted_times), baseline=base
    )


def test_goodput_retention_degenerate_traces_warn_not_nan():
    with pytest.warns(RuntimeWarning, match="0/0"):
        assert _report((), ()).goodput_retention == 1.0
    with pytest.warns(RuntimeWarning, match="faulted replay"):
        assert _report((0.0,), (5.0,)).goodput_retention == 0.0
    with pytest.warns(RuntimeWarning, match="fault-free twin"):
        assert _report((5.0,), (0.0,)).goodput_retention == 0.0
    # Healthy case: no warning, plain ratio.
    assert _report((10.0,), (8.0,)).goodput_retention == pytest.approx(0.8)
    # No baseline: undefined, not fabricated.
    assert TraceReport("p", [], _StubRuntime(1.0)).goodput_retention is None


# ---------------------------------------------------------------------------
# RealBackend integration (slow lane: compiles JAX steps)
# ---------------------------------------------------------------------------


def _tiny_real_spec():
    models = tuple(
        GPU_CATALOG[name].model() for name in ("a100", "v100", "rtx6000")
    )
    return JobSpec(
        name="rj",
        node_models=models,
        comm=CommModel(t_o=0.04, t_u=0.008, gamma=0.15),
        total_batch=12,
        b_noise=500.0,
        ref_batch=12,
        backend="real",
    )


def _real_config():
    return RealBackendConfig(arch="olmo-1b", reduced=True, seq_len=16, lr=0.3)


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.slow
def test_real_backend_poison_excluded_counted_and_contained():
    """A NaN-poisoned node's gradients never reach the Eq. (9) aggregate:
    losses and params stay finite, the anomaly is counted per node, and
    GradObservation.valid marks the exclusion."""
    pytest.importorskip("jax")
    plan = FaultPlan(
        poisons=(GradientPoison(node=1, at_epoch=0, duration=1, mode="nan"),)
    )
    inj = FaultInjector(plan)
    backend = _real_config().build(noise=0.0, seed=1, injector=inj)
    backend.configure(_tiny_real_spec(), (0, 1, 2), seed=1)
    inj.begin_epoch(0)
    res = backend.execute([4, 4, 4], steps=2)
    assert all(math.isfinite(x) for x in res.losses)
    assert all(np.isfinite(leaf).all() for leaf in _leaves(backend.params))
    assert res.grad_anomalies == (0, 2, 0)  # both steps excluded node 1
    for obs in res.grad_observations:
        assert obs.valid == (True, False, True)
        assert not obs.all_valid
    # Poison window closed: the guard re-admits the node.
    inj.begin_epoch(1)
    res2 = backend.execute([4, 4, 4], steps=2)
    assert res2.grad_anomalies == (0, 0, 0)
    assert all(o.all_valid for o in res2.grad_observations)


@pytest.mark.slow
def test_real_backend_idle_injector_is_bit_identical():
    """The guard + injector seam are always compiled in; with an empty
    plan the produced params are bitwise identical to a no-injector run."""
    pytest.importorskip("jax")
    spec = _tiny_real_spec()
    plain = _real_config().build(noise=0.0, seed=3)
    seamed = _real_config().build(
        noise=0.0, seed=3, injector=FaultInjector(FaultPlan())
    )
    plain.configure(spec, (0, 1, 2), seed=3)
    seamed.configure(spec, (0, 1, 2), seed=3)
    ra = plain.execute([4, 4, 4], steps=2)
    rb = seamed.execute([4, 4, 4], steps=2)
    assert ra.epoch_seconds == rb.epoch_seconds
    assert ra.measurements == rb.measurements
    assert ra.losses == rb.losses
    for a, b in zip(_leaves(plain.params), _leaves(seamed.params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_real_backend_routes_timing_faults_through_injector():
    """Satellite 1 regression: timing faults (here a straggler) perturb the
    REAL backend's measurement stream through the same injector seam the
    simulator uses — before this PR they were silently ignored."""
    pytest.importorskip("jax")
    spec = _tiny_real_spec()
    # 30x: slow enough that the synchronous step actually waits on the
    # straggler (a mild slowdown hides inside the comm-bound batch time).
    inj = FaultInjector(
        FaultPlan(
            stragglers=(Straggler(node=0, at_epoch=0, duration=1, slowdown=30.0),)
        )
    )
    clean = _real_config().build(noise=0.0, seed=1)
    faulted = _real_config().build(noise=0.0, seed=1, injector=inj)
    clean.configure(spec, (0, 1, 2), seed=1)
    faulted.configure(spec, (0, 1, 2), seed=1)
    inj.begin_epoch(0)
    rc = clean.execute([4, 4, 4], steps=2)
    rf = faulted.execute([4, 4, 4], steps=2)
    assert rf.epoch_seconds > rc.epoch_seconds
    c0 = rc.measurements[0].observations[0]
    f0 = rf.measurements[0].observations[0]
    assert f0.a_time == pytest.approx(30.0 * c0.a_time)
    assert {f["kind"] for f in inj.injected} == {"straggler"}
    # Timing-only faults leave the gradients untouched.
    assert rf.losses == rc.losses
    assert rf.grad_anomalies == (0, 0, 0)
