"""ExecutionBackend acceptance tests: SimBackend replay parity with the
pre-refactor runtime (golden values), RealBackend gradient/GNS/clock
behaviour, preemption checkpoint/restore bit-exactness, the synthetic-trace
arrival/size distributions, and the make_policy deprecation shim."""
import math
import os
import warnings

import numpy as np
import pytest

from repro.core.perf_model import CommModel
from repro.core.scheduler import JobSpec, random_jobs
from repro.core.simulator import GPU_CATALOG
from repro.runtime import (
    ClusterRuntime,
    EpochRecord,
    JobState,
    RealBackendConfig,
    SimBackend,
    make_backend,
    replay,
    synthetic_trace,
)

N_NODES = 12


# ---------------------------------------------------------------------------
# SimBackend: bit-identical to the pre-refactor JobHandle.advance path
# ---------------------------------------------------------------------------

# Golden values captured by running the PR-4 (pre-ExecutionBackend) runtime
# on this exact scenario: synthetic_trace(3, 12, seed=0) replayed with
# policy="cannikin", epochs_per_event=2, steps=2, noise=0.01, seed=0.
_GOLDEN_AGG_GOODPUT = 2125.4784947969247
_GOLDEN_AGG_FRACTION = 1.0928105167204858
_GOLDEN_ASSIGNMENT = {"job1": (1, 5, 7, 8, 9, 10), "job2": (0, 2, 3, 4, 6)}
_GOLDEN_EPOCHS = {"job0": 6, "job1": 8, "job2": 6}
_GOLDEN_COUNTERS = {
    "allocations": 5,
    "warm_rounds": 28,
    "cold_rounds": 3,
    "solved_rows": 372,
    "cached_rows": 396,
}
_GOLDEN_SIM_TIME = {
    "job0": 2.780991958839693,
    "job1": 15.168174637445608,
    "job2": 33.567468442725044,
}
_GOLDEN_LAST_BATCHES = {
    "job0": (92, 102, 29, 33),
    "job1": (187, 188, 629, 175, 362, 507),
    "job2": (654, 559, 216, 147, 472),
}
_GOLDEN_GOODPUTS = {"job1": 1619.3591772804705, "job2": 506.11931751645443}


def test_sim_backend_replay_bit_identical_to_pre_refactor_golden():
    """A 2-epoch-per-event run through JobHandle.advance on SimBackend is
    bit-identical — allocations, counters, plans, simulated clocks — to the
    pre-refactor (controller + SimulatedCluster inlined) path."""
    trace, _ = synthetic_trace(3, N_NODES, seed=0)
    rep = replay(
        trace, N_NODES, policy="cannikin", epochs_per_event=2, steps=2,
        noise=0.01, seed=0,
    )
    s = rep.summary()
    assert s["aggregate_goodput"] == _GOLDEN_AGG_GOODPUT
    assert s["aggregate_fraction"] == _GOLDEN_AGG_FRACTION
    assert rep.runtime.allocation.assignment == _GOLDEN_ASSIGNMENT
    assert s["epochs"] == _GOLDEN_EPOCHS
    assert s["counters"] == _GOLDEN_COUNTERS
    for name, handle in rep.runtime.handles.items():
        assert handle.sim_time == _GOLDEN_SIM_TIME[name], name
        assert handle.last_plan.batches == _GOLDEN_LAST_BATCHES[name], name
        # Unified telemetry: every advanced epoch left an EpochRecord whose
        # plan/clock agree with the controller surface.
        assert len(handle.records) == handle.epochs_run
        assert all(r.backend == "sim" for r in handle.records)
        assert handle.records[-1].batches == handle.last_plan.batches
        assert handle.sim_time == pytest.approx(
            sum(r.epoch_seconds for r in handle.records)
        )
        assert math.isnan(handle.records[-1].mean_loss)  # sim: no gradients
    for name, g in _GOLDEN_GOODPUTS.items():
        assert rep.runtime.allocation.goodputs[name] == g


def test_sim_backend_direct_and_factory():
    spec = random_jobs(1, 4, seed=3)[0]
    backend = make_backend("sim", noise=0.0, seed=0)
    assert isinstance(backend, SimBackend)
    with pytest.raises(RuntimeError):
        backend.execute([2, 2], 1)
    backend.configure(spec, (0, 1, 2, 3), seed=5)
    result = backend.execute([4, 4, 4, 4], steps=3)
    assert len(result.measurements) == 3
    assert result.epoch_seconds > 0
    assert math.isnan(result.b_noise) and math.isnan(result.mean_loss)
    assert result.grad_observations == ()
    assert backend.snapshot() == {}  # nothing statistical to persist
    with pytest.raises(ValueError):
        make_backend("quantum")


def test_jobspec_backend_field_defaults_and_stamps():
    spec = random_jobs(1, 4, seed=1)[0]
    assert spec.backend == "sim"
    _, jobs = synthetic_trace(2, 6, seed=0, backend="real", total_batch=16)
    assert all(j.backend == "real" and j.total_batch == 16 for j in jobs)


class _FakeBackend:
    kind = "stale"

    def __init__(self):
        self.snaps = 0
        self.value = 0

    def configure(self, spec, node_ids, *, seed=0):
        pass

    def execute(self, batches, steps, *, lr_scale=1.0):
        raise NotImplementedError

    def snapshot(self):
        self.snaps += 1
        return {"v": self.value}

    def load_snapshot(self, state):
        self.value = state["v"]


def test_preempt_snapshots_only_on_running_edge():
    """A duplicate Preemption must not re-serialize post-preemption live
    state over the good snapshot (the checkpoint models a process that
    already died); the event counter still counts every event."""
    from repro.runtime.runtime import JobHandle

    spec = random_jobs(1, 2, seed=0)[0]
    h = JobHandle(spec)
    h.set_nodes((0, 1))
    assert h.state == JobState.RUNNING
    h.backend = _FakeBackend()
    h.backend.value = 42
    h.preempt()
    assert h.backend.snaps == 1
    assert h._snapshot == {"v": 42}
    h.backend.value = 0          # live state diverges after preemption
    h.preempt()                  # duplicate event
    assert h.backend.snaps == 1  # not re-snapshotted
    assert h._snapshot == {"v": 42}
    assert h.preemptions == 2    # events still counted (reconcile semantics)


def test_bind_backend_rebuilds_on_kind_change():
    """Node churn keeps the backend object (statistical state survives),
    but a spec naming a different backend kind gets a fresh engine."""
    from repro.runtime.runtime import JobHandle

    spec = random_jobs(1, 3, seed=0)[0]
    h = JobHandle(spec)
    h.set_nodes((0, 1))
    first = h.backend
    assert isinstance(first, SimBackend)
    h.set_nodes((0, 1, 2))       # churn: same engine, reconfigured
    assert h.backend is first
    h.backend = _FakeBackend()   # stale kind vs spec.backend == "sim"
    h.set_nodes((0, 1))
    assert isinstance(h.backend, SimBackend)
    assert h.backend is not first


# ---------------------------------------------------------------------------
# synthetic_trace: arrival processes / job-size distributions (satellite)
# ---------------------------------------------------------------------------


def test_synthetic_trace_default_unchanged():
    """The fixed trace stays the default and is byte-for-byte what it was:
    no RNG draw may leak into the default path."""
    trace, jobs = synthetic_trace(3, N_NODES, seed=0)
    times = [e.time for e in trace]
    assert times == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert [j.total_batch for j in jobs] == [
        j.total_batch for j in random_jobs(3, N_NODES, 0)
    ]


def test_synthetic_trace_poisson_arrivals_seeded():
    t1, _ = synthetic_trace(4, 8, seed=3, arrival="poisson", departure=False,
                            node_leave=False)
    t2, _ = synthetic_trace(4, 8, seed=3, arrival="poisson", departure=False,
                            node_leave=False)
    times = [e.time for e in t1]
    assert times == [e.time for e in t2]          # seeded: reproducible
    assert times[0] == 0.0
    gaps = np.diff(times)
    assert (gaps > 0).all()                        # strictly increasing
    assert len(set(np.round(gaps, 12))) > 1        # not the fixed spacing
    t3, _ = synthetic_trace(4, 8, seed=4, arrival="poisson", departure=False,
                            node_leave=False)
    assert [e.time for e in t3] != times           # seed-sensitive
    with pytest.raises(ValueError):
        synthetic_trace(2, 8, arrival="uniform")


def test_synthetic_trace_lognormal_sizes_heavy_tailed():
    _, fixed = synthetic_trace(16, 8, seed=5, departure=False, node_leave=False)
    _, heavy = synthetic_trace(16, 8, seed=5, departure=False, node_leave=False,
                               size_dist="lognormal", size_sigma=1.0)
    assert [j.name for j in heavy] == [j.name for j in fixed]
    sizes = np.array([j.total_batch for j in heavy], dtype=float)
    assert (sizes >= np.array([j.ref_batch for j in heavy])).all()
    # Heavy tail: the multiplicative factors really spread (not all ~1).
    factors = sizes / np.array([j.total_batch for j in fixed], dtype=float)
    assert factors.max() / factors.min() > 3.0
    # Reproducible per seed.
    _, heavy2 = synthetic_trace(16, 8, seed=5, departure=False, node_leave=False,
                                size_dist="lognormal", size_sigma=1.0)
    assert [j.total_batch for j in heavy2] == [j.total_batch for j in heavy]
    with pytest.raises(ValueError):
        synthetic_trace(2, 8, size_dist="pareto")


def test_synthetic_trace_poisson_replays_through_runtime():
    trace, jobs = synthetic_trace(
        3, N_NODES, seed=2, arrival="poisson", size_dist="lognormal",
        size_sigma=0.8,
    )
    rep = replay(trace, N_NODES, policy="cannikin", epochs_per_event=1, steps=2)
    assert rep.aggregate_goodput > 0
    assert rep.runtime.handles[jobs[0].name].state == JobState.DONE


# ---------------------------------------------------------------------------
# make_policy deprecation shim (satellite)
# ---------------------------------------------------------------------------


def test_launch_make_policy_emits_deprecation_warning():
    from repro.launch.train import make_policy
    from repro.core.controller import CannikinController

    with pytest.deprecated_call(match="make_partition_policy"):
        policy = make_policy(
            "cannikin", 4, candidates=[32, 64], ref_batch=32, adaptive=True
        )
    assert isinstance(policy, CannikinController)
    # The replacement factory itself must stay warning-free.
    from repro.runtime import make_partition_policy

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        make_partition_policy("cannikin", 4, candidates=[32], ref_batch=32)


# ---------------------------------------------------------------------------
# RealBackend (slow lane: compiles JAX steps)
# ---------------------------------------------------------------------------


def _tiny_real_spec(total_batch=12, backend="real"):
    """3 heterogeneous nodes, CPU-sized batches."""
    models = tuple(
        GPU_CATALOG[name].model() for name in ("a100", "v100", "rtx6000")
    )
    return JobSpec(
        name="rj",
        node_models=models,
        comm=CommModel(t_o=0.04, t_u=0.008, gamma=0.15),
        total_batch=total_batch,
        b_noise=500.0,
        ref_batch=total_batch,
        backend=backend,
    )


def _real_config():
    return RealBackendConfig(arch="olmo-1b", reduced=True, seq_len=16, lr=0.3)


@pytest.mark.slow
def test_real_backend_tiny_dense_losses_gns_and_clock():
    """RealBackend on a tiny dense model: finite decreasing-ish losses, a
    positive b_noise from real gradient square-norms, and a monotone
    simulated clock."""
    pytest.importorskip("jax")
    from repro.core.controller import CannikinController
    from repro.runtime import EpochLoop

    spec = _tiny_real_spec()
    backend = _real_config().build(noise=0.0, seed=0)
    backend.configure(spec, (0, 1, 2), seed=1)
    ctrl = CannikinController(
        3, batch_candidates=[12, 24], ref_batch=12, adaptive=True
    )
    loop = EpochLoop(ctrl, backend, steps_per_epoch=2)
    records = loop.run(4)
    assert len(records) == 4
    assert all(isinstance(r, EpochRecord) and r.backend == "real" for r in records)
    assert all(np.isfinite(r.mean_loss) for r in records)
    assert records[-1].mean_loss < records[0].mean_loss
    # Theorem-4.1 tracking: both the backend tracker and the controller saw
    # real gradient telemetry.
    assert backend.gns.count > 0 and backend.gns.b_noise > 0
    assert np.isfinite(backend.gns.b_noise)
    assert ctrl.gns.count > 0 and records[-1].b_noise > 0
    # Monotone simulated clock.
    clocks = np.cumsum([r.epoch_seconds for r in records])
    assert (np.diff(clocks) > 0).all()
    assert backend.sim_time == pytest.approx(clocks[-1])
    assert backend.steps_done == 8


@pytest.mark.slow
def test_real_backend_checkpoint_roundtrip_bit_exact(tmp_path):
    """snapshot → file → load_snapshot restores params/opt-state/GNS/stream
    counters bit-exactly even after the live state was scrambled."""
    pytest.importorskip("jax")
    import jax

    from repro.core.gns import GNSState

    spec = _tiny_real_spec()
    backend = _real_config().build(noise=0.0, seed=0)
    backend.configure(spec, (0, 1, 2), seed=1)
    backend.execute([4, 4, 4], steps=2)
    path = os.path.join(tmp_path, "ck.npz")
    backend.checkpoint(path)
    want_params = jax.tree_util.tree_leaves(backend.params)
    want_gns, want_steps, want_sim = backend.gns, backend.steps_done, backend.sim_time

    backend.params = jax.tree_util.tree_map(lambda x: x + 1.0, backend.params)
    backend.gns = GNSState()
    backend.steps_done = 999
    backend.sim_time = 0.0
    backend.restore(path)

    got_params = jax.tree_util.tree_leaves(backend.params)
    for a, b in zip(want_params, got_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert backend.gns == want_gns
    assert backend.steps_done == want_steps
    assert backend.sim_time == want_sim


def _drive(preempt: bool, ckpt_dir, scramble: bool):
    rt = ClusterRuntime(
        3, policy="cannikin", seed=0,
        real_backend=_real_config(),
        checkpoint_dir=str(ckpt_dir) if preempt else None,
    )
    spec = _tiny_real_spec()
    handle = rt.submit(spec, at=0.0)
    rt.run()
    rt.advance(epochs=2, steps=2)
    if preempt:
        import jax

        from repro.core.gns import GNSState

        rt.preempt(spec.name, at=1.0)
        rt.run()
        assert handle.state == JobState.PREEMPTED
        assert handle.checkpoint_path is not None
        assert os.path.exists(handle.checkpoint_path)
        if scramble:
            # The in-process state is clobbered: only the checkpoint can
            # make resume correct.
            handle.backend.params = jax.tree_util.tree_map(
                lambda x: x * 0.0, handle.backend.params
            )
            handle.backend.gns = GNSState()
            handle.backend.steps_done = 0
        rt.submit(spec, at=2.0)  # JobCompletion-free resume
        rt.run()
        assert handle.state == JobState.RUNNING
    rt.advance(epochs=2, steps=2)
    return handle


@pytest.mark.slow
def test_runtime_preemption_checkpoint_restore_bit_exact(tmp_path):
    """Preemption → resume on RealBackend restores params/opt-state/GNS
    state from the checkpoint file bit-exactly: the preempted-and-resumed
    run finishes with the same losses and parameters as an unpreempted run
    with the same seed and plans — even though the live backend state was
    zeroed between preempt and resume."""
    pytest.importorskip("jax")
    import jax

    plain = _drive(preempt=False, ckpt_dir=tmp_path, scramble=False)
    resumed = _drive(preempt=True, ckpt_dir=tmp_path, scramble=True)

    assert plain.epochs_run == resumed.epochs_run == 4
    assert resumed.preemptions == 1
    # Same plans on both sides (single job -> full cluster both times).
    assert [r.batches for r in plain.records] == [
        r.batches for r in resumed.records
    ]
    # Same final losses, bit for bit.
    assert [r.mean_loss for r in plain.records] == [
        r.mean_loss for r in resumed.records
    ]
    # Same final parameters and GNS state, bit for bit.
    for a, b in zip(
        jax.tree_util.tree_leaves(plain.backend.params),
        jax.tree_util.tree_leaves(resumed.backend.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert plain.backend.gns == resumed.backend.gns
    assert plain.backend.steps_done == resumed.backend.steps_done
