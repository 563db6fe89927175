"""Fused on-device epoch + sharded RealBackend acceptance tests.

Covers the PR's tentpole contracts: fused-mode plans are bit-compatible
with the two-program path (and certified against the host float64 oracle
within 1e-5), the shard_map per-node backward matches the single-device
vmap backward, the fused epoch program compiles once, the fused path
eliminates the per-step host<->device transfer traffic, and a sharded
backend's checkpoint generations round-trip bit-exactly through
``CheckpointManager``.

The default test image has one CPU device (the sharded path then runs with
a size-1 mesh); the CI ``multi-device-smoke`` lane re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where the node axis
is genuinely split.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

jax = pytest.importorskip("jax")

from repro.core.controller import CannikinController, FUSED_CERT_TOL  # noqa: E402
from repro.core.perf_model import CommModel  # noqa: E402
from repro.core.scheduler import JobSpec  # noqa: E402
from repro.core.simulator import GPU_CATALOG  # noqa: E402
from repro.runtime import EpochLoop, RealBackendConfig  # noqa: E402

N_EPOCHS = 7
STEPS = 3


def _spec(n: int = 3, total_batch: int = 12) -> JobSpec:
    names = ("a100", "v100", "rtx6000", "a5000", "a4000", "p4000", "a100", "v100")
    models = tuple(GPU_CATALOG[name].model() for name in names[:n])
    return JobSpec(
        name="fused-job",
        node_models=models,
        comm=CommModel(t_o=0.04, t_u=0.008, gamma=0.15),
        total_batch=total_batch,
        b_noise=500.0,
        ref_batch=total_batch,
        backend="real",
    )


def _run_loop(*, fused: bool, sharded: bool = False, n: int = 3,
              total: int = 12, candidates=(12, 24, 36)):
    spec = _spec(n, total_batch=total)
    backend = RealBackendConfig(
        arch="olmo-1b", reduced=True, seq_len=16, lr=0.3, sharded=sharded
    ).build(noise=0.0, seed=0)
    backend.configure(spec, tuple(range(n)), seed=1)
    ctrl = CannikinController(
        n, batch_candidates=list(candidates), ref_batch=total, adaptive=True
    )
    loop = EpochLoop(ctrl, backend, steps_per_epoch=STEPS, fused=fused)
    loop.run(N_EPOCHS)
    return ctrl, backend, loop


def test_fused_plans_match_two_program_path():
    """Acceptance: a fused-mode loop produces the same plan sequence —
    total batch, per-node partition, lr scale — as the two-program loop on
    the same seeds, every on-device proposal certifies against the host
    float64 oracle within 1e-5, and the fused epoch program compiles once."""
    ctrl_two, backend_two, loop_two = _run_loop(fused=False)
    ctrl_fused, backend_fused, loop_fused = _run_loop(fused=True)
    rec_two, rec_fused = loop_two.history, loop_fused.history

    assert len(rec_two) == len(rec_fused) == N_EPOCHS
    for a, b in zip(rec_two, rec_fused):
        assert a.total_batch == b.total_batch
        assert a.batches == b.batches
        # The fused plan's LR rule is evaluated at the device-estimated
        # (float32 EMA) noise scale; the two-program plan at the host
        # float64 EMA — same rule, ~1e-8 relative drift.
        assert b.lr_scale == pytest.approx(a.lr_scale, rel=1e-6)
    # Fused mode actually engaged (after the bootstrap/first adaptive epoch)
    # and every staged proposal certified.
    s = ctrl_fused.stats
    assert s.fused_plans >= 1
    assert s.fused_certifications >= s.fused_plans
    assert s.fused_cert_failures == 0
    assert s.fused_max_rel_err <= FUSED_CERT_TOL
    assert not ctrl_fused._fused_disabled
    # Losses agree with the two-program path (same step body, scanned).
    la = np.asarray([r.mean_loss for r in rec_two])
    lb = np.asarray([r.mean_loss for r in rec_fused])
    np.testing.assert_allclose(lb, la, rtol=1e-5, atol=1e-6)
    # One fused epoch program for the whole run: a single (n, shard) cache
    # entry whose jit traced exactly once across all fused epochs.
    assert len(backend_fused._fused_cache) == 1
    (fn,) = backend_fused._fused_cache.values()
    assert fn._cache_size() == 1


def test_fused_epoch_cuts_transfers_per_epoch():
    """The fused program ships the epoch once and pulls one telemetry
    bundle: at least 2x fewer host<->device transfers per adaptive epoch
    than the two-program path (the bench gate, asserted here at test
    scale)."""
    _, backend_two, loop_two = _run_loop(fused=False)
    ctrl_fused, backend_fused, loop_fused = _run_loop(fused=True)
    assert ctrl_fused.stats.fused_plans >= 1

    # Marginal cost of one more adaptive (post-bootstrap) epoch per loop.
    # The two-program path pays per step (4 h2d + 4 d2h), the fused path a
    # flat 23/epoch, so the gate needs a realistic step count to bind.
    loop_two.steps_per_epoch = loop_fused.steps_per_epoch = 16
    backend_two.telemetry.reset()
    backend_fused.telemetry.reset()
    loop_two.run_epoch()
    rec = loop_fused.run_epoch()
    assert rec.plan.batch_policy.endswith("+fused")
    two = backend_two.telemetry.snapshot()
    fused = backend_fused.telemetry.snapshot()
    total = lambda s: s["h2d_arrays"] + s["d2h_arrays"]  # noqa: E731
    assert total(fused) * 2 <= total(two)


def test_sharded_backward_matches_vmap_backward():
    """shard_map-vs-vmap parity: the sharded per-node backward (psum'd
    Eq. 9 aggregation, composed global loss) reproduces the single-device
    vmap backward — losses within 1e-6, final parameters and gradient
    telemetry matching — on the same seeds and plans."""
    n = 4
    spec = _spec(n, total_batch=16)
    plans = [[4, 4, 4, 4], [6, 4, 3, 3], [2, 6, 5, 3]]

    def drive(sharded: bool):
        backend = RealBackendConfig(
            arch="olmo-1b", reduced=True, seq_len=16, lr=0.3, sharded=sharded
        ).build(noise=0.0, seed=0)
        backend.configure(spec, tuple(range(n)), seed=1)
        results = [backend.execute(p, steps=2) for p in plans]
        return backend, results

    b_vmap, r_vmap = drive(sharded=False)
    b_shard, r_shard = drive(sharded=True)

    for rv, rs in zip(r_vmap, r_shard):
        np.testing.assert_allclose(
            np.asarray(rs.losses), np.asarray(rv.losses), rtol=1e-6
        )
    # Same learned parameters after three epochs of heterogeneous plans.
    for a, b in zip(
        jax.tree_util.tree_leaves(b_vmap.params),
        jax.tree_util.tree_leaves(b_shard.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
    # Theorem-4.1 telemetry matches: same noise-scale estimate.
    assert b_vmap.gns.count == b_shard.gns.count
    assert b_vmap.gns.b_noise == pytest.approx(b_shard.gns.b_noise, rel=1e-3)


def test_sharded_fused_loop_runs_and_certifies():
    """Sharded backend + fused mode together (the full tentpole): the loop
    runs, fused plans engage, and certification stays within tolerance.
    Under the CI 8-device lane the node axis is genuinely split."""
    ctrl, backend, loop = _run_loop(
        fused=True, sharded=True, n=4, total=16, candidates=(16, 32)
    )
    records = loop.history
    assert len(records) == N_EPOCHS
    assert all(np.isfinite(r.mean_loss) for r in records)
    s = ctrl.stats
    assert s.fused_plans >= 1
    assert s.fused_cert_failures == 0
    assert s.fused_max_rel_err <= FUSED_CERT_TOL
    assert backend._mesh is not None


def test_sharded_checkpoint_roundtrip_bit_exact(tmp_path):
    """A sharded backend's snapshot gathers to host numpy, so the PR-7
    checkpoint generations stay byte-stable: save -> scramble -> restore
    through ``CheckpointManager`` recovers params/opt-state/GNS/counters
    bit-exactly, and training resumes."""
    from repro.core.gns import GNSState
    from repro.train.checkpoint import CheckpointManager

    n = 4
    spec = _spec(n, total_batch=16)
    backend = RealBackendConfig(
        arch="olmo-1b", reduced=True, seq_len=16, lr=0.3, sharded=True
    ).build(noise=0.0, seed=0)
    backend.configure(spec, tuple(range(n)), seed=1)
    backend.execute([4, 4, 4, 4], steps=2)

    snap = backend.snapshot()
    # Byte-stability contract: every leaf is host numpy, no device arrays.
    for leaf in jax.tree_util.tree_leaves(snap):
        assert isinstance(leaf, (np.ndarray, np.generic)), type(leaf)
    mgr = CheckpointManager(str(tmp_path), "fused-job", keep=2)
    mgr.save(snap)
    want_params = [np.asarray(x) for x in jax.tree_util.tree_leaves(snap["params"])]
    want_gns, want_steps = backend.gns, backend.steps_done

    backend.params = jax.tree_util.tree_map(lambda x: x * 0.0, backend.params)
    backend.gns = GNSState()
    backend.steps_done = 0
    tree, gen, _ = mgr.restore(backend.snapshot())
    backend.load_snapshot(tree)
    assert gen == 1

    got_params = [
        np.asarray(x) for x in jax.tree_util.tree_leaves(backend.params)
    ]
    for a, b in zip(want_params, got_params):
        np.testing.assert_array_equal(a, b)
    assert backend.gns == want_gns
    assert backend.steps_done == want_steps
    # The restored sharded backend keeps training.
    result = backend.execute([4, 4, 4, 4], steps=1)
    assert np.isfinite(result.mean_loss)
