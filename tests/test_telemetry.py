"""RealBackend observability: the ``telemetry`` counters (padded rows,
observed transfers, step programs built), the named scopes of the step
program, and the ``repro.`` profiler spans of the epoch and the step."""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

jax = pytest.importorskip("jax")

from repro.core.controller import CannikinController  # noqa: E402
from repro.core.perf_model import CommModel  # noqa: E402
from repro.core.scheduler import JobSpec  # noqa: E402
from repro.core.simulator import GPU_CATALOG  # noqa: E402
from repro.runtime import EpochLoop, RealBackendConfig  # noqa: E402
from repro.runtime.backend import STEP_SCOPES  # noqa: E402

SPLIT = [14, 7, 3]          # widths 16/8/8: 32 rows computed for 24
SEQ = 16


def _backend(*, sharded: bool = False):
    spec = JobSpec(
        name="telemetry-job",
        node_models=tuple(GPU_CATALOG[g].model() for g in ("a5000", "a4000", "p4000")),
        comm=CommModel(t_o=0.04, t_u=0.008, gamma=0.15),
        total_batch=12,
        b_noise=500.0,
        ref_batch=12,
        backend="real",
    )
    backend = RealBackendConfig(
        arch="olmo-1b", reduced=True, seq_len=SEQ, lr=0.3, sharded=sharded
    ).build(noise=0.0, seed=0)
    backend.configure(spec, (0, 1, 2), seed=1)
    return backend


def _loop(*, fused: bool):
    backend = _backend()
    ctrl = CannikinController(
        3, batch_candidates=[12, 24, 36], ref_batch=12, adaptive=True
    )
    return ctrl, backend, EpochLoop(ctrl, backend, steps_per_epoch=3, fused=fused)


def test_two_program_epoch_counts_padded_rows():
    backend = _backend()
    backend.execute(SPLIT, 2)
    t = backend.telemetry.snapshot()
    assert t["steps"] == 2
    assert t["rows_computed"] == 2 * 32
    assert t["rows_useful"] == 2 * 24
    assert t["steps_ragged"] == 2


def test_two_program_epoch_at_one_width_counts_no_ragged_step():
    backend = _backend()
    backend.execute([4, 4, 4], 2)
    t = backend.telemetry.snapshot()
    assert t["steps"] == 2
    assert t["rows_computed"] == 2 * 24
    assert t["rows_useful"] == 2 * 12
    assert t["steps_ragged"] == 0


def test_observed_transfers_per_step_are_four_each_way():
    backend = _backend()
    backend.execute(SPLIT, 1)  # build the step program
    for steps in (1, 3):
        backend.telemetry.reset()
        backend.execute(SPLIT, steps)
        t = backend.telemetry.snapshot()
        # tok, lab, msk and the lr scalar per step; r and poison per epoch.
        assert t["h2d_arrays"] == 4 * steps + 2
        assert t["h2d_bytes"] == steps * (2 * 3 * 16 * SEQ * 4 + 3 * 16 * 4 + 4) + 2 * 3 * 4
        # valid (3 bools), loss, sq_i (3 floats), sq_g per step.
        assert t["d2h_arrays"] == 4 * steps
        assert t["d2h_bytes"] == steps * (3 + 4 + 3 * 4 + 4)


def test_fused_epoch_counts_rows_per_scanned_step():
    ctrl, backend, loop = _loop(fused=True)
    for _ in range(8):
        before = backend.telemetry.snapshot()
        certified = ctrl.stats.fused_certifications
        rec = loop.run_epoch()
        if ctrl.stats.fused_certifications > certified:  # it ran fused
            break
    else:
        pytest.fail("the loop never ran a fused epoch")
    after = backend.telemetry.snapshot()
    d = {k: after[k] - before[k] for k in after}
    b_max = max(8, -(-max(rec.batches) // 8) * 8)
    assert d["steps"] == 3
    assert d["rows_useful"] == 3 * sum(rec.batches)
    assert d["rows_computed"] == 3 * 3 * b_max
    assert d["steps_ragged"] == 0  # the fused epoch runs the uniform layout
    # One shipment of the epoch (10 arrays) and one 13-leaf bundle back.
    assert d["h2d_arrays"] == 10 and d["d2h_arrays"] == 13


def test_programs_built_counts_step_cache_misses():
    backend = _backend()
    backend.execute(SPLIT, 1)
    assert backend.telemetry.programs_built == 1
    backend.execute([13, 8, 3], 1)  # same padded width: cached
    assert backend.telemetry.programs_built == 1
    backend.execute([4, 4, 4], 1)   # b_max 8: a new program
    assert backend.telemetry.programs_built == 2


def test_splits_of_equal_widths_share_a_program():
    backend = _backend()
    for split in ([14, 7, 3], [15, 7, 2]):   # widths 16/8/8
        backend.execute(split, 1)
    assert backend.telemetry.programs_built == 1
    for split in ([4, 4, 4], [5, 5, 2]):     # widths 8/8/8
        backend.execute(split, 1)
    assert backend.telemetry.programs_built == 2
    assert set(backend._step_cache) == {(16, 8, 8), (8, 8, 8)}
    assert backend.telemetry.steps_ragged == 2


@pytest.mark.parametrize(
    "sharded, widths",
    [(False, (16, 16, 16)), (True, None), (False, (16, 8, 8))],
    ids=["False", "True", "ragged"],
)
def test_lowered_step_carries_the_phase_scopes(sharded, widths):
    backend = _backend(sharded=sharded)
    n, b_max = 3, 16
    fn = backend._node_grad_fn_sharded(n) if sharded else backend._node_grad_fn(widths)
    tok = np.zeros((n, b_max, SEQ), np.int32)
    vec = np.ones((n,), np.float32)
    text = fn.lower(
        backend.params, backend.opt_state, tok, tok,
        np.ones((n, b_max), np.float32), vec / n, np.float32(1.0), vec,
    ).as_text(debug_info=True)
    for scope in STEP_SCOPES:
        assert f"/{scope}/" in text, scope


def _host_spans(trace_dir):
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return spans


def test_profiler_trace_nests_step_spans_in_the_epoch(tmp_path):
    _, backend, loop = _loop(fused=False)
    loop.run_epoch()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop.run_epoch()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    assert {"repro.epoch", "repro.epoch.plan", "repro.epoch.execute",
            "repro.epoch.observe"} <= names

    def inside(child, parents):
        return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)

    (epoch,) = [s for s in spans if s[0] == "repro.epoch"]
    assert epoch[3]["epoch"] == 1
    execute = [s for s in spans if s[0] == "repro.epoch.execute"]
    steps = [s for s in spans if s[0] == "repro.step"]
    assert len(steps) == 3 and inside(execute[0], [epoch])
    assert all(inside(s, execute) for s in steps)
    for phase in ("input", "put", "dispatch", "pull", "gns"):
        kids = [s for s in spans if s[0] == f"repro.step.{phase}"]
        assert len(kids) == 3, phase
        for k in kids:
            same = [s for s in steps if s[3]["step_num"] == k[3]["step_num"]]
            assert inside(k, same), (phase, k)
