"""The float64 references of the controller's noise estimate and plan: they
agree with the program's controller where it is sound, and the control
(float32) and each planted fault read above the cell's limits."""
import numpy as np
import pytest

from chipbench import spec
from chipbench.planner import best_split_time, compare_plans, gns_trajectory, step_time

CELL = "olmo-1b.clusterA-adaptive"
MIX = spec.cell(CELL)["mix"]
LIMITS = spec.cell(CELL)["limits"]["limits"]
# The split the controller plans for cluster A at total 24: its water-fill
# (14.46, 7.08, 2.47) rounded; the best integer split is 15/7/2, 2.66%
# faster, so the rounding alone reads a split gap of 0.0266.
PLAN = [14, 7, 3]
BEST = [15, 7, 2]


def _steps(seed, splits, b_noise=650.0):
    """Gradient square norms as a noise scale ``b_noise`` would give them."""
    rng = np.random.default_rng(seed)
    out = []
    for split in splits:
        b = np.asarray(split, np.float64)
        local = 1.0 + b_noise / b * rng.chisquare(50, b.size) / 50
        glob = 1.0 + b_noise / b.sum() * rng.chisquare(50) / 50
        out.append({"local_sqnorms": local.tolist(), "global_sqnorm": float(glob),
                    "batches": [int(x) for x in split], "valid": [True] * b.size})
    return out


def _program_estimates(steps, decay):
    from repro.core.gns import GNSState, estimate_gns, gns_update

    state, out = GNSState(), []
    for st in steps:
        _, g, s = estimate_gns(st["local_sqnorms"], st["global_sqnorm"], st["batches"])
        state = gns_update(state, g, s, decay=decay)
        out.append(state.b_noise)
    return out


def _record(seed, window_splits, steps_per_epoch=8):
    splits = [[4, 4, 4], [5, 5, 2]] + window_splits
    epochs = []
    for k, split in enumerate(splits):
        steps = _steps(seed * 100 + k, [split] * steps_per_epoch)
        epochs.append({"batches": split, "total": sum(split), "window": k >= 2,
                       "steps": steps})
    flat = [st for e in epochs for st in e["steps"]]
    est = _program_estimates(flat, MIX["gns_decay"])
    for k, e in enumerate(epochs):
        e["b_noise"] = est[(k + 1) * steps_per_epoch - 1]
    return {"epochs": epochs}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_estimate_matches_the_programs_estimator(seed):
    steps = _steps(seed, [[14, 7, 3]] * 20 + [[15, 7, 2]] * 20)
    ref = np.asarray(gns_trajectory(steps, MIX["gns_decay"]))
    prog = np.asarray(_program_estimates(steps, MIX["gns_decay"]))
    np.testing.assert_allclose(ref, prog, rtol=1e-12)


def test_best_split_beats_or_ties_every_split_tried():
    best = best_split_time(MIX, 24)
    assert best == pytest.approx(float(step_time(MIX, np.asarray([BEST]))[0]))
    assert float(step_time(MIX, np.asarray([PLAN]))[0]) / best - 1 == pytest.approx(0.0266, abs=1e-4)
    rng = np.random.default_rng(0)
    for _ in range(200):
        a, b = sorted(rng.integers(1, 24, 2))
        split = np.asarray([a, b - a, 24 - b])
        if split.min() >= 1 and split.max() <= MIX["max_local"]:
            assert float(step_time(MIX, split[None])[0]) >= best - 1e-15


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sound_plans_pass_and_faults_fail(seed):
    sound = compare_plans(MIX, _record(seed, [PLAN] * 3))
    assert all(v["value"] <= LIMITS[k] for k, v in sound.items()), sound
    assert sound["total_gap"]["value"] == 0.0
    assert compare_plans(MIX, _record(seed, [BEST] * 3))["split_gap"]["value"] == 0.0
    even = compare_plans(MIX, _record(seed, [[8, 8, 8]] * 3))
    assert even["split_gap"]["value"] > LIMITS["split_gap"], even
    fixed = compare_plans(MIX, _record(seed, [[8, 3, 1]] * 3))
    assert fixed["total_gap"]["value"] > LIMITS["total_gap"], fixed
    control = compare_plans(MIX, _record(seed, [PLAN] * 3), gns_dtype=np.float32)
    assert control["gns_gap"]["value"] > LIMITS["gns_gap"], control
    plain = compare_plans(MIX, _record(seed, [PLAN] * 3), gns_weights="plain")
    assert plain["gns_gap"]["value"] > LIMITS["gns_gap"], plain
