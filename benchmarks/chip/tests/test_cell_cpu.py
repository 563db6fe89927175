"""The cell runner end to end on the CPU at each configuration's small
preset: set-up, window, result line and the comparison with the reference.
At float32 the program and the reference agree to rounding, so the cell's
own limits pass; with a fault planted in the program's timed path they
fail."""
import pytest

from drive import FAULTS, run_tiny

ONE_CHIP = ["olmo-1b.clusterA-adaptive"]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_one_chip_cell_runs_and_agrees_with_reference(cell):
    r = run_tiny(cell)
    assert list(r) == KEYS
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["tokens_per_s"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert all(c["value"] < 1e-4 for k, c in r["checks"].items()
               if k not in ("split_gap", "total_gap")), r["checks"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_traced_run_reports_per_layer_metrics(cell):
    r = run_tiny(cell, trace=True)
    # No TPU plane on the CPU: the device readers find nothing and are left out.
    assert set(r["metrics"]) == {"controller_ms_per_epoch"}
    assert r["metrics"]["controller_ms_per_epoch"]["value"] > 0


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_one_chip_fault_is_not_correct(cell, fault):
    r = run_tiny(cell, fault)
    assert r["correct"] is False, r["checks"]

