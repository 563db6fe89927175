"""The control: the reference computed with float8 matmul operands, put in
the program's place, fails at least one of each cell's limits; the float32
reference against itself passes all of them."""
import pytest

from chipbench import spec
from chipbench.reference import RefTrainer, compare
from chipbench.traffic import make_stream
from presets import tiny

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2**31 + 5


def _run(cell, precision):
    cfg, mix = tiny(cell, "bfloat16")
    split = mix["check_split"]
    stream = make_stream(mix, cfg["vocab"], SEED)
    batches = [stream.batch(s, sum(split)) for s in range(3)]
    return RefTrainer(cfg, precision=precision).run(SEED, batches, split, mix["lr"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell):
    limits = spec.cell(cell)["limits"]["limits"]
    ref = _run(cell, "f32")
    ctl = compare(_run(cell, "fp8"), ref)
    same = compare(ref, ref)
    assert all(same[k]["value"] <= limits[k] for k in same)
    assert any(ctl[k]["value"] > limits[k] for k in ctl), ctl
