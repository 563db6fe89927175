"""The step's device time by named scope and the device's idle time inside
the program's ``repro.`` spans (``chipbench.phases``), on hand-made traces
and on a small trace recorded on a TPU v5e (``data/trace_spans.json``: the
events of ``trace.load_events`` and, under ``scope_of``, the phase of each
op from the compiled step's HLO), and the six readers on a run's numbers."""
import json
from pathlib import Path

import pytest

from chipbench import phases as ph
from chipbench import spec as specs
from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "trace_spans.json"
ms = 1e6
SCOPE_OF = {"while.1": "node_backward", "fusion.2": "node_backward",
            "fusion.3": "loss_forward", "fusion.4": "eq9_aggregate",
            "fusion.5": "optimizer_update", "while.7": "node_backward"}


def _events():
    return {
        "device": {"/device:TPU:0": [
            ["while.1", 1 * ms, 5 * ms],
            ["fusion.2", 1.5 * ms, 3 * ms],      # the loop's body
            ["fusion.3", 5 * ms, 6 * ms],
            ["fusion.4", 6 * ms, 6.5 * ms],
            ["fusion.5", 6.5 * ms, 7 * ms],
            ["copy.6", 8 * ms, 9 * ms],          # no phase: other
            ["while.7", 9.5 * ms, 12 * ms],      # runs past the window
        ]},
        "host": [
            [tr.WINDOW_SPAN, 0.0, 10 * ms],
            ["repro.step", 0.0, 10 * ms],
            ["repro.step.input", 0.0, 1 * ms],
            ["repro.step.pull", 7 * ms, 8.5 * ms],
            ["repro.step.pull", 9 * ms, 9.2 * ms],
            ["repro.step.input", -2 * ms, -1 * ms],       # before the window
            ["bench.epoch", 0.0, 10 * ms],
        ],
    }


def test_scope_time_goes_to_the_innermost_op_and_adds_to_busy():
    ev = _events()
    r = ph.reduce_phases(ev, SCOPE_OF)
    got = {k: pytest.approx(v) for k, v in r["scope_s"].items()}
    assert got == {"node_backward": 4.5e-3, "loss_forward": 1e-3, "eq9_aggregate": 0.5e-3,
                   "optimizer_update": 0.5e-3, ph.OTHER: 1e-3}
    assert sum(r["scope_s"].values()) == pytest.approx(tr.reduce_events(ev)["busy_s"])


def test_a_while_and_its_body_count_once():
    # An unscoped scan (the fused epoch's) around a scoped body.
    ev = {"device": {"/device:TPU:0": [["while.1", 1 * ms, 5 * ms],
                                       ["fusion.2", 1.5 * ms, 3 * ms],
                                       ["while.3", 2 * ms, 2.5 * ms]]},
          "host": [[tr.WINDOW_SPAN, 0.0, 10 * ms]]}
    s = ph.reduce_phases(ev, {"fusion.2": "node_backward", "while.3": "node_backward"})
    assert s["scope_s"]["node_backward"] == pytest.approx(1.5e-3)
    assert s["scope_s"][ph.OTHER] == pytest.approx(2.5e-3)


def test_idle_inside_each_span_on_the_first_device():
    idle = ph.reduce_phases(_events(), SCOPE_OF)["idle_in_span"]
    # Busy 1-7, 8-9 and 9.5-10 ms: the input span idles 0-1, the two pulls
    # 7-8 and 9-9.2; the step span holds every idle instant of the window.
    assert idle == {"repro.step": pytest.approx(2.5e-3),
                    "repro.step.input": pytest.approx(1e-3),
                    "repro.step.pull": pytest.approx(1.2e-3)}


def test_no_scope_or_no_window_reads_nothing():
    ev = _events()
    assert ph.reduce_phases(ev, {})["scope_s"] is None
    ev["host"] = ev["host"][1:]
    assert ph.reduce_phases(ev, SCOPE_OF) is None


HLO = """
ENTRY %main.1 (p.1: bf16[8]) -> bf16[8] {
  %while.109 = (s32[], bf16[3,16]) while(%tuple.3), condition=%cond.1, body=%body.2, metadata={op_name="jit(step)/node_backward/vmap(transpose(jvp()))/while" stack_frame_id=18}
  %fusion.5 = bf16[8]{0} fusion(%p.1), kind=kLoop, calls=%fused.5, metadata={op_name="jit(step)/optimizer_update/mul"}
  %copy.2 = bf16[8]{0} copy(%p.1)
  %add.3 = bf16[8]{0} add(%p.1, %p.1), metadata={op_name="jit(step)/sub"}
  ROOT %tuple.9 = (bf16[8]) tuple(%fusion.5), metadata={op_name="jit(step)/loss_forward/reduce_sum"}
}
"""


def test_hlo_scopes_maps_each_instruction_to_its_phase():
    assert ph.hlo_scopes(HLO) == {"while.109": "node_backward",
                                  "fusion.5": "optimizer_update",
                                  "tuple.9": "loss_forward"}


@pytest.mark.parametrize("text,scope", [
    ("jit(step)/node_backward/vmap(transpose(jvp()))/dot_general", "node_backward"),
    ("jit(epoch_fn)/while/body/loss_forward/reduce_sum", "loss_forward"),
    ("jit(step)/shard_map/eq9_aggregate/psum", "eq9_aggregate"),
    ("jit(step)/optimizer_update/mul", "optimizer_update"),
    ("jit(step)/node_backward_x/add", ph.OTHER),
])
def test_op_scope_reads_the_path_component(text, scope):
    assert ph.op_scope(text) == scope


def _run(trace=None, program=None, steps=8):
    return {"trace": trace, "steps": steps, "program": program}


PHASE_TRACE = {"scope_s": {"node_backward": 8.0, "eq9_aggregate": 0.3, "loss_forward": 2.0,
                           "optimizer_update": 0.1, "other": 0.1},
               "idle_in_span": {"repro.step.input": 0.12, "repro.step.pull": 0.004}}


@pytest.mark.parametrize("metric,value", [
    ("backward_ms_per_step", 1000.0),
    ("loss_forward_ms_per_step", 250.0),
    ("aggregate_ms_per_step", 50.0),
    ("input_idle_ms_per_step", 15.0),
    ("sync_idle_ms_per_step", 0.5),
])
def test_trace_readers(metric, value):
    read = specs.reader(metric)
    assert read(_run(PHASE_TRACE)) == pytest.approx(value)
    # A CPU run, a program without the spans or scopes, no steps: nothing.
    assert read(_run(None)) is None
    assert read(_run({"busy_s": 1.0, "scope_s": None, "idle_in_span": {}})) is None
    assert read(_run({"busy_s": 1.0})) is None
    assert read(_run(PHASE_TRACE, steps=0)) is None


def test_padding_share_reads_the_program_counters():
    read = specs.reader("padding_share")
    assert read(_run(program={"rows_computed": 384, "rows_useful": 192})) == 50.0
    assert read(_run(program={"rows_computed": 240, "rows_useful": 240})) == 0.0
    assert read(_run(program=None)) is None
    assert read({"trace": None, "steps": 8}) is None
    assert read(_run(program={"rows_computed": 0, "rows_useful": 0})) is None


def test_recorded_trace_splits_busy_time_by_phase():
    # Two steps of the one-chip cell, trimmed to the 300 longest device ops.
    events = json.loads(DATA.read_text())
    busy = tr.reduce_events(events)["busy_s"]
    r = ph.reduce_phases(events, events["scope_of"])
    assert sum(r["scope_s"].values()) == pytest.approx(busy, rel=1e-9)
    assert r["scope_s"]["node_backward"] > r["scope_s"]["loss_forward"] > 0
    idle = r["idle_in_span"]
    assert {"repro.step.input", "repro.step.pull", "repro.step.dispatch"} <= set(idle)
    window = tr.reduce_events(events)["window_s"]
    assert all(0 <= v <= window for v in idle.values())
