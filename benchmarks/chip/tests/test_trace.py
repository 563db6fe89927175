"""The trace reduction: busy union, window, collective time and idle gaps
named by host spans, on a hand-made trace and on a small trace recorded on
a TPU v5e (``data/trace_small.json``, made by ``record_trace.py``)."""
import json
from pathlib import Path

import pytest

from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"


def _events():
    ms = 1e6
    return {
        "device": {
            "/device:TPU:0": [
                ["fusion.1", 1 * ms, 4 * ms],
                ["fusion.2", 2 * ms, 3 * ms],      # nested: counts once
                ["all-reduce.7", 6 * ms, 7 * ms],
                ["fusion.1", 9 * ms, 12 * ms],     # runs past the window
            ],
            "/device:TPU:1": [
                ["fusion.1", 1 * ms, 5 * ms],
                ["all-reduce.7", 6 * ms, 8 * ms],
            ],
        },
        "host": [
            [tr.WINDOW_SPAN, 0.0, 10 * ms],
            ["bench.epoch", 0.0, 10 * ms],
            ["bench.input", 4.5 * ms, 5.8 * ms],
            ["before the window", -5 * ms, -1 * ms],
        ],
    }


def test_busy_is_the_union_inside_the_window():
    r = tr.reduce_events(_events())
    assert r["window_s"] == pytest.approx(10e-3)
    # TPU:0 busy 1-4, 6-7, 9-10 = 5 ms; TPU:1 busy 1-5, 6-8 = 6 ms.
    assert r["busy_s"] == pytest.approx(5.5e-3)
    assert r["collective_s"] == pytest.approx(1.5e-3)
    assert r["collective_ops"] == 2
    assert r["devices"] == 2


def test_idle_gaps_are_named_by_the_innermost_host_span():
    r = tr.reduce_events(_events())
    # TPU:0 idles 0-1, 4-6 and 7-9 ms; the middle of 4-6 lies in bench.input.
    got = [[n, pytest.approx(s)] for n, s in r["idle_gaps"]]
    assert got == [["bench.input", 2e-3], ["bench.epoch", 2e-3], ["bench.epoch", 1e-3]]


def test_no_window_or_no_device_reads_nothing():
    ev = _events()
    assert tr.reduce_events({"device": ev["device"], "host": ev["host"][1:]}) is None
    assert tr.reduce_events({"device": {}, "host": ev["host"]}) is None


def _busy_brute(ops, lo, hi):
    """Covered length by cutting the window at every event edge."""
    edges = sorted({lo, hi, *[min(max(x, lo), hi) for _, s, e in ops for x in (s, e)]})
    covered = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = 0.5 * (a + b)
        if any(s <= mid < e for _, s, e in ops):
            covered += b - a
    return covered


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_trace_reduces_to_its_union():
    events = json.loads(DATA.read_text())
    r = tr.reduce_events(events)
    assert r is not None
    win = [e for e in events["host"] if e[0] == tr.WINDOW_SPAN][0]
    expect = sum(_busy_brute(ops, win[1], win[2]) for ops in events["device"].values())
    assert r["busy_s"] == pytest.approx(expect / len(events["device"]) * 1e-9, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert len(r["device_ops"]) <= tr.TOP and len(r["idle_gaps"]) <= tr.TOP
