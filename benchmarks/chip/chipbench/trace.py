"""Reduction of a profiler trace to the benchmark's device numbers.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
a plain form, ``{"device": {plane: [[name, start_ns, end_ns], ...]},
"host": [[name, start_ns, end_ns], ...]}``: the operations each device ran
(its ``XLA Ops`` line) and every host event.  ``reduce_events`` works on
that form only, so the tests check it on a small recorded trace.

- busy: the union of a device's operation intervals inside the window (the
  host span ``bench.window``), so overlapping or nested events count once
  and a renamed program changes nothing;
- window: the length of ``bench.window``;
- collectives: the summed time of operations whose HLO name is an
  all-reduce, all-gather, reduce-scatter, collective-permute or all-to-all;
- idle gaps: the spaces between busy intervals on the first device, each
  named by the innermost host event that covers its middle.
Times from several devices are averaged over the devices.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)
TOP = 10

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def op_name(text: str) -> str:
    """An HLO operation's name from the instruction text a TPU trace gives
    as the event name (``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load_events(path: str) -> Dict[str, object]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[str, List[List[object]]] = {}
    host: List[List[object]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            ops = [
                [op_name(e.name), float(e.start_ns), float(e.start_ns + e.duration_ns)]
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events
            ]
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)]
                    for e in line.events if e.duration_ns > 0
                )
    return {"device": device, "host": host}


def describe(path: str) -> List[Tuple[str, List[Tuple[str, int]]]]:
    """Planes of a trace with their lines and event counts."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(p.name, [(ln.name, sum(1 for _ in ln.events)) for ln in p.lines])
            for p in data.planes]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals, in start order."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _window(host: Sequence[Sequence[object]]) -> Optional[Interval]:
    spans = [(float(s), float(e)) for n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def _name_gap(host: Sequence[Sequence[object]], lo: float, hi: float) -> str:
    """The shortest host event (not the window itself) covering the gap's
    middle; ``"no host span"`` where none does."""
    mid = 0.5 * (lo + hi)
    best: Optional[Tuple[float, str]] = None
    for n, s, e in host:
        if n == WINDOW_SPAN or not (float(s) <= mid <= float(e)):
            continue
        length = float(e) - float(s)
        if best is None or length < best[0]:
            best = (length, str(n))
    return best[1] if best else "no host span"


def reduce_events(events: Dict[str, object]) -> Optional[Dict[str, object]]:
    """Device numbers of the traced window, or None where the trace holds no
    window span or no device operation inside it."""
    host = events["host"]
    win = _window(host)  # type: ignore[arg-type]
    devices = events["device"]
    if win is None or not devices:
        return None
    lo, hi = win
    busy: List[float] = []
    coll: List[float] = []
    op_time: Dict[str, float] = {}
    first_union: List[Interval] = []
    for i, plane in enumerate(sorted(devices)):  # type: ignore[arg-type]
        ops = devices[plane]  # type: ignore[index]
        spans = clip([(float(s), float(e)) for _, s, e in ops], lo, hi)
        merged = union(spans)
        busy.append(sum(e - s for s, e in merged))
        if i == 0:
            first_union = merged
        c = 0.0
        for name, s, e in ops:
            part = clip([(float(s), float(e))], lo, hi)
            if not part:
                continue
            dur = part[0][1] - part[0][0]
            op_time[str(name)] = op_time.get(str(name), 0.0) + dur
            if COLLECTIVE.match(str(name)):
                c += dur
        coll.append(c)
    if not any(busy):
        return None
    n_dev = len(busy)
    gaps: List[Tuple[float, str]] = []
    prev = lo
    for s, e in first_union + [(hi, hi)]:
        if s > prev:
            gaps.append((s - prev, _name_gap(host, prev, s)))  # type: ignore[arg-type]
        prev = max(prev, e)
    gaps.sort(reverse=True)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "collective_s": sum(coll) / n_dev * 1e-9,
        "collective_ops": sum(
            1 for ops in devices.values() for n, s, e in ops  # type: ignore[union-attr]
            if COLLECTIVE.match(str(n)) and clip([(float(s), float(e))], lo, hi)
        ),
        "devices": n_dev,
        "device_ops": [[n, t / n_dev * 1e-9] for n, t in top_ops],
        "idle_gaps": [[n, g * 1e-9] for g, n in gaps[:TOP]],
    }
