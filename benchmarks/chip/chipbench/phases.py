"""The step's device time by phase, and the device's idle time inside the
program's host spans, from a profiler trace.

The program names the phases of its step program with ``jax.named_scope``
(``node_backward``, ``eq9_aggregate``, ``loss_forward``,
``optimizer_update``), so the ``op_name`` metadata of every operation of the
compiled step holds one of them as a path component.  A TPU trace's
operation events carry no metadata, only the operation's name, so
``hlo_scopes`` reads the phase of each operation from the compiled step's
HLO text (``compiled.as_text()``).  The program also wraps its host work in
``repro.``-prefixed profiler spans (``repro.epoch``, ``repro.step.input``,
``repro.step.pull``, ...).

``reduce_phases`` works on ``chipbench.trace``'s plain form and such a map:

- ``scope_s``: each phase's device time inside the window.  At every
  instant the time goes to the innermost operation running (the latest to
  start), so a scan's ``while`` and the body operations inside it count
  once, and the phases and ``other`` (operations the map does not name)
  add up to the busy time.  Averaged over the devices; None where no
  operation in the window has a phase (a program without the scopes, or an
  executable loaded from a compilation cache filled before they existed:
  the cache's key leaves metadata out).  The map is of one program: an
  operation of another program in the window with the same name would be
  counted under the step's phase.
- ``idle_in_span``: for each ``repro.`` span name, the first device's idle
  time inside the union of that span's intervals in the window.
"""
from __future__ import annotations

import heapq
import re
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as tr

SCOPES = ("node_backward", "eq9_aggregate", "loss_forward", "optimizer_update")
OTHER = "other"
SPAN_PREFIX = "repro."
_SCOPE_RE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?:/|$)")
# One HLO instruction: ``%name = ... metadata={op_name="..." ...}``.
_HLO_OP_RE = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?op_name="([^"]*)"', re.M)

Interval = Tuple[float, float]


def op_scope(op_name: str) -> str:
    """The phase an ``op_name`` path names; ``OTHER`` where it names none."""
    m = _SCOPE_RE.search(op_name)
    return m.group(1) if m else OTHER


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Operation name -> phase for every instruction of a compiled HLO
    module whose ``op_name`` metadata names one."""
    out = {}
    for m in _HLO_OP_RE.finditer(hlo_text):
        scope = op_scope(m.group(2))
        if scope != OTHER:
            out[m.group(1)] = scope
    return out


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def by_scope(ops: Sequence[Sequence[object]], scope_of: Dict[str, str],
             lo: float, hi: float) -> Dict[str, float]:
    """Device time (ns) of each phase inside ``[lo, hi]``, each instant
    going to the innermost operation running then."""
    items = sorted(
        (max(float(s), lo), min(float(e), hi), scope_of.get(str(n), OTHER))
        for n, s, e in ops
        if float(e) > lo and float(s) < hi
    )
    edges = sorted({x for s, e, _ in items for x in (s, e)})
    out: Dict[str, float] = {}
    active: List[Tuple[float, float, str]] = []  # (-start, end, phase)
    k = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(items) and items[k][0] <= a:
            s, e, sc = items[k]
            heapq.heappush(active, (-s, e, sc))
            k += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            sc = active[0][2]
            out[sc] = out.get(sc, 0.0) + (b - a)
    return out


def idle_in_spans(host: Sequence[Sequence[object]], busy: Sequence[Interval],
                  lo: float, hi: float) -> Dict[str, float]:
    """Idle time (ns) of a device busy over ``busy`` (sorted, disjoint)
    inside each ``repro.`` span name's intervals, within ``[lo, hi]``."""
    spans: Dict[str, List[Interval]] = {}
    for n, s, e in host:
        if str(n).startswith(SPAN_PREFIX):
            spans.setdefault(str(n), []).append((float(s), float(e)))
    out = {}
    for name, iv in spans.items():
        inside = tr.clip(tr.union(iv), lo, hi)
        if inside:
            out[name] = sum(e - s for s, e in inside) - _overlap(inside, busy)
    return out


def reduce_phases(events: Dict[str, object],
                  scope_of: Dict[str, str]) -> Optional[Dict[str, object]]:
    """``scope_s`` and ``idle_in_span`` of the traced window (seconds), with
    ``scope_of`` (``hlo_scopes`` of the step) naming each operation's
    phase; None where the trace holds no window span or no device plane."""
    host = events["host"]
    win = tr._window(host)  # type: ignore[arg-type]
    devices = events["device"]
    if win is None or not devices:
        return None
    lo, hi = win
    planes = sorted(devices)  # type: ignore[arg-type]
    totals: Dict[str, float] = {}
    for plane in planes:
        for k, v in by_scope(devices[plane], scope_of, lo, hi).items():  # type: ignore[index]
            totals[k] = totals.get(k, 0.0) + v
    scope_s = None
    if any(k != OTHER for k in totals):
        scope_s = {k: totals.get(k, 0.0) / len(planes) * 1e-9 for k in SCOPES + (OTHER,)}
    first = devices[planes[0]]  # type: ignore[index]
    busy = tr.union(tr.clip([(float(s), float(e)) for _, s, e in first], lo, hi))
    idle = idle_in_spans(host, busy, lo, hi)  # type: ignore[arg-type]
    return {"scope_s": scope_s,
            "idle_in_span": {k: v * 1e-9 for k, v in sorted(idle.items())}}
