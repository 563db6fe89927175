#!/usr/bin/env python3
"""Record a short profiler trace of a cell's step and keep it, trimmed, as
the small trace the trace-reduction tests read.

    python3 benchmarks/chip/record_trace.py --workload olmo-1b.clusterA-adaptive \\
        --steps 2 --out benchmarks/chip/tests/data/trace_small.json

Runs ``--steps`` steps at the mix's ``check_split`` inside a
``bench.window`` span (each step in a ``bench.epoch`` span), then writes
the events ``chipbench.trace.load_events`` reads, with each device's
operations cut to the ``--keep`` longest and the host events to those of
0.1 ms or more that overlap the window, and prints what the reduction makes
of it.
"""
import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
MIN_HOST_NS = 1e5  # host events shorter than this are dropped from the file
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--keep", type=int, default=300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.profiler import TraceAnnotation

    from chipbench import spec as specs
    from chipbench import trace as tr
    from chipbench.cell import EPOCH_SPAN, build_backend, devices_for
    from chipbench.reference import RefTrainer
    from chipbench.traffic import make_stream
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    c = specs.cell(args.workload)
    cfg, mix = c["cfg"], c["mix"]
    devices = devices_for(int(c["chips"]), require_tpu=True)
    split = [int(b) for b in mix["check_split"]]
    backend = build_backend(cfg, mix, 0, RefTrainer(cfg, device=devices[0]),
                            make_stream(mix, cfg["vocab"], 0))
    backend.execute(split, 1)  # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        jax.profiler.start_trace(tmp)
        with TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(args.steps):
                with TraceAnnotation(EPOCH_SPAN):
                    backend.execute(split, 1)
        jax.profiler.stop_trace()
        xplane = tr.find_xplane(tmp)
        for plane, lines in tr.describe(xplane):
            print(f"plane {plane}: {lines[:12]}", flush=True)
        events = tr.load_events(xplane)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    win = [e for e in events["host"] if e[0] == tr.WINDOW_SPAN][0]
    lo, hi = win[1], win[2]
    for plane, ops in events["device"].items():
        print(f"{plane}: {len(ops)} ops", flush=True)
        kept = sorted(ops, key=lambda e: e[1] - e[2])[: args.keep]
        events["device"][plane] = sorted(kept, key=lambda e: e[1])
    events["host"] = [e for e in events["host"]
                      if e[2] > lo and e[1] < hi and e[2] - e[1] >= MIN_HOST_NS]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(events))
    print(json.dumps(tr.reduce_events(events), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
