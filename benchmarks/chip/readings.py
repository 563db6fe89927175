#!/usr/bin/env python3
"""Readings that set a cell's comparison limits, on the chip, in one
process.

    python3 benchmarks/chip/readings.py --workload olmo-1b.clusterA-adaptive \\
        --seeds 11,12,13 --control-seeds 3 --seconds 1 --out chiprun_out/readings.json

For every seed, a whole run of the cell (set-up, a window of ``--seconds``,
the comparison) gives the program's readings of every compared number: the
lower readings.  For the first ``--control-seeds`` seeds also the upper
readings, each from the reference put in the program's place on the same
rows and plans: the control (the step's reference with float8 matmul
operands; the noise estimate in float32), the fault ``half_batch`` (each
node's loss and gradient over half its rows), and the planner's faults
``even_split`` (the window's totals split evenly), ``fixed_total`` (the
reference total, split at its best) and ``plain_gns`` (the noise estimate
with equal node weights).  A state left unchanged reads 1 on
``change_leaf`` by construction and is not run.  Writes every number to
``--out`` as JSON.
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def planner_faults(mix, record):
    """The planner's numbers with each fault planted in its reference."""
    import numpy as np

    from chipbench.planner import best_split_time, compare_plans, step_time

    def altered(fn):
        rec = copy.deepcopy(record)
        for e in rec["epochs"]:
            if e["window"]:
                e["batches"], e["total"] = fn(e)
        return rec

    n = len(mix["nodes"])

    def even(e):
        t = e["total"]
        return [t // n + (i < t % n) for i in range(n)], t

    def fixed(e):
        import itertools

        t = int(mix["ref_batch"])
        best = best_split_time(mix, t)
        for split in itertools.product(range(1, int(mix["max_local"]) + 1), repeat=n):
            if sum(split) == t and float(step_time(mix, np.asarray([split]))[0]) == best:
                return list(split), t
        raise AssertionError("no best split")

    return {
        "control": compare_plans(mix, record, gns_dtype=np.float32),
        "even_split": compare_plans(mix, altered(even)),
        "fixed_total": compare_plans(mix, altered(fixed)),
        "plain_gns": compare_plans(mix, record, gns_weights="plain"),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))

    from chipbench import spec as specs
    from chipbench.cell import run_cell
    from chipbench.reference import RefTrainer, compare

    c = specs.cell(args.workload)
    cfg, mix = c["cfg"], c["mix"]
    out = {"workload": args.workload, "program": [], "control": [],
           "faults": {"half_batch": []}, "planner": []}
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        record = {}
        result = run_cell(args.workload, seed, args.seconds, False, t_start=time.time(),
                          record=record, log=lambda s: print(s, flush=True))
        out["device"] = result["device"]
        row = {"seed": seed, "correct": result["correct"], "failed": result["failed"],
               "checks": result["checks"], "metrics": result["metrics"],
               "splits": [e["batches"] for e in record["epochs"]]}
        out["program"].append(row)
        print(f"seed {seed} program {json.dumps(row['checks'])} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
        if k < args.control_seeds:
            ref, batches, split = record["ref"], record["batches"], record["split"]
            ctl = RefTrainer(cfg, precision="fp8").run(seed, batches, split, mix["lr"])
            out["control"].append({"seed": seed, "numbers": compare(ctl, ref)})
            half = RefTrainer(cfg, fault="half_batch").run(seed, batches, split, mix["lr"])
            out["faults"]["half_batch"].append({"seed": seed, "numbers": compare(half, ref)})
            out["planner"].append({"seed": seed, **planner_faults(mix, record)})
            for key in ("control", "planner"):
                print(f"seed {seed} {key} {json.dumps(out[key][-1])}", flush=True)
            print(f"seed {seed} half_batch {json.dumps(out['faults']['half_batch'][-1])}",
                  flush=True)
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
