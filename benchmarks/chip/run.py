#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips it asks for.

    python3 benchmarks/chip/run.py --workload olmo-1b.clusterA-adaptive \\
        --seed 1234 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``).
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The last line of
stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` a ``breakdown``, and last the
compared numbers with their limits (``checks``).  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"run.py: the program is not in this checkout: {e}", file=sys.stderr)
        return 2
    from chipbench.cell import NoChip, print_checks, run_cell

    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
