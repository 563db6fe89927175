"""Goodput-sweep benchmark: the OptPerf engines head-to-head (the §4.1/§4.5
control-loop hot path behind the Table 5 overhead claims).

Lanes, at n nodes x C candidate total batch sizes:

  * scalar water-fill loop  — ``solve_optperf_waterfill`` per candidate
  * scalar Algorithm 1 loop — ``solve_optperf_algorithm1`` per candidate
    (with §4.5 boundary-hint chaining, as the old selector sweep did)
  * batched engine          — one ``solve_optperf_batch`` array pass
  * warm-started engine     — the same sweep re-solved after a small
    coefficient drift, brackets seeded from the previous ``t_stars``
  * jax engine              — ``solve_optperf_batch_jax``: the sweep
    jit-compiled on-device (cold and warm-seeded)
  * scheduler               — ``allocate`` at J jobs x N nodes: warm-started
    stacked rounds (NumPy and stacked-jax engines) vs the per-(job, node)
    scalar loop
  * incremental             — ``Scheduler.add_job`` on a single-job arrival
    vs a cold full re-allocation over the same job set

Hard gates (full mode):
  * batched engine <= 1e-6 relative opt_perf gap vs the scalar oracle and
    >= 10x over the scalar loop at 64x64,
  * warm-started sweep >= 5x over the cold batched sweep under small drift
    at 64x64 (and bit-equal results to ~1e-9),
  * jax engine <= 1e-5 relative gap vs the scalar oracle,
  * batched AND stacked-jax ``allocate`` >= 10x over the scalar loop at
    8 jobs x 64 nodes with assignments identical to the scalar oracle,
  * incremental ``Scheduler.add_job`` >= 3x over the cold full re-run at
    8 (+1 arriving) jobs x 64 nodes, emitting the identical allocation
    (the gate was 5x when cold ``allocate`` still paid one scalar
    chosen-set solve per greedy round; batching those into stacked
    ``solve_optperf_waterfill_subsets`` calls made the cold baseline
    ~1.7x faster, compressing the ratio — the incremental path itself
    reuses exactly as much as before, see the arrival counters).

Results land in ``artifacts/bench/sweep.json`` (uploaded per CI run so the
perf trajectory is tracked per PR).

Usage:
    PYTHONPATH=src:. python -m benchmarks.bench_sweep            # full (64x64)
    PYTHONPATH=src:. python -m benchmarks.bench_sweep --smoke    # CI-sized
"""
from __future__ import annotations

import argparse
import time
from typing import List

import numpy as np

from benchmarks.common import Row, save_json, time_call
from repro.core.goodput import goodput_curve
from repro.core.optperf import (
    solve_optperf_algorithm1,
    solve_optperf_batch,
    solve_optperf_waterfill,
)
from repro.core.perf_model import ClusterPerfModel, CommModel, NodePerfModel
from repro.core.scheduler import Scheduler, allocate, random_jobs
from repro.core.simulator import drift_model


def _random_model(n: int, seed: int = 0) -> ClusterPerfModel:
    rng = np.random.default_rng(seed)
    nodes = tuple(
        NodePerfModel(
            q=float(rng.uniform(1e-4, 5e-3)),
            s=float(rng.uniform(0, 0.02)),
            k=float(rng.uniform(1e-4, 8e-3)),
            m=float(rng.uniform(0, 0.02)),
        )
        for _ in range(n)
    )
    comm = CommModel(t_o=0.04, t_u=0.008, gamma=0.15)
    return ClusterPerfModel(nodes=nodes, comm=comm)


def _candidates(count: int) -> np.ndarray:
    return np.unique(np.round(np.geomspace(64, 65536, count))).astype(np.float64)


def run_config(n: int, num_candidates: int, repeats: int) -> dict:
    model = _random_model(n)
    cands = _candidates(num_candidates)

    def scalar_waterfill():
        return [solve_optperf_waterfill(model, float(b)) for b in cands]

    def scalar_algorithm1():
        hint = None
        out = []
        for b in cands:
            sol = solve_optperf_algorithm1(model, float(b), boundary_hint=hint)
            hint = sum(1 for s in sol.bottleneck if s == "compute")
            out.append(sol)
        return out

    def batched():
        return solve_optperf_batch(model, cands)

    t_wf = time_call(scalar_waterfill, repeats=repeats)
    t_a1 = time_call(scalar_algorithm1, repeats=repeats)
    t_batch = time_call(batched, repeats=repeats)

    batch_sol = batched()
    scalar_sols = scalar_waterfill()
    gaps = [
        abs(batch_sol.opt_perfs[j] - s.opt_perf) / s.opt_perf
        for j, s in enumerate(scalar_sols)
    ]
    return {
        "n": n,
        "candidates": int(cands.size),
        "scalar_waterfill_us": t_wf,
        "scalar_algorithm1_us": t_a1,
        "batched_us": t_batch,
        "speedup_vs_waterfill_loop": t_wf / t_batch,
        "speedup_vs_algorithm1_loop": t_a1 / t_batch,
        "max_rel_gap_vs_oracle": float(max(gaps)),
    }


def run_warm(n: int, num_candidates: int, repeats: int, *, assert_gate: bool) -> dict:
    """Warm-start lane: re-solve after a small drift, seeded vs cold."""
    model = _random_model(n)
    cands = _candidates(num_candidates)
    base = solve_optperf_batch(model, cands)
    drifted = drift_model(model, rel=1e-4, seed=1)

    t_cold = time_call(lambda: solve_optperf_batch(drifted, cands), repeats=repeats)
    t_warm = time_call(
        lambda: solve_optperf_batch(drifted, cands, warm_start=base.t_stars),
        repeats=repeats,
    )
    cold_sol = solve_optperf_batch(drifted, cands)
    warm_sol = solve_optperf_batch(drifted, cands, warm_start=base.t_stars)
    gap = float(np.max(np.abs(warm_sol.opt_perfs - cold_sol.opt_perfs) / cold_sol.opt_perfs))
    rec = {
        "n": n,
        "candidates": int(cands.size),
        "drift_rel": 1e-4,
        "cold_us": t_cold,
        "warm_us": t_warm,
        "speedup_warm_vs_cold": t_cold / t_warm,
        "cold_evals": cold_sol.iterations,
        "warm_evals": warm_sol.iterations,
        "max_rel_gap_warm_vs_cold": gap,
    }
    if gap > 1e-9:
        raise AssertionError(f"warm-started sweep drifted from cold: {rec}")
    if assert_gate and rec["speedup_warm_vs_cold"] < 5.0:
        raise AssertionError(f"warm sweep under 5x at {n}x{num_candidates}: {rec}")
    return rec


def run_jax(n: int, num_candidates: int, repeats: int) -> dict:
    """JAX-engine lane: jit-compiled on-device sweep vs the scalar oracle."""
    from repro.core.optperf_jax import solve_optperf_batch_jax

    model = _random_model(n)
    cands = _candidates(num_candidates)
    # warmup inside time_call covers jit compilation.
    t_jax = time_call(lambda: solve_optperf_batch_jax(model, cands), repeats=repeats)
    base = solve_optperf_batch_jax(model, cands)
    t_jax_warm = time_call(
        lambda: solve_optperf_batch_jax(model, cands, warm_start=base.t_stars),
        repeats=repeats,
    )
    t_np = time_call(lambda: solve_optperf_batch(model, cands), repeats=repeats)
    gaps = []
    for j, b in enumerate(cands):
        wf = solve_optperf_waterfill(model, float(b))
        gaps.append(abs(base.opt_perfs[j] - wf.opt_perf) / wf.opt_perf)
    rec = {
        "n": n,
        "candidates": int(cands.size),
        "jax_us": t_jax,
        "jax_warm_us": t_jax_warm,
        "numpy_batched_us": t_np,
        "max_rel_gap_vs_oracle": float(max(gaps)),
    }
    if rec["max_rel_gap_vs_oracle"] > 1e-5:
        raise AssertionError(f"jax engine drifted from scalar oracle: {rec}")
    return rec


def run_scheduler(n_jobs: int, n_nodes: int, *, assert_gate: bool) -> dict:
    """Scheduler lane: warm-started stacked allocation (NumPy batched and
    stacked-jax engines) vs the per-(job, node) scalar loop, one scalar
    baseline shared by both engine lanes."""
    jobs = random_jobs(n_jobs, n_nodes)

    def timed(engine: str, repeats: int) -> float:
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            allocate(jobs, n_nodes, engine=engine)
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    t_batched = timed("batched", repeats=3)
    t_scalar = timed("scalar", repeats=1)  # the slow baseline: once is enough
    a_s = allocate(jobs, n_nodes, engine="scalar")
    a_b = allocate(jobs, n_nodes, engine="batched")
    rec = {
        "jobs": n_jobs,
        "nodes": n_nodes,
        "batched_us": t_batched,
        "scalar_us": t_scalar,
        "speedup": t_scalar / t_batched,
        "assignments_equal": a_b.assignment == a_s.assignment,
        "aggregate_fraction": a_b.aggregate_fraction,
    }
    allocate(jobs, n_nodes, engine="jax")  # jit warmup outside the clock
    rec["jax_us"] = timed("jax", repeats=3)
    rec["jax_speedup"] = t_scalar / rec["jax_us"]
    a_j = allocate(jobs, n_nodes, engine="jax")
    rec["jax_assignments_equal"] = a_j.assignment == a_s.assignment
    if not rec["jax_assignments_equal"]:
        raise AssertionError(f"jax allocate diverged from scalar: {rec}")
    if assert_gate and rec["jax_speedup"] < 10.0:
        raise AssertionError(
            f"stacked-jax allocate under 10x at {n_jobs}x{n_nodes}: {rec}"
        )
    if not rec["assignments_equal"]:
        raise AssertionError(f"batched allocate diverged from scalar: {rec}")
    if assert_gate and rec["speedup"] < 10.0:
        raise AssertionError(f"batched allocate under 10x at {n_jobs}x{n_nodes}: {rec}")
    return rec


def run_incremental(n_jobs: int, n_nodes: int, *, assert_gate: bool) -> dict:
    """Incremental lane: ``Scheduler.add_job`` on a single-job arrival vs a
    cold full re-allocation over the same (n_jobs + 1)-job set."""
    jobs = random_jobs(n_jobs, n_nodes)
    arriving = random_jobs(n_jobs + 1, n_nodes)[n_jobs]
    everyone = list(jobs) + [arriving]

    t_full = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        full = allocate(everyone, n_nodes)
        t_full = min(t_full, time.perf_counter() - t0)

    t_inc = float("inf")
    inc = None
    arrival = {}
    for _ in range(3):
        sched = Scheduler(n_nodes)
        for job in jobs:
            sched.add_job(job)
        before = (
            sched.warm_rounds, sched.cold_rounds,
            sched.solved_rows, sched.cached_rows,
        )
        t0 = time.perf_counter()
        inc = sched.add_job(arriving)
        t_inc = min(t_inc, time.perf_counter() - t0)
        # Counters for the arrival alone (the setup add_jobs excluded).
        arrival = dict(zip(
            ("warm_rounds", "cold_rounds", "solved_rows", "cached_rows"),
            (
                sched.warm_rounds - before[0], sched.cold_rounds - before[1],
                sched.solved_rows - before[2], sched.cached_rows - before[3],
            ),
        ))

    goodput_gap = max(
        abs(inc.goodputs[name] - full.goodputs[name])
        / max(full.goodputs[name], 1e-12)
        for name in full.goodputs
    )
    rec = {
        "jobs": n_jobs,
        "nodes": n_nodes,
        "full_us": t_full * 1e6,
        "incremental_us": t_inc * 1e6,
        "speedup": t_full / t_inc,
        "assignments_equal": inc.assignment == full.assignment,
        "max_rel_goodput_gap": float(goodput_gap),
        **arrival,
    }
    if not rec["assignments_equal"] or goodput_gap > 1e-12:
        raise AssertionError(f"incremental add_job diverged from full re-run: {rec}")
    if assert_gate and rec["speedup"] < 3.0:
        raise AssertionError(
            f"incremental add_job under 3x at {n_jobs}x{n_nodes}: {rec}"
        )
    return rec


def run(smoke: bool = False) -> List[Row]:
    configs = [(8, 8)] if smoke else [(16, 16), (64, 64), (256, 64)]
    repeats = 3 if smoke else 5
    rows: List[Row] = []
    payload = {}
    for n, c in configs:
        rec = run_config(n, c, repeats)
        payload[f"n{n}_c{c}"] = rec
        rows.append(
            Row(
                f"sweep/batched/n{n}xc{c}",
                rec["batched_us"],
                f"speedup={rec['speedup_vs_waterfill_loop']:.1f}x;"
                f"gap={rec['max_rel_gap_vs_oracle']:.2e}",
            )
        )
        rows.append(Row(f"sweep/scalar_waterfill/n{n}xc{c}", rec["scalar_waterfill_us"], ""))
        rows.append(Row(f"sweep/scalar_algorithm1/n{n}xc{c}", rec["scalar_algorithm1_us"], ""))
        # The acceptance gate: >= 10x over the per-candidate scalar loop and
        # <= 1e-6 relative opt_perf gap at the 64x64 configuration.
        if rec["max_rel_gap_vs_oracle"] > 1e-6:
            raise AssertionError(f"batched engine drifted from oracle: {rec}")
        if not smoke and (n, c) == (64, 64) and rec["speedup_vs_waterfill_loop"] < 10.0:
            raise AssertionError(f"batched sweep under 10x at 64x64: {rec}")

    # Warm-start lane (gate: >= 5x at the full 64x64 configuration).
    wn, wc = (8, 8) if smoke else (64, 64)
    rec = run_warm(wn, wc, repeats=max(repeats, 7), assert_gate=not smoke)
    payload["warm"] = rec
    rows.append(
        Row(
            f"sweep/warm/n{wn}xc{wc}",
            rec["warm_us"],
            f"speedup={rec['speedup_warm_vs_cold']:.1f}x;"
            f"evals={rec['warm_evals']}vs{rec['cold_evals']}",
        )
    )

    # JAX-engine lane (gate: <= 1e-5 vs the scalar oracle; CPU jit in CI).
    rec = run_jax(wn, wc, repeats)
    payload["jax"] = rec
    rows.append(
        Row(
            f"sweep/jax/n{wn}xc{wc}",
            rec["jax_us"],
            f"warm={rec['jax_warm_us']:.0f}us;"
            f"gap={rec['max_rel_gap_vs_oracle']:.2e}",
        )
    )

    # Scheduler lanes (gates: batched and stacked-jax both >= 10x at
    # 8 jobs x 64 nodes, assignments identical to the scalar oracle).
    sj, sn = (3, 12) if smoke else (8, 64)
    rec = run_scheduler(sj, sn, assert_gate=not smoke)
    payload["scheduler"] = rec
    rows.append(
        Row(
            f"sweep/scheduler/j{sj}xn{sn}",
            rec["batched_us"],
            f"speedup={rec['speedup']:.1f}x",
        )
    )
    if "jax_us" in rec:
        rows.append(
            Row(
                f"sweep/scheduler_jax/j{sj}xn{sn}",
                rec["jax_us"],
                f"speedup={rec['jax_speedup']:.1f}x",
            )
        )

    # Incremental lane (gate: >= 3x over the cold full re-run on a
    # single-job arrival, identical allocation; was 5x before the cold
    # baseline's chosen-set solves were batched).
    rec = run_incremental(sj, sn, assert_gate=not smoke)
    payload["incremental"] = rec
    rows.append(
        Row(
            f"sweep/incremental/j{sj}xn{sn}",
            rec["incremental_us"],
            f"speedup={rec['speedup']:.1f}x;"
            f"rows={rec['solved_rows']}solved/{rec['cached_rows']}cached",
        )
    )

    # A goodput_curve smoke call so the end-to-end consumer path is timed too.
    model = _random_model(16)
    cands = _candidates(16)
    t_curve = time_call(lambda: goodput_curve(model, cands, 500.0, 128), repeats=repeats)
    rows.append(Row("sweep/goodput_curve/n16xc16", t_curve, ""))
    payload["goodput_curve_n16_c16_us"] = t_curve
    save_json("sweep", payload)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(smoke=args.smoke):
        print(row.csv(), flush=True)


if __name__ == "__main__":
    main()
