"""Fault-recovery overhead lane: chaos replay vs its fault-free twin.

    PYTHONPATH=src:. python -m benchmarks.bench_faults [--smoke]

Replays the standard 3-job synthetic trace under the default seeded chaos
plan (one crash, one transient straggler, one flapping node, one noise
spike — see ``FaultPlan.chaos``) with the HealthMonitor detecting and the
runtime self-healing, then measures what the faults cost:

* ``goodput_retention`` — fault-free sim-time / faulted sim-time (gate:
  >= 0.8 — detection plus recovery must keep at least 80% of throughput);
* ``detection_latency_epochs`` / ``mttr_epochs`` — how fast faults are
  caught and repaired;
* wall-clock replay overhead of the fault-tolerance layer itself on a
  fault-free trace (injector + monitor present but idle).

A second, real-backend lane replays a single real-gradient job under the
``chaos-real`` integrity plan (gradient poison + solver stall) with the
runtime invariant checker on: the anomaly guard must contain the poison,
the numerical-health channel must quarantine the node, the watchdog must
absorb the stall, the final loss must be finite, and goodput retention
must clear the same gate — all with zero invariant violations.

Results merge into ``artifacts/bench/sweep.json`` under the ``"faults"``
key (real-backend lane under ``"faults"."real"``) so the sweep artifact
stays the one-stop perf record.
"""
import argparse
import json
import math
import os
import tempfile
import time

from benchmarks.common import ARTIFACTS, Row, save_json

from repro.runtime import (
    FaultPlan,
    RealBackendConfig,
    Trace,
    make_fault_plan,
    replay,
    synthetic_trace,
)

N_JOBS, N_NODES, SEED = 3, 12, 0
EPOCHS_PER_EVENT, STEPS, NOISE = 6, 2, 0.01
RETENTION_GATE = 0.8
REAL_NODES, REAL_EPOCHS = 3, 6


def _replay(faults=None, health=None, checkpoint_dir=None):
    trace, _ = synthetic_trace(N_JOBS, N_NODES, seed=SEED)
    return replay(
        trace, N_NODES, policy="cannikin", epochs_per_event=EPOCHS_PER_EVENT,
        steps=STEPS, noise=NOISE, seed=SEED, faults=faults, health=health,
        checkpoint_dir=checkpoint_dir,
    )


def _real_spec():
    from repro.core.perf_model import CommModel
    from repro.core.scheduler import JobSpec
    from repro.core.simulator import GPU_CATALOG

    return JobSpec(
        name="real-job",
        node_models=tuple(
            GPU_CATALOG[n].model() for n in ("a100", "v100", "rtx6000")
        ),
        comm=CommModel(t_o=0.04, t_u=0.008, gamma=0.15),
        total_batch=12,
        b_noise=500.0,
        ref_batch=12,
        backend="real",
    )


def _run_real_lane(rows):
    """chaos-real on a single real-gradient job: poison + stall contained."""
    plan = make_fault_plan("chaos-real", REAL_NODES, seed=SEED)
    trace = Trace().arrive(_real_spec(), at=0.0)
    t0 = time.perf_counter()
    rep = replay(
        trace, REAL_NODES, policy="cannikin", epochs_per_event=REAL_EPOCHS,
        steps=STEPS, seed=SEED,
        real_backend=RealBackendConfig(
            arch="olmo-1b", reduced=True, seq_len=16, lr=0.3
        ),
        faults=plan, invariants=True,
    )
    elapsed = time.perf_counter() - t0
    telemetry = rep.runtime.fault_telemetry()
    assert telemetry is not None
    retention = rep.goodput_retention
    assert retention is not None

    # The integrity gates (deterministic, so they hold in smoke runs too).
    handle = rep.runtime.handles["real-job"]
    assert all(
        math.isfinite(r.mean_loss) for r in handle.records
    ), "non-finite loss under gradient poison"
    assert telemetry["detected"]["numeric"] >= 1, "poison never detected"
    assert telemetry["recoveries"]["quarantine"] >= 1, "poison never quarantined"
    assert telemetry["watchdog"]["solver_timeouts"] >= 1, "stall never caught"
    assert telemetry["invariants"]["violations"] == 0, "invariant violations"
    assert retention >= RETENTION_GATE, (
        f"real-lane retention {retention:.3f} below gate {RETENTION_GATE}"
    )

    record = {
        "n_nodes": REAL_NODES,
        "epochs_per_event": REAL_EPOCHS,
        "plan": plan.describe(),
        "goodput_retention": retention,
        "retention_gate": RETENTION_GATE,
        "detection_latency_epochs": telemetry["detection_latency_epochs"],
        "detected": telemetry["detected"],
        "recoveries": telemetry["recoveries"],
        "watchdog": telemetry["watchdog"],
        "invariants": telemetry["invariants"],
        "checkpoint_rollbacks": telemetry["checkpoint_rollbacks"],
        "replay_s": elapsed,
    }
    rows.append(
        Row(
            f"faults/chaos_real/j1xn{REAL_NODES}",
            elapsed * 1e6,
            f"retention={retention:.3f};"
            f"numeric={telemetry['detected']['numeric']};"
            f"viol={telemetry['invariants']['violations']}",
        )
    )
    return record


def run(smoke: bool = False):
    rows = []
    plan = FaultPlan.chaos(N_NODES, seed=SEED)

    # Chaos lane: the default plan on the standard trace -----------------
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        rep = _replay(faults=plan, checkpoint_dir=ckpt_dir)
    chaos_s = time.perf_counter() - t0
    telemetry = rep.runtime.fault_telemetry()
    assert telemetry is not None
    retention = rep.goodput_retention
    assert retention is not None

    # Overhead lane: injector + monitor present but idle (no faults) -----
    t0 = time.perf_counter()
    base = _replay()
    plain_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    monitored = _replay(health=True)
    monitored_s = time.perf_counter() - t0
    overhead = (monitored_s - plain_s) / plain_s if plain_s > 0 else 0.0
    # The observation-only guarantee: an idle monitor changes nothing.
    assert monitored.runtime.allocation.assignment == base.runtime.allocation.assignment

    record = {
        "n_jobs": N_JOBS,
        "n_nodes": N_NODES,
        "seed": SEED,
        "epochs_per_event": EPOCHS_PER_EVENT,
        "plan": plan.describe(),
        "goodput_retention": retention,
        "retention_gate": RETENTION_GATE,
        "detection_latency_epochs": telemetry["detection_latency_epochs"],
        "mttr_epochs": telemetry["mttr_epochs"],
        "mttr_sim_seconds": telemetry["mttr_sim_seconds"],
        "detected": telemetry["detected"],
        "recoveries": telemetry["recoveries"],
        "faulted_sim_time": rep.total_sim_time,
        "fault_free_sim_time": rep.baseline.total_sim_time,
        "chaos_replay_s": chaos_s,
        "monitor_overhead_frac": overhead,
    }
    rows.append(
        Row(
            f"faults/chaos/j{N_JOBS}xn{N_NODES}",
            chaos_s * 1e6,
            f"retention={retention:.3f};lat={telemetry['detection_latency_epochs']}ep;"
            f"mttr={telemetry['mttr_epochs']}ep",
        )
    )
    rows.append(
        Row(
            f"faults/monitor_idle/j{N_JOBS}xn{N_NODES}",
            monitored_s * 1e6,
            f"overhead={overhead * 100:.1f}%",
        )
    )

    # Gate: detection + recovery must retain >= 80% of fault-free
    # throughput on the standard trace under the default chaos plan.
    # The replay is deterministic, so the gate holds in smoke runs too.
    del smoke
    assert retention >= RETENTION_GATE, (
        f"goodput retention {retention:.3f} below gate {RETENTION_GATE}"
    )

    # Real-backend integrity lane ----------------------------------------
    record["real"] = _run_real_lane(rows)

    # Merge into the sweep artifact (keep every other lane's record).
    sweep_path = os.path.join(ARTIFACTS, "bench", "sweep.json")
    payload = {}
    if os.path.exists(sweep_path):
        try:
            with open(sweep_path) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError):
            payload = {}
    payload["faults"] = record
    save_json("sweep", payload)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="accepted for lane-runner symmetry (already CI-sized)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(smoke=args.smoke):
        print(row.csv(), flush=True)


if __name__ == "__main__":
    main()
