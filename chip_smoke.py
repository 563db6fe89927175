#!/usr/bin/env python3
"""Chip smoke test: the adaptive heterogeneous trainer on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # sharded node mesh over four chips

One chip (the default) drives the main training path once: the Cannikin
controller plans uneven per-node batches for the paper's 3-node cluster A
(Table 2), and ``RealBackend`` takes real gradient steps with the Eq. (9)
aggregation and the Theorem-4.1 GNS tracker, through ``EpochLoop`` -- the
objects ``repro.launch.train`` builds for ``--mode hetero``.  The model is
OLMo-1B at its published widths with random weights from ``--seed``; the
per-node timing is the simulated cluster clock, as everywhere in this repo.
Two bootstrap epochs and one optperf epoch run the two-program path, then
two epochs run the fused on-device epoch program.

``--four-chips`` runs only the sharded path: four nodes over four chips
(``RealBackend(sharded=True)``, the shard_map backward with the Eq. (9)
psum), compared step by step with the one-chip vmap backward on the same
seed and batches.

Every phase's result is checked; any failed check or exception exits
non-zero.  On success the last line of stdout is one JSON object naming the
device.  There is no CPU fallback: without a TPU the script exits non-zero
before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "olmo-1b"
# Cuts of OLMo-1B (published: 16 layers, sequences of 2048), widths kept.
# Depth: the vmapped 3-node backward keeps one gradient copy per node; at
# b_max 16 and 512 tokens, 8 layers compile to 14.45 GB of the v5e's
# 15.75 GB (argument + output - alias + temp) and 9 layers to 15.86 GB.
LAYERS = 8
SEQ = 512
# Four-chip parity: the one-chip vmap reference over 4 nodes must fit too.
FOUR_CHIP_LAYERS = 4
LR = 0.3
STEPS = 4
# Candidate totals and the per-node cap keep every planned local batch at
# or under 16, so b_max (padded to a multiple of 8) never exceeds 16.
CANDIDATES = (12, 18, 24)
REF_BATCH = 12
MAX_LOCAL = 16
TWO_PROGRAM_EPOCHS = 3
FUSED_EPOCHS = 2
# First loss: ln(vocab) for uniform logits; the fan-in-scaled head after a
# normalised final layer adds about 0.5.
LOSS_BAND = 0.15
# Sharded vs vmap, bf16 model: per-node backward on different devices and
# a different reduction order for the global loss and the psum.
PARITY_LOSS_RTOL = 1e-2
PARITY_SQ_RTOL = 5e-2
# Params are bf16: allow two bf16 ulps (2**-6 relative) or 1e-3 absolute.
PARITY_PARAM_RTOL = 2.0 ** -6
PARITY_PARAM_ATOL = 1e-3


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


class CompileLog:
    """Backend compiles, from JAX's own monitoring events (a persistent
    cache hit is counted too, with its retrieval time)."""

    def __init__(self) -> None:
        import jax

        self.events: List[Tuple[str, float]] = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), float(duration)))

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def since(self, mark: int) -> List[Tuple[str, float]]:
        return self.events[mark:]

    def report(self) -> None:
        big = [(n, s) for n, s in self.events if s >= 1.0]
        print(f"compiles: {len(self.events)} programs, "
              f"{sum(s for _, s in self.events):.3f} s in all, "
              f"persistent-cache hits {self.cache_hits}")
        for name, secs in big:
            print(f"  compile {name}: {secs:.3f} s")


def device_line(devices) -> Dict[str, object]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {devices[0].platform} devices; "
              "nothing was run", file=sys.stderr)
        sys.exit(2)
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    return devices


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def tree_host(tree):
    import jax
    import numpy as np

    return [np.asarray(jax.device_get(x), np.float32)
            for x in jax.tree_util.tree_leaves(tree)]


# ---------------------------------------------------------------------------
# one chip: the adaptive epoch loop
# ---------------------------------------------------------------------------


def run_one_chip(api, *, seq: int, seed: int, log: CompileLog) -> Dict[str, object]:
    """Train ``api`` on cluster A through the cannikin EpochLoop; check and
    return the results."""
    import jax

    from repro.core.controller import CannikinController
    from repro.core.simulator import SimulatedCluster, cluster_A
    from repro.data import SyntheticLM
    from repro.optim import constant_schedule, sgd
    from repro.runtime import EpochLoop, RealBackend

    profiles, comm = cluster_A()
    sim = SimulatedCluster(profiles, comm, noise=0.01, seed=seed)
    data = SyntheticLM(vocab=api.cfg.vocab, seq_len=seq, seed=seed)
    t0 = time.perf_counter()
    backend = RealBackend(
        api, sgd(constant_schedule(LR)), data, cluster=sim, seed=seed
    )
    jax.block_until_ready(backend.params)
    print(f"init: {time.perf_counter() - t0:.3f} s wall "
          f"({api.param_count():,} params)")
    ctrl = CannikinController(
        sim.n, batch_candidates=CANDIDATES, ref_batch=REF_BATCH,
        adaptive=True, max_local=MAX_LOCAL,
    )
    loop = EpochLoop(ctrl, backend, steps_per_epoch=STEPS)
    print(f"cluster A nodes: {[p.name for p in profiles]}; candidates "
          f"{list(CANDIDATES)}, ref {REF_BATCH}, max local {MAX_LOCAL}, "
          f"{STEPS} steps/epoch")

    records = []
    all_losses: List[float] = []
    steady_tps = []
    for e in range(TWO_PROGRAM_EPOCHS + FUSED_EPOCHS):
        loop.fused = e >= TWO_PROGRAM_EPOCHS
        mark = len(log.events)
        t0 = time.perf_counter()
        rec = loop.run_epoch()  # ends in a host sync: float(loss) / device_get
        wall = time.perf_counter() - t0
        compiled = log.since(mark)
        tokens = STEPS * rec.total_batch * seq
        tps = tokens / wall
        if not compiled:
            steady_tps.append(tps)
        epoch_losses = list(loop.last_result.losses)
        all_losses.extend(epoch_losses)
        print(
            f"epoch {rec.epoch} [{rec.phase}] {'fused' if loop.fused else 'two-program'}"
            f" total={rec.total_batch} split={list(rec.batches)}"
            f" wall={wall:.3f}s tokens/s={tps:.1f}"
            f" compiles_in_epoch={len(compiled)}"
            f" ({sum(s for _, s in compiled):.3f}s)"
            f" losses={[round(x, 4) for x in epoch_losses]}"
            f" simulated_cluster_batch_time={rec.measured_batch_time * 1e3:.3f}ms"
            f" simulated_cluster_clock={loop.sim_time:.3f}s",
            flush=True,
        )
        records.append(rec)

    log.report()
    s = ctrl.stats
    peak = peak_bytes(jax.devices()[0])
    print(f"peak_bytes_in_use: {peak} ({peak / 2**30:.3f} GiB)")
    print(f"memory_stats: {jax.devices()[0].memory_stats()}")
    if steady_tps:
        print(f"tokens/s (epochs without a compile): "
              f"{', '.join(f'{x:.1f}' for x in steady_tps)}")
    print(f"controller: fused_plans={s.fused_plans} "
          f"fused_certifications={s.fused_certifications} "
          f"fused_cert_failures={s.fused_cert_failures} "
          f"fused_max_rel_err={s.fused_max_rel_err:.3e}")
    print("engine_degradations: n/a (no CannikinPolicy on the EpochLoop path)")
    print(f"b_noise: controller={ctrl.gns.b_noise:.6g} "
          f"backend={backend.gns.b_noise:.6g}; "
          f"anomalous_steps={backend.anomalous_steps}")

    ln_v = math.log(api.cfg.vocab)
    check(all(math.isfinite(x) for x in all_losses),
          f"all {len(all_losses)} losses finite")
    check(abs(all_losses[0] - ln_v) <= LOSS_BAND * ln_v,
          f"first loss {all_losses[0]:.4f} within {LOSS_BAND:.0%} of "
          f"ln({api.cfg.vocab}) = {ln_v:.4f}")
    b_noise = ctrl.gns.b_noise
    check(math.isfinite(b_noise) and b_noise > 0, f"b_noise {b_noise:.6g} finite and > 0")
    check(any(r.phase == "optperf" for r in records), "optperf phase reached")
    check(any(r.phase == "optperf" and len(set(r.batches)) > 1 for r in records),
          "optperf splits uneven")
    check(max(max(r.batches) for r in records) <= MAX_LOCAL,
          f"every local batch <= {MAX_LOCAL}")
    check(s.fused_plans >= 1, f"fused_plans {s.fused_plans} >= 1")
    check(s.fused_cert_failures == 0, "fused_cert_failures == 0")
    check(backend.anomalous_steps == 0, "no step excluded a node (anomaly guard)")
    return {"peak_bytes": peak, "losses": all_losses}


# ---------------------------------------------------------------------------
# four chips: sharded node mesh vs the one-chip vmap backward
# ---------------------------------------------------------------------------

FOUR_NODE_PLANS = ([8, 6, 3, 7], [5, 8, 8, 2])
FOUR_NODE_STEPS = 2


def drive_four_nodes(api, *, seq: int, seed: int, sharded: bool):
    from repro.core.simulator import SimulatedCluster, make_cluster
    from repro.data import SyntheticLM
    from repro.optim import constant_schedule, sgd
    from repro.runtime import RealBackend

    profiles, comm = make_cluster(["a5000", "a4000", "p4000", "a4000"])
    backend = RealBackend(
        api, sgd(constant_schedule(LR)),
        SyntheticLM(vocab=api.cfg.vocab, seq_len=seq, seed=seed),
        cluster=SimulatedCluster(profiles, comm, noise=0.0, seed=seed),
        seed=seed, sharded=sharded,
    )
    results = []
    for plan in FOUR_NODE_PLANS:
        t0 = time.perf_counter()
        results.append(backend.execute(plan, FOUR_NODE_STEPS))
        print(f"  {'sharded' if sharded else 'vmap   '} plan={plan} "
              f"losses={[f'{x:.5f}' for x in results[-1].losses]} "
              f"wall={time.perf_counter() - t0:.3f}s", flush=True)
    return backend, results


def run_four_chips(api, *, seq: int, seed: int, log: CompileLog) -> None:
    import jax
    import numpy as np

    devices = jax.devices()[:4]
    print(f"sharded: 4 nodes over {len(devices)} chips "
          f"(mesh ('nodes',)), plans {list(FOUR_NODE_PLANS)}, "
          f"{FOUR_NODE_STEPS} steps each")
    from repro.launch.mesh import node_shard_count

    mesh_size = node_shard_count(4)
    backend, shard_res = drive_four_nodes(api, seq=seq, seed=seed, sharded=True)
    replicated = all(
        len(x.sharding.device_set) == len(devices)
        for x in jax.tree_util.tree_leaves(backend.params)
    )
    shard_params = tree_host(backend.params)
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(backend.params)
    )
    del backend
    peaks = [peak_bytes(d) for d in devices]
    for d, p in zip(devices, peaks):
        print(f"  device {d.id}: peak_bytes_in_use {p} ({p / 2**30:.3f} GiB)")

    print("one-chip vmap reference: the same 4 nodes, seed and batches")
    ref, vmap_res = drive_four_nodes(api, seq=seq, seed=seed, sharded=False)
    vmap_params = tree_host(ref.params)
    del ref
    log.report()

    loss_s = np.asarray([x for r in shard_res for x in r.losses])
    loss_v = np.asarray([x for r in vmap_res for x in r.losses])
    sqi_s = np.asarray([o.local_sqnorms for r in shard_res for o in r.grad_observations])
    sqi_v = np.asarray([o.local_sqnorms for r in vmap_res for o in r.grad_observations])
    sqg_s = np.asarray([o.global_sqnorm for r in shard_res for o in r.grad_observations])
    sqg_v = np.asarray([o.global_sqnorm for r in vmap_res for o in r.grad_observations])
    rel = lambda a, b: float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
    p_diff = max(float(np.max(np.abs(a - b))) for a, b in zip(shard_params, vmap_params))
    p_close = all(
        np.allclose(a, b, rtol=PARITY_PARAM_RTOL, atol=PARITY_PARAM_ATOL)
        for a, b in zip(shard_params, vmap_params)
    )
    p_equal = sum(int((a == b).sum()) for a, b in zip(shard_params, vmap_params))
    p_total = sum(a.size for a in vmap_params)
    print(f"parity: loss max rel {rel(loss_s, loss_v):.3e}, sq_i max rel "
          f"{rel(sqi_s, sqi_v):.3e}, sq_g max rel {rel(sqg_s, sqg_v):.3e}, "
          f"params max abs {p_diff:.3e} ({p_equal / p_total:.6f} of elements equal)")

    check(mesh_size == 4, f"node mesh spans {mesh_size} chips")
    check(replicated, "params replicated on all 4 chips after the sharded steps")
    check(all(p >= param_bytes for p in peaks),
          f"every chip's peak holds at least the {param_bytes} param bytes")
    check(min(peaks) >= 0.5 * max(peaks),
          "no chip's peak under half the largest (nothing landed on one chip)")
    check(bool(np.isfinite(loss_s).all()), "sharded losses finite")
    check(rel(loss_s, loss_v) <= PARITY_LOSS_RTOL,
          f"losses agree within rel {PARITY_LOSS_RTOL}")
    check(rel(sqi_s, sqi_v) <= PARITY_SQ_RTOL, f"sq_i agree within rel {PARITY_SQ_RTOL}")
    check(rel(sqg_s, sqg_v) <= PARITY_SQ_RTOL, f"sq_g agree within rel {PARITY_SQ_RTOL}")
    check(p_close, f"params agree within rel {PARITY_PARAM_RTOL} + abs "
          f"{PARITY_PARAM_ATOL} elementwise")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip path and its "
                         "one-chip vmap reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script: {e}",
              file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)

    from repro.configs import get_api

    layers = FOUR_CHIP_LAYERS if args.four_chips else LAYERS
    api = get_api(ARCH, n_layers=layers)
    cfg = api.cfg
    dev = device_line(devices)
    print(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}); "
          f"compile cache {cache_dir}")
    print(f"model: {ARCH} d_model={cfg.d_model} heads={cfg.n_heads}x{cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} {cfg.param_dtype.__name__}; "
          f"cuts: depth {layers} of 16 layers, sequences {SEQ} tokens "
          f"(OLMo trains at 2048)")
    log = CompileLog()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            run_four_chips(api, seq=SEQ, seed=args.seed, log=log)
        else:
            run_one_chip(api, seq=SEQ, seed=args.seed, log=log)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(f"total wall: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
