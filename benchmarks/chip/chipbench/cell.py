"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

The window drives the objects the program's own launcher builds for a
heterogeneous job: a ``SimulatedCluster`` from the mix's node models, a
``CannikinController`` (adaptive, the mix's candidates, reference batch and
per-node cap), a ``RealBackend`` (SGD, the mix's learning rate, the
benchmark's own token stream, one node per chip where the mix says
``sharded``) and an ``EpochLoop`` on its default epoch path.

Set-up, all of it timed as ``setup_s``: the weights, made on the device in
one program from the seed; three steps through ``RealBackend.execute`` at
the mix's ``check_split`` (the window's padded width), which the reference
follows later; the controller's bootstrap epochs.  The window then runs
whole epochs until ``seconds`` have passed.

``correct`` compares the three steps with the float32 reference
(``chipbench.reference``) and every plan the controller made, bootstrap
and window, with the float64 references of its estimate and its plan
(``chipbench.planner``).
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import spec as specs
from chipbench import trace as tr
from chipbench.flops import flops_per_token
from chipbench.planner import compare_plans
from chipbench.reference import RefTrainer, change_norms, compare, leaf_norms, weight_seed
from chipbench.traffic import make_cluster, make_stream

EPOCH_SPAN = "bench.epoch"
CHECK_STEPS = 3
# Epochs the controller may spend in bootstrap before the window; it needs
# two (one even split, one inverse-speed split) to fit its node models.
MAX_BOOTSTRAP_EPOCHS = 4
class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events (the same listeners as the repository's chip smoke)."""

    def __init__(self) -> None:
        import jax

        self.compiles: List[Tuple[str, float]] = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((str(kw.get("fun_name", "?")), float(duration)))

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> Tuple[int, int]:
        return len(self.compiles), self.cache_hits


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform} devices and no TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def program_api(cfg: Dict[str, Any], ref_mod):
    """The program's model at the configuration's sizes (``adapters/``),
    whose ``init`` makes the benchmark's weights: the reference's layout,
    from the key the backend passes, which ``weight_seed`` chose."""
    adapter = specs.module("adapters", cfg["adapter"])
    return adapter.program_api(cfg, lambda key: ref_mod.init(cfg, key))


def _epoch_failures(result) -> int:
    bad = 0
    for loss, obs in zip(result.losses, result.grad_observations):
        if not math.isfinite(loss) or not obs.all_valid:
            bad += 1
    return bad


def _epoch_entry(rec, res, window: bool) -> Dict[str, Any]:
    """What the planner's reference reads of one epoch."""
    n = len(rec.batches)
    return {
        "batches": [int(b) for b in rec.batches], "total": int(rec.total_batch),
        "b_noise": float(rec.b_noise), "phase": rec.phase, "window": window,
        "steps": [{"local_sqnorms": [float(x) for x in o.local_sqnorms],
                   "global_sqnorm": float(o.global_sqnorm),
                   "batches": [int(b) for b in o.batches],
                   "valid": list(o.valid) or [True] * n}
                  for o in res.grad_observations],
    }


def _compiled_step_bytes(backend, n: int, seq: int, log) -> int:
    """HBM the widest compiled step needs while it runs (arguments, outputs
    not aliased to them, temporaries), from its ``memory_analysis``; 0
    where the backend's step cannot be found.  The live-buffer peak JAX
    reports leaves out a program's temporaries."""
    import jax

    try:
        b_max = max(backend._step_cache)
        fn = backend._step_cache[b_max]

        where = jax.tree_util.tree_leaves(backend.params)[0].sharding

        def spec(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where)

        tok = jax.ShapeDtypeStruct((n, b_max, seq), "int32", sharding=where)
        msk = jax.ShapeDtypeStruct((n, b_max), "float32", sharding=where)
        vec = jax.ShapeDtypeStruct((n,), "float32", sharding=where)
        one = jax.ShapeDtypeStruct((), "float32", sharding=where)
        m = fn.lower(jax.tree_util.tree_map(spec, backend.params),
                     jax.tree_util.tree_map(spec, backend.opt_state),
                     tok, tok, msk, vec, one, vec).compile().memory_analysis()
    except Exception as e:  # the program's internals moved: say so, report the rest
        log(f"compiled step size not read: {type(e).__name__}: {e}")
        return 0
    size = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    log(f"compiled step b_max {b_max}: {size} bytes (temp {m.temp_size_in_bytes})")
    return int(size)


def _memory_peak(devices, step_bytes: int) -> int:
    """The fullest chip's peak: the larger of JAX's live-buffer peak and the
    compiled step's own need."""
    peaks = [step_bytes]
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def build_backend(cfg, mix, seed: int, trainer: RefTrainer, stream):
    """The program's ``RealBackend`` for the mix, with the seed's weights."""
    import jax

    from repro.optim.optimizers import constant_schedule, sgd
    from repro.runtime.backend import RealBackend

    backend = RealBackend(
        program_api(cfg, trainer.mod), sgd(constant_schedule(mix["lr"])), stream,
        cluster=make_cluster(mix, seed), seed=weight_seed(seed),
        sharded=bool(mix["sharded"]),
    )
    jax.block_until_ready(backend.params)
    return backend


def check_steps(backend, trainer: RefTrainer, split, seed: int, d0):
    """The first ``CHECK_STEPS`` steps of ``backend`` at ``split``, through
    the window's own call (``RealBackend.execute``) and feed; returns the
    program's readings in ``RefTrainer.run``'s terms and the count of steps
    with a non-finite loss or a node excluded by the anomaly guard."""
    import jax

    first = backend.execute(split, 1)
    mom1 = np.asarray(leaf_norms(backend.opt_state.momentum))
    rest = backend.execute(split, CHECK_STEPS - 1)
    p0 = trainer.init(seed)
    change = np.asarray(change_norms(jax.device_put(backend.params, d0), p0))
    del p0
    done = [first, rest]
    prog = {
        "losses": [x for r in done for x in r.losses],
        "sq_i": [list(o.local_sqnorms) for r in done for o in r.grad_observations],
        "mom1_norms": mom1,
        "change_norms": change,
    }
    return prog, sum(_epoch_failures(r) for r in done)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             cfg: Optional[Dict[str, Any]] = None, mix: Optional[Dict[str, Any]] = None,
             require_tpu: bool = True, record: Optional[Dict[str, Any]] = None,
             log=print) -> Dict[str, Any]:
    """Run cell ``name`` once and return the result line's object.

    ``cfg``/``mix`` replace the cell's configuration and traffic files (the
    CPU tests pass small presets, and keep the cell's limits);
    ``require_tpu=False`` lets it run on whatever JAX finds.  ``record``,
    where given, receives what the references read and gave."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core.controller import CannikinController
    from repro.launch.compile_cache import configure_compile_cache
    from repro.runtime.backend import EpochLoop

    c = specs.cell(name)
    cfg = cfg or c["cfg"]
    mix = mix or c["mix"]
    chips = int(c["chips"])
    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = devices_for(chips, require_tpu)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}
    log(f"device: {device['kind']} x{device['count']} ({device['platform']}); "
        f"cell {name}; seed {seed}; compile cache {cache_dir}")
    compile_log = CompileLog()

    trainer = RefTrainer(cfg, device=d0)
    seq = int(mix["seq_len"])
    split = [int(b) for b in mix["check_split"]]
    stream = make_stream(mix, cfg["vocab"], seed)
    backend = build_backend(cfg, mix, seed, trainer, stream)
    prog, check_failures = check_steps(backend, trainer, split, seed, d0)

    mix_nodes = len(mix["nodes"])
    ctrl = CannikinController(
        mix_nodes, batch_candidates=mix["candidates"], ref_batch=mix["ref_batch"],
        adaptive=True, max_local=mix["max_local"], gns_decay=mix["gns_decay"],
    )
    loop = EpochLoop(ctrl, backend, steps_per_epoch=int(mix["steps_per_epoch"]))
    planned: List[Dict[str, Any]] = []
    while not ctrl.can_model():
        if len(planned) == MAX_BOOTSTRAP_EPOCHS:
            raise RuntimeError("the controller never left its bootstrap phase")
        with TraceAnnotation(EPOCH_SPAN):
            rec = loop.run_epoch()
        planned.append(_epoch_entry(rec, loop.last_result, window=False))
        log(f"setup epoch {rec.epoch} [{rec.phase}] split={list(rec.batches)}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    mark = compile_log.mark()
    ctrl0 = ctrl.stats.overhead_seconds
    setup_s = time.time() - t_start
    epochs: List[Dict[str, Any]] = []
    attempted = failed = useful_tokens = 0
    t0 = time.perf_counter()
    with TraceAnnotation(tr.WINDOW_SPAN):
        while True:
            with TraceAnnotation(EPOCH_SPAN):
                e0 = time.perf_counter()
                rec = loop.run_epoch()
                e1 = time.perf_counter()
            res = loop.last_result
            steps = len(res.losses)
            attempted += steps
            failed += _epoch_failures(res)
            useful_tokens += steps * int(sum(rec.batches)) * seq
            epochs.append({"split": list(rec.batches), "phase": rec.phase,
                           "seconds": e1 - e0})
            planned.append(_epoch_entry(rec, res, window=True))
            if e1 - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    ctrl_s = ctrl.stats.overhead_seconds - ctrl0
    if trace_dir:
        jax.profiler.stop_trace()
    in_window = compile_log.mark()
    memory_peak = _memory_peak(devices, _compiled_step_bytes(backend, mix_nodes, seq, log))
    window_steps = attempted
    for e in epochs:
        log(f"window epoch [{e['phase']}] split={e['split']} {e['seconds']:.4f} s")
    log(f"window: {len(epochs)} epochs, {window_steps} steps, {window_s:.4f} s, "
        f"{useful_tokens} useful tokens; compiles in window "
        f"{in_window[0] - mark[0]}, persistent-cache loads in window "
        f"{in_window[1] - mark[1]}; compiles in all {in_window[0]} "
        f"({sum(s for _, s in compile_log.compiles):.3f} s)")

    del loop, ctrl, backend
    gc.collect()

    batches = [stream.batch(s, sum(split)) for s in range(CHECK_STEPS)]
    t_ref = time.perf_counter()
    ref = trainer.run(seed, batches, split, mix["lr"])
    numbers = compare(prog, ref)
    numbers.update(compare_plans(mix, {"epochs": planned}))
    if record is not None:
        record.update(epochs=planned, ref=ref, batches=batches, split=split)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")

    limits = c["limits"]
    checks: Dict[str, Dict[str, Any]] = {}
    correct = check_failures == 0 and limits is not None
    for key, num in numbers.items():
        lim = None if limits is None else limits["limits"].get(key)
        ok = lim is not None and num["value"] <= lim
        correct = correct and ok
        checks[key] = {"value": num["value"], "limit": lim}

    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {},
        "device": dict(device, memory_peak_bytes=memory_peak),
    }
    units = {m["name"]: m["unit"] for m in c["end_to_end"] + c["per_layer"]}
    if not trace:
        values = {"tokens_per_s": useful_tokens / window_s, "setup_s": setup_s}
        for m in c["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": units[m["name"]]}
    else:
        reduced = None
        xplane = tr.find_xplane(trace_dir)
        if xplane:
            reduced = tr.reduce_events(tr.load_events(xplane))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = {
            "trace": reduced,
            "steps": window_steps,
            "epochs": len(epochs),
            "useful_tokens": useful_tokens,
            "controller_s": ctrl_s,
            "chips": len(devices),
            "window_s": window_s,
            "flops_per_token": flops_per_token(
                cfg, seq, trainer.mod.matmul_params(cfg)),
            "peak_flops": None,
        }
        if reduced is not None:
            run["peak_flops"] = specs.peak(device["kind"])["bf16_flops_per_s"]
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            log(f"trace: busy {reduced['busy_s']:.6f} s of {reduced['window_s']:.6f} s "
                f"over {reduced['devices']} devices; collectives "
                f"{reduced['collective_ops']} ops, {reduced['collective_s']:.6f} s")
        for m in c["per_layer"]:
            value = specs.reader(m["name"])(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": units[m["name"]]}
    for key, num in numbers.items():
        extra = {k: v for k, v in num.items() if k != "value"}
        log(f"compared {key}: {num['value']:.6g} limit {checks[key]['limit']} {extra}")
    result["checks"] = checks
    return result


def print_checks(result: Dict[str, Any]) -> None:
    """Each compared number beside its limit, as the last lines on stderr."""
    for key, chk in result["checks"].items():
        print(f"check {key} {chk['value']:.6g} limit {chk['limit']}", file=sys.stderr)
    print(f"correct {str(result['correct']).lower()}", file=sys.stderr, flush=True)
