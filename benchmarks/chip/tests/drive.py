"""Drive a whole run of a cell at a small preset on the CPU, optionally with
the program's timed path broken underneath (for the fault tests).

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tests/drive.py olmo-1b.clusterA-adaptive half_batch

prints the result line's object as JSON.  Faults:

- ``frozen``: the step returns its parameters and optimizer state unchanged;
- ``half_batch``: each node's second half of rows is masked out, so its
  mean is taken over the rest;
- ``even_split``: the controller's plans, once it has node models, split
  their total evenly;
- ``fixed_total``: the controller keeps the reference total, as if it were
  not adaptive;
- ``plain_gns``: the controller's noise estimate weighs every node alike
  (the homogeneous estimator) in place of Theorem 4.1's weights.
"""
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

STEP_FAULTS = ("frozen", "half_batch")
PLAN_FAULTS = ("even_split", "fixed_total", "plain_gns")
FAULTS = STEP_FAULTS + PLAN_FAULTS


def _broken(body, fault):
    import jax.numpy as jnp

    def build(*args, **kw):
        step = body(*args, **kw)

        def run(params, opt_state, tokens, labels, mask, r, lr_scale, poison):
            if fault == "half_batch":
                counts = mask.sum(axis=1, keepdims=True)
                keep = jnp.arange(mask.shape[1])[None, :] < jnp.maximum(1.0, jnp.floor(counts / 2))
                mask = mask * keep
            out = step(params, opt_state, tokens, labels, mask, r, lr_scale, poison)
            if fault == "frozen":
                return (params, opt_state) + tuple(out[2:])
            return out

        return run

    return build


def _even(plan_epoch):
    def run(self, **kw):
        plan = plan_epoch(self, **kw)
        if plan.phase == "bootstrap":  # its distinct sizes fit the node models
            return plan
        n, total = len(plan.batches), plan.total_batch
        even = tuple(total // n + (i < total % n) for i in range(n))
        return dataclasses.replace(plan, batches=even)

    return run


@contextlib.contextmanager
def planted(fault):
    """The program with ``fault`` planted in its step or its controller
    (None: as it is)."""
    from repro.core import controller, gns
    from repro.runtime import backend

    cls = controller.CannikinController
    saved = (backend.node_step_body, cls.plan_epoch, controller.estimate_gns, cls.__init__)
    try:
        if fault in STEP_FAULTS:
            backend.node_step_body = _broken(saved[0], fault)
        elif fault == "even_split":
            cls.plan_epoch = _even(saved[1])
        elif fault == "fixed_total":
            cls.__init__ = lambda self, *a, **kw: saved[3](self, *a, **dict(kw, adaptive=False))
        elif fault == "plain_gns":
            controller.estimate_gns = lambda sq, g, b, **kw: gns.homogeneous_gns(sq, g, b)
        elif fault is not None:
            raise ValueError(fault)
        yield
    finally:
        backend.node_step_body, cls.plan_epoch, controller.estimate_gns, cls.__init__ = saved


def run_tiny(cell, fault=None, *, dtype="float32", trace=False, seed=2**31 + 77):
    from chipbench.cell import run_cell
    from presets import tiny

    cfg, mix = tiny(cell, dtype)
    with planted(fault):
        return run_cell(cell, seed, 1.0, trace, t_start=time.time(), cfg=cfg, mix=mix,
                        require_tpu=False, log=lambda s: None)


if __name__ == "__main__":
    fault = sys.argv[2] if len(sys.argv) > 2 and sys.argv[2] != "none" else None
    print(json.dumps(run_tiny(sys.argv[1], fault)))
