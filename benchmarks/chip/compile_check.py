#!/usr/bin/env python3
"""Compile a cell's programs at their real sizes for a described TPU v5e
(``v5e:2x2``), with no chip, and print each one's ``memory_analysis``.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py \\
        --workload olmo-1b.clusterA-adaptive [--layers 6,7]

Compiles the program's one-chip vmapped step at the mix's node count,
``b_max`` 16 and sequence length, and the float32 reference's per-node
gradient at the widest node's rows.  ``--layers``
compiles the program's step at other depths instead, to find the deepest
that fits.  Nothing runs; a compile that passes is not a chip run.
"""
import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
B_MAX = 16


def hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def report(what: str, compiled) -> dict:
    m = compiled.memory_analysis()
    row = {"program": what, "argument": m.argument_size_in_bytes,
           "output": m.output_size_in_bytes, "alias": m.alias_size_in_bytes,
           "temp": m.temp_size_in_bytes, "total": hbm_bytes(compiled)}
    print(json.dumps(row), flush=True)
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", default="")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import spec as specs
    from chipbench.cell import program_api
    from chipbench.reference import RefTrainer
    from repro.core.aggregation import ANOMALY_OUTLIER_FACTOR
    from repro.optim.optimizers import constant_schedule, sgd
    from repro.runtime.backend import STATE_DONATION, node_step_body

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    c = specs.cell(args.workload)
    mix = c["mix"]
    n, seq = len(mix["nodes"]), int(mix["seq_len"])
    depths = [int(x) for x in args.layers.split(",") if x] or [c["cfg"]["n_layers"]]
    one = SingleDeviceSharding(topo.devices[0])

    def shapes(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)

    for layers in depths:
        cfg = dict(c["cfg"], n_layers=layers)
        trainer = RefTrainer(cfg)
        api = program_api(cfg, trainer.mod)
        opt = sgd(constant_schedule(mix["lr"]))
        step = node_step_body(api, opt, outlier_factor=ANOMALY_OUTLIER_FACTOR)
        params = shapes(jax.eval_shape(api.init, jax.random.PRNGKey(0)), one)
        opt_state = shapes(jax.eval_shape(opt.init, params), one)
        tok = jax.ShapeDtypeStruct((n, B_MAX, seq), jnp.int32, sharding=one)
        msk = jax.ShapeDtypeStruct((n, B_MAX), jnp.float32, sharding=one)
        vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one)
        scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
        compiled = jax.jit(step, donate_argnums=STATE_DONATION).lower(
            params, opt_state, tok, tok, msk, vec, scalar, vec).compile()
        report(f"{cfg['name']} L{layers} program step n={n} b_max={B_MAX} seq={seq}", compiled)
        if args.layers:
            continue
        rows = max(mix["check_split"])
        t = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one)
        compiled = trainer._grad.lower(params, t, t).compile()
        report(f"{cfg['name']} L{layers} reference node gradient rows={rows} seq={seq}",
               compiled)
    return 0


if __name__ == "__main__":
    sys.exit(main())
