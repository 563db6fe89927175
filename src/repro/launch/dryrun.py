import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and record memory/cost/collective statistics.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices let jax.make_mesh build the
16x16 (single-pod) and 2x16x16 (multi-pod) meshes; `.lower().compile()`
must succeed for every combination; `memory_analysis()` proves fit;
`cost_analysis()` + HLO collective parsing feed the §Roofline report.

Results are written incrementally to JSON (one file per combo) so reruns
skip finished work:  artifacts/dryrun/<arch>__<shape>__<mesh>.json

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, arch_ids, get_api
from repro.launch.hlo_stats import analyze_hlo
from repro.sharding.context import sharding_context
from repro.launch.mesh import (
    make_production_mesh,
    make_rules,
    train_microbatches,
)
from repro.models import common
from repro.optim import adamw, constant_schedule
from repro.train.step import build_train_step

DTYPE_BYTES = {
    "pred": 0.125, "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|((?:[a-z0-9]+)\[[0-9,]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += int(n * DTYPE_BYTES[dtype])
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Per-device bytes moved by collectives, from the optimized HLO.

    Convention: a collective op's cost is the byte size of its (tuple)
    result — for all-gather that is the gathered buffer, for all-reduce the
    reduced buffer, for reduce-scatter the scattered shard (we add operand
    sizes would double-count fusions; the result-size convention is uniform
    and monotone in actual link traffic).
    """
    per_kind: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        tuple_body, single, kind = m.group(1), m.group(2), m.group(3)
        text = tuple_body if tuple_body is not None else single
        b = _shape_bytes(text or "")
        per_kind[kind] = per_kind.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return {
        "bytes_by_kind": per_kind,
        "count_by_kind": counts,
        "total_bytes": int(sum(per_kind.values())),
    }


def _memory_analysis_dict(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        if hasattr(ma, attr):
            out[attr] = int(getattr(ma, attr))
    if not out:
        out["repr"] = str(ma)
    return out


def _cost_analysis_dict(compiled) -> Dict[str, Any]:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    keep = ("flops", "transcendentals", "bytes accessed")
    return {
        k: float(v)
        for k, v in ca.items()
        if isinstance(v, (int, float)) and any(k.startswith(p) for p in keep)
        and "{" not in k.replace("{}", "")
    }


# ---------------------------------------------------------------------------
# step builders per shape kind
# ---------------------------------------------------------------------------


def build_dryrun(api, shape, mesh, rules):
    """Returns (fn, args_shapedtypes, in_shardings)."""
    arch_id = api.arch_id
    if shape.kind == "train":
        opt = adamw(constant_schedule(1e-4))
        batch_extent = int(np.prod([
            mesh.devices.shape[list(mesh.axis_names).index(a)]
            for a in rules.batch_axes
        ]))
        mb = train_microbatches(
            arch_id, global_batch=shape.global_batch, batch_extent=batch_extent
        )
        batch_specs = api.train_batch_specs(shape.global_batch, shape.seq_len)
        mb_shardings = {
            name: NamedSharding(
                mesh, rules.batch_spec(extra_dims=len(sds.shape) - 1)
            )
            for name, sds in batch_specs.items()
        }
        step = build_train_step(
            api,
            opt,
            microbatches=mb,
            with_metrics=False,
            microbatch_shardings=mb_shardings,
        )
        params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
        opt_sds = jax.eval_shape(opt.init, params_sds)
        pspecs = api.specs(rules)
        ospecs = _opt_specs(opt_sds, pspecs)
        bspecs = api.batch_sharding(rules, batch_specs)
        fn = lambda params, opt_state, batch: step(params, opt_state, batch)
        args = (params_sds, opt_sds, batch_specs)
        shardings = (
            _named(mesh, pspecs),
            _named(mesh, ospecs),
            _named(mesh, bspecs),
        )
        out_shardings = (_named(mesh, pspecs), _named(mesh, ospecs), None)
        return fn, args, shardings, out_shardings

    if shape.kind == "prefill":
        batch_specs = api.train_batch_specs(shape.global_batch, shape.seq_len)
        batch_specs.pop("labels", None)
        batch_specs.pop("weights", None)
        params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
        pspecs = api.specs(rules)
        bspecs = api.batch_sharding(rules, batch_specs)

        def fn(params, batch):
            logits = api.logits(params, batch)
            return logits[:, -1]  # next-token distribution

        args = (params_sds, batch_specs)
        shardings = (_named(mesh, pspecs), _named(mesh, bspecs))
        return fn, args, shardings, None

    # decode
    params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    cache_sds = jax.eval_shape(
        lambda: api.init_cache(shape.global_batch, shape.seq_len)
    )
    pspecs = api.specs(rules)
    cspecs = api.cache_specs(rules, shape.global_batch, shape.seq_len)
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    tok_spec = rules.spec(("batch", None), tok_sds.shape, path="tokens")

    def fn(params, cache, tokens, pos):
        return api.decode_step(params, cache, tokens, pos)

    args = (params_sds, cache_sds, tok_sds, pos_sds)
    shardings = (
        _named(mesh, pspecs),
        _named(mesh, cspecs),
        NamedSharding(mesh, tok_spec),
        NamedSharding(mesh, P()),
    )
    out_shardings = (None, _named(mesh, cspecs))
    return fn, args, shardings, out_shardings


def _named(mesh, specs):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _opt_specs(opt_sds, pspecs):
    """Optimizer moments inherit parameter specs; scalars replicated."""
    flat_p, _ = jax.tree_util.tree_flatten(pspecs, is_leaf=lambda x: isinstance(x, P))

    def match(sds_tree):
        flat_s, treedef = jax.tree_util.tree_flatten(sds_tree)
        # Moment trees mirror the params tree; step counters are scalars.
        out = []
        pi = 0
        for leaf in flat_s:
            if hasattr(leaf, "shape") and leaf.shape == ():
                out.append(P())
            else:
                out.append(flat_p[pi % len(flat_p)])
                pi += 1
        return jax.tree_util.tree_unflatten(treedef, out)

    return match(opt_sds)


# ---------------------------------------------------------------------------


def applicable(api, shape) -> bool:
    if shape.name == "long_500k" and not api.supports_long_context():
        return False
    return True


def run_one(arch_id: str, shape_name: str, mesh_kind: str, outdir: str, *, force=False) -> Dict:
    outpath = os.path.join(outdir, f"{arch_id}__{shape_name}__{mesh_kind}.json")
    if os.path.exists(outpath) and not force:
        with open(outpath) as f:
            return json.load(f)
    shape = SHAPES[shape_name]
    api = get_api(arch_id)
    record: Dict[str, Any] = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_kind,
        "kind": shape.kind,
        "param_count": api.param_count(),
    }
    if not applicable(api, shape):
        record["status"] = "skipped"
        record["reason"] = "long_500k requires sub-quadratic decode (DESIGN.md §5)"
        _write(outpath, record)
        return record

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = make_rules(
        mesh, arch_id, kind=shape.kind, global_batch=shape.global_batch
    )
    t0 = time.time()
    try:
        fn, args, in_shardings, out_shardings = build_dryrun(api, shape, mesh, rules)
        with jax.set_mesh(mesh), sharding_context(mesh, rules):
            jitted = jax.jit(
                fn,
                in_shardings=in_shardings,
                out_shardings=out_shardings,
            )
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        hlo_text = compiled.as_text()
        record.update(
            status="ok",
            lower_seconds=round(t_lower, 2),
            compile_seconds=round(t_compile, 2),
            n_devices=int(np.prod(mesh.devices.shape)),
            memory=_memory_analysis_dict(compiled),
            cost_raw=_cost_analysis_dict(compiled),
            collectives_raw=collective_bytes(hlo_text),
            # Trip-count-corrected per-device stats (launch/hlo_stats.py) —
            # the §Roofline source of truth (cost_raw counts while bodies
            # once; see EXPERIMENTS.md).
            hlo=analyze_hlo(hlo_text).as_dict(),
            fallbacks=rules.fallback_report(),
        )
    except Exception as e:
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    _write(outpath, record)
    return record


def _write(path: str, record: Dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = arch_ids() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_one(arch, shape, mesh_kind, args.out, force=args.force)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    flops = rec["hlo"].get("matmul_flops", 0)
                    cb = rec["hlo"].get("collective_bytes", 0)
                    extra = (
                        f"lower={rec['lower_seconds']}s compile={rec['compile_seconds']}s "
                        f"flops/dev={flops:.3g} coll={cb/1e6:.1f}MB"
                    )
                elif status == "error":
                    failures += 1
                    extra = rec["error"][:160]
                print(f"[{status:7s}] {arch:18s} {shape:12s} {mesh_kind:6s} {extra}",
                      flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
