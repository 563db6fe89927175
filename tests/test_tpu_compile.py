"""Compile-only checks for a TPU v5e, against a described ``v5e:2x2`` chip.

Nothing runs on a chip here: the installed TPU compiler compiles for a
described topology and refuses what the chip would refuse (kernel tiling,
unsupported Mosaic lowerings, programs that overflow HBM).  Covered:

* the three Pallas kernels at published widths -- WKV at rwkv6-7b widths,
  the selective scan at hymba-1.5b widths, flash attention at OLMo widths;
* the ``chip_smoke.py`` RealBackend programs at its exact sizes (the step
  and the fused epoch), whose summed ``memory_analysis`` must fit in 15 GB;
* the sharded step on a four-chip ``("nodes",)`` mesh, and the one-chip
  vmap program it is compared with.

The topology is described only inside a fixture (never at import), so every
xdist worker collects the same tests and only the worker running this file
loads the TPU library.  The persistent compilation cache is off around
these compiles: a TPU entry written here could not be read back on the CPU.
"""
import os
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # compiles whole programs

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

from repro.configs import get_api  # noqa: E402
from repro.core.aggregation import ANOMALY_OUTLIER_FACTOR  # noqa: E402
from repro.core.optperf_jax import DeviceCoeffs  # noqa: E402
from repro.launch.mesh import make_node_mesh, mesh_axis_sizes  # noqa: E402
from repro.optim import constant_schedule, sgd  # noqa: E402
from repro.runtime.backend import (  # noqa: E402
    STATE_DONATION,
    fused_epoch_body,
    node_step_body,
    sharded_node_step_body,
)
from repro.sharding.rules import MeshRules  # noqa: E402

HBM_BUDGET = 15e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or it refuses the topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _model_state(layers, sharding):
    api = get_api(chip_smoke.ARCH, n_layers=layers)
    opt = sgd(constant_schedule(chip_smoke.LR))
    params = _shapes(jax.eval_shape(api.init, jax.random.PRNGKey(0)), sharding)
    return api, opt, params, _shapes(jax.eval_shape(opt.init, params), sharding)


def test_wkv_kernel_compiles_at_rwkv6_widths(one_chip):
    from repro.kernels.rwkv6_wkv import wkv

    x = jax.ShapeDtypeStruct((1, 2048, 64, 64), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda r, k, v, w, u: wkv(r, k, v, w, u, chunk=64)).lower(
        x, x, x, x, u
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssm_scan_kernel_compiles_at_hymba_widths(one_chip):
    from repro.configs import hymba_1_5b
    from repro.kernels.ssm_scan import ssm_scan
    from repro.models.hymba import ssm_d_block

    cfg = hymba_1_5b.config()
    d, n, chunk = cfg.inner, cfg.ssm_state, cfg.ssm_chunk
    assert (d, n, chunk) == (3200, 16, 128)
    assert ssm_d_block(d) == 640
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda u, dt, b, c, a: ssm_scan(u, dt, b, c, a, chunk=chunk, d_block=ssm_d_block(d))
    ).lower(f32(1, 2048, d), f32(1, 2048, d), f32(1, 2048, n), f32(1, 2048, n),
            f32(d, n)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles_at_olmo_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, 2048, 16, 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(flash_attention).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["step", "fused_epoch", "ragged_step"])
def test_smoke_programs_fit_one_chip(one_chip, program):
    """The chip smoke's largest programs -- 3 cluster-A nodes at b_max 16
    (its per-node cap), its depth and sequence cuts -- fit 15 GB of HBM;
    ``ragged_step`` is the step at the 14/7/3 split's widths 16/8/8."""
    n, b_max, seq = 3, chip_smoke.MAX_LOCAL, chip_smoke.SEQ
    api, opt, params, opt_state = _model_state(chip_smoke.LAYERS, one_chip)
    widths = (b_max, b_max // 2, b_max // 2) if program == "ragged_step" else None
    step = node_step_body(
        api, opt, outlier_factor=ANOMALY_OUTLIER_FACTOR, widths=widths
    )
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    if program != "fused_epoch":
        fn = step
        args = (i32(n, b_max, seq), i32(n, b_max, seq), f32(n, b_max),
                f32(n), f32(), f32(n))
    else:
        fn = fused_epoch_body(step, n, gns_decay=0.9)
        s = chip_smoke.STEPS
        coeffs = DeviceCoeffs(
            f32(n), f32(n), f32(n),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip),
            f32(n), f32(), f32(),
        )
        args = (i32(s, n, b_max, seq), i32(s, n, b_max, seq), f32(s, n, b_max),
                f32(n), f32(), f32(n), f32(n), (f32(), f32(), i32()), coeffs,
                f32(len(chip_smoke.CANDIDATES)), f32(), f32())
    compiled = jax.jit(fn, donate_argnums=STATE_DONATION).lower(
        params, opt_state, *args
    ).compile()
    assert compiled.memory_analysis().alias_size_in_bytes > 0  # donated in place
    assert _hbm_bytes(compiled) <= HBM_BUDGET


def test_four_chip_sharded_step_and_vmap_reference(topo):
    """The --four-chips phase: the shard_map step over a 4-chip node mesh
    (Eq. 9 as a psum) compiles with its collectives, and the one-chip vmap
    reference it is compared with fits one chip."""
    n, b_max, seq = 4, 8, chip_smoke.SEQ
    mesh = make_node_mesh(n, devices=topo.devices)
    assert mesh.devices.size == 4
    rules = MeshRules(mesh_axes=mesh_axis_sizes(mesh), node_axis="nodes")
    rep = NamedSharding(mesh, P())
    nodes = NamedSharding(mesh, P("nodes"))
    api, opt, params, opt_state = _model_state(chip_smoke.FOUR_CHIP_LAYERS, rep)
    step = sharded_node_step_body(
        api, opt, mesh, rules, n, outlier_factor=ANOMALY_OUTLIER_FACTOR
    )
    tok = jax.ShapeDtypeStruct((n, b_max, seq), jnp.int32, sharding=nodes)
    msk = jax.ShapeDtypeStruct((n, b_max), jnp.float32, sharding=nodes)
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rep)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    compiled = jax.jit(step, donate_argnums=STATE_DONATION).lower(
        params, opt_state, tok, tok, msk, vec, scalar, vec
    ).compile()
    # The Eq. (9) psum and the sq-norm all-gather (which the TPU compiler
    # rewrites as an all-reduce) cross the chips.
    assert "all-reduce" in compiled.as_text()
    assert _hbm_bytes(compiled) <= HBM_BUDGET  # per device

    one = SingleDeviceSharding(topo.devices[0])
    api, opt, params, opt_state = _model_state(chip_smoke.FOUR_CHIP_LAYERS, one)
    ref = node_step_body(api, opt, outlier_factor=ANOMALY_OUTLIER_FACTOR)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)  # noqa: E731
    i32 = jax.ShapeDtypeStruct((n, b_max, seq), jnp.int32, sharding=one)
    compiled = jax.jit(ref, donate_argnums=STATE_DONATION).lower(
        params, opt_state, i32, i32, f32(n, b_max), f32(n), f32(), f32(n)
    ).compile()
    assert _hbm_bytes(compiled) <= HBM_BUDGET
