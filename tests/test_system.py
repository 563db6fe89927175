"""End-to-end system behaviour: the paper's headline comparisons, executed
at reduced scale — Cannikin vs DDP-even vs LB-BSP on a simulated
heterogeneous cluster with real JAX training underneath (Fig. 8/9/10
analogues), plus a small multi-device SPMD check via subprocess."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-compiling; excluded from the fast lane

from repro.configs import get_api
from repro.core import CannikinController, SimulatedCluster, cluster_B
from repro.core.baselines import EvenPartition, LBBSPPartition
from repro.data import SyntheticLM
from repro.optim import constant_schedule, sgd
from repro.train import HeteroTrainer


def make_trainer(policy_name, seed=0, adaptive=False, ref_batch=64):
    api = get_api("olmo-1b", reduced=True)
    profiles, comm = cluster_B()
    sim = SimulatedCluster(profiles, comm, noise=0.01, seed=seed)
    data = SyntheticLM(vocab=api.cfg.vocab, seq_len=24, seed=seed)
    if policy_name == "cannikin":
        policy = CannikinController(
            sim.n,
            batch_candidates=[ref_batch, ref_batch * 2, ref_batch * 4],
            ref_batch=ref_batch,
            adaptive=adaptive,
        )
    elif policy_name == "even":
        policy = EvenPartition(sim.n)
    else:
        policy = LBBSPPartition(sim.n, delta=5)
    tr = HeteroTrainer(
        api, sgd(constant_schedule(0.3)), sim, policy, data, steps_per_epoch=4,
        seed=seed,
    )
    tr.set_fixed_total(ref_batch)
    return tr


def test_cannikin_fastest_batch_time_fixed_total():
    """Fig. 10 analogue (fixed total batch): after learning, Cannikin's batch
    processing time beats DDP-even and LB-BSP-at-epoch-6."""
    results = {}
    for name in ("cannikin", "even", "lb-bsp"):
        tr = make_trainer(name)
        tr.run(6)
        results[name] = tr.history[-1].measured_batch_time
    assert results["cannikin"] < results["even"]
    assert results["cannikin"] < results["lb-bsp"]


def test_convergence_wallclock_ordering():
    """Fig. 8 analogue: simulated wall-clock to reach a fixed loss —
    Cannikin (adaptive) <= even split."""
    target = 4.0
    wall = {}
    for name in ("cannikin", "even"):
        tr = make_trainer(name, adaptive=(name == "cannikin"))
        for _ in range(20):
            r = tr.run_epoch()
            if r.mean_loss <= target:
                break
        wall[name] = tr.sim_time
    assert wall["cannikin"] <= wall["even"] * 1.02


def test_spmd_multi_device_hetero_weights():
    """Runs a pjit weighted-loss step on 8 fake devices in a subprocess and
    checks the Eq. (9) gradient matches the single-device computation."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_api

api = get_api("olmo-1b", reduced=True)
params = api.init(jax.random.PRNGKey(0))
B, S = 16, 16
rng = jax.random.PRNGKey(1)
tokens = jax.random.randint(rng, (B, S), 0, api.cfg.vocab)
labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, api.cfg.vocab)
weights = jnp.linspace(0.5, 2.0, B)

def loss(params, batch):
    l, _ = api.loss(params, batch)
    return l

g1 = jax.grad(loss)(params, {"tokens": tokens, "labels": labels, "weights": weights})

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
bs = NamedSharding(mesh, P("data"))
batch = {
    "tokens": jax.device_put(tokens, NamedSharding(mesh, P("data", None))),
    "labels": jax.device_put(labels, NamedSharding(mesh, P("data", None))),
    "weights": jax.device_put(weights, bs),
}
g2 = jax.jit(jax.grad(loss))(params, batch)
for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               rtol=2e-2, atol=2e-3)
print("SPMD-OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=420,
    )
    assert "SPMD-OK" in out.stdout, out.stderr[-2000:]


def test_set_mesh_resolves_bare_specs_on_auto_mesh():
    """Under ``jax.set_mesh`` with Auto axes (how the dry-run and the
    production mesh are built), bare-PartitionSpec sharding constraints
    resolve against the active mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    with jax.set_mesh(mesh):
        y = jax.jit(
            lambda x: jax.lax.with_sharding_constraint(x, P("data"))
        )(jnp.arange(8.0))
    assert float(y.sum()) == 28.0


def test_dryrun_subprocess_tiny_mesh():
    """A miniature dry-run (4x4 mesh) in a subprocess: lower+compile the
    llama3 reduced train step with the production sharding rules."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_api
from repro.sharding.rules import MeshRules
from repro.optim import adamw, constant_schedule
from repro.train.step import build_train_step

api = get_api("llama3-8b", reduced=True)
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = MeshRules(mesh_axes={"data": 4, "model": 4}, batch_axes=("data",))
opt = adamw(constant_schedule(1e-3))
step = build_train_step(api, opt, microbatches=2, with_metrics=False)
batch_sds = api.train_batch_specs(8, 32)
params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
opt_sds = jax.eval_shape(opt.init, params_sds)
pspecs = api.specs(rules)
named = lambda t: jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), t,
                                         is_leaf=lambda x: isinstance(x, P))
bspecs = {k: NamedSharding(mesh, rules.batch_spec(extra_dims=len(v.shape)-1))
          for k, v in batch_sds.items()}
from repro.launch.dryrun import _opt_specs
ospecs = _opt_specs(opt_sds, pspecs)
with jax.set_mesh(mesh):
    compiled = jax.jit(
        lambda p, o, b: step(p, o, b),
        in_shardings=(named(pspecs), named(ospecs), bspecs),
    ).lower(params_sds, opt_sds, batch_sds).compile()
print("DRYRUN-OK", compiled.cost_analysis()["flops"] > 0)
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=420,
    )
    assert "DRYRUN-OK True" in out.stdout, out.stderr[-2000:]


def test_dryrun_artifacts_complete_if_present():
    """If the full dry-run has been executed, every (arch x shape x mesh)
    must be ok or a documented skip."""
    import glob

    files = glob.glob("artifacts/dryrun/*.json")
    if len(files) < 80:
        pytest.skip("full dry-run artifacts not present")
    bad = []
    for f in files:
        rec = json.load(open(f))
        if rec["status"] == "error":
            bad.append((rec["arch"], rec["shape"], rec["mesh"], rec["error"]))
        elif rec["status"] == "skipped":
            assert rec["arch"].startswith("whisper"), rec
    assert not bad, bad
