"""ExecutionBackend: the plan → execute → observe engine behind JobHandle.

The paper's Cannikin system is a *runtime* that observes training steps,
refits performance models, and adapts batch sizes.  What produces the
observations — real JAX gradient steps or the calibrated timing simulator —
is an implementation detail the loop must not care about.  This module is
that seam:

* :class:`ExecutionBackend` — the protocol: ``configure`` (follow node-set
  changes), ``execute`` (run one epoch of ``steps`` batches with a given
  partition, returning :class:`ExecutionResult` — per-node
  ``NodeObservation`` measurements plus loss/GNS telemetry), and
  ``snapshot``/``load_snapshot`` (preemption checkpoint state).
* :class:`SimBackend` — :class:`~repro.core.simulator.SimulatedCluster`
  behind the protocol: timing measurements only, no gradients (losses are
  NaN).  The default for trace replay.
* :class:`RealBackend` — the gradient engine extracted from the old
  ``HeteroTrainer``: the vmapped per-node backward over the padded
  ``(n, b_max)`` layout, each node at its own bucketed width, Eq. (9)
  weighted aggregation, a Theorem-4.1 GNS tracker, and a simulated
  cluster clock (the paper's own separation: statistical behaviour is
  real, per-node timing is simulated).  Its state
  (params / opt-state / GNS / stream counters) round-trips bit-exactly
  through :mod:`repro.train.checkpoint` for preemption/resume.
* :func:`run_backend_epoch` / :class:`EpochLoop` — the policy loop the
  runtime owns: plan (CannikinController or a baseline partition policy) →
  ``backend.execute`` → observe (measurements + gradient telemetry), each
  epoch surfacing one unified :class:`EpochRecord` (merging the old
  ``EpochResult``/``EpochPlan`` telemetry split).

``JobHandle.advance`` and ``HeteroTrainer`` are both thin shells over this
module, so scheduler decisions, simulated traces, and real training can
never diverge in protocol.
"""
from __future__ import annotations

import dataclasses
from typing import (
    Any,
    Callable,
    Dict,
    List,
    Optional,
    Protocol,
    Sequence,
    Tuple,
    runtime_checkable,
)

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.aggregation import ratios
from repro.core.controller import (
    CannikinController,
    EpochPlan,
    FusedProposal,
    FusedSweepContext,
)
from repro.core.gns import GNSState, estimate_gns, gns_update
from repro.core.scheduler import JobSpec
from repro.core.simulator import NodeProfile, SimulatedCluster, StepMeasurement
from repro.runtime.telemetry import Telemetry

__all__ = [
    "GradObservation",
    "ExecutionResult",
    "EpochRecord",
    "ExecutionBackend",
    "SimBackend",
    "RealBackend",
    "RealBackendConfig",
    "BACKENDS",
    "make_backend",
    "run_backend_epoch",
    "EpochLoop",
]


# ---------------------------------------------------------------------------
# telemetry records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GradObservation:
    """One step's gradient telemetry — the Theorem-4.1 GNS ingredients:
    per-node gradient square-norms |g_i|^2, the aggregated |g|^2, and the
    local batch sizes that produced them.  ``valid`` marks which nodes the
    anomaly guard kept in the Eq. (9) aggregate (empty tuple = unguarded
    legacy observation, treated as all-valid)."""

    local_sqnorms: Tuple[float, ...]
    global_sqnorm: float
    batches: Tuple[int, ...]
    valid: Tuple[bool, ...] = ()

    @property
    def all_valid(self) -> bool:
        return all(self.valid) if self.valid else True


@dataclasses.dataclass(frozen=True)
class ExecutionResult:
    """What one backend epoch produced.

    ``measurements`` carry the per-node :class:`NodeObservation` timing the
    performance-model fitters consume; ``losses``/``grad_observations`` are
    the statistical telemetry (empty on :class:`SimBackend`); ``b_noise``
    is the backend's own GNS tracker estimate after the epoch (NaN when the
    backend computes no gradients).
    """

    epoch_seconds: float
    measurements: Tuple[StepMeasurement, ...]
    losses: Tuple[float, ...]
    grad_observations: Tuple[GradObservation, ...]
    b_noise: float
    # Per-node counts of steps the gradient anomaly guard excluded the node
    # from aggregation this epoch (aligned with the configured node order;
    # empty for backends without a guard).  The runtime feeds this to
    # HealthMonitor.observe_numerics.
    grad_anomalies: Tuple[int, ...] = ()

    @property
    def mean_loss(self) -> float:
        return float(np.mean(self.losses)) if self.losses else float("nan")


@dataclasses.dataclass(frozen=True)
class EpochRecord:
    """Unified per-epoch telemetry: plan + execution in one record (the old
    ``EpochResult``/``EpochPlan`` split, merged).  ``mean_loss`` is NaN and
    ``b_noise`` non-finite for backends that compute no gradients."""

    epoch: int
    backend: str                           # ExecutionBackend.kind
    total_batch: int
    batches: Tuple[int, ...]
    lr_scale: float
    phase: str                             # "bootstrap" | "optperf" | baseline name
    predicted_batch_time: Optional[float]
    measured_batch_time: float
    epoch_seconds: float                   # simulated cluster wall-clock
    mean_loss: float
    b_noise: float
    plan: Optional[EpochPlan] = None


# ---------------------------------------------------------------------------
# the backend protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class ExecutionBackend(Protocol):
    """What the runtime's epoch loop needs from an execution engine.

    ``configure`` rebinds the backend to a job's current node set (called on
    every reallocation; the timing model follows the nodes, any learned
    statistical state — params, optimizer, GNS — survives).  ``execute``
    runs one epoch.  ``snapshot``/``load_snapshot`` expose the state that
    must survive preemption as a checkpointable pytree (``{}`` when there is
    nothing worth persisting).
    """

    kind: str

    def configure(
        self, spec: JobSpec, node_ids: Sequence[int], *, seed: int = 0
    ) -> None: ...

    def execute(
        self, batches: Sequence[int], steps: int, *, lr_scale: float = 1.0
    ) -> ExecutionResult: ...

    def snapshot(self) -> Dict[str, Any]: ...

    def load_snapshot(self, state: Dict[str, Any]) -> None: ...


def _profiles_for(spec: JobSpec, node_ids: Sequence[int]) -> List[NodeProfile]:
    """The job's own ground-truth node models over its held nodes, as timing
    profiles (identical to the old ``JobHandle._rebuild_sim`` construction)."""
    profiles = []
    for nid in node_ids:
        m = spec.node_models[nid]
        profiles.append(
            NodeProfile(name=f"{spec.name}:n{nid}", q=m.q, s=m.s, k=m.k, m=m.m)
        )
    return profiles


class SimBackend:
    """:class:`SimulatedCluster` behind the :class:`ExecutionBackend`
    protocol: per-node timing measurements with optional multiplicative
    noise, no gradients.  ``configure`` rebuilds the cluster from the job
    spec's own node models (per-job heterogeneity included), exactly as the
    pre-refactor ``JobHandle`` did — replayed traces are bit-identical."""

    kind = "sim"

    def __init__(
        self,
        cluster: Optional[SimulatedCluster] = None,
        *,
        noise: float = 0.0,
        injector: Any = None,
    ) -> None:
        self.cluster = cluster
        self.noise = noise
        self.injector = injector  # Optional[repro.runtime.faults.FaultInjector]
        self.sim_time = 0.0
        self.epochs_run = 0
        self._job: Optional[str] = None
        self._node_ids: Tuple[int, ...] = ()

    def configure(
        self, spec: JobSpec, node_ids: Sequence[int], *, seed: int = 0
    ) -> None:
        self.cluster = SimulatedCluster(
            _profiles_for(spec, node_ids), spec.comm, noise=self.noise, seed=seed
        )
        self._job = spec.name
        self._node_ids = tuple(int(n) for n in node_ids)

    def execute(
        self, batches: Sequence[int], steps: int, *, lr_scale: float = 1.0
    ) -> ExecutionResult:
        if self.cluster is None:
            raise RuntimeError("SimBackend not configured with a cluster")
        t, ms = self.cluster.run_epoch(list(batches), steps)
        if self.injector is not None:
            # Pure post-transform of the measurements: the cluster's RNG
            # stream is untouched, so the fault-free replay stays
            # bit-identical and faults compose deterministically on top.
            t, ms = self.injector.perturb(
                self._job or "?", self._node_ids, t, list(ms)
            )
        self.sim_time += t
        self.epochs_run += 1
        return ExecutionResult(
            epoch_seconds=t,
            measurements=tuple(ms),
            losses=(),
            grad_observations=(),
            b_noise=float("nan"),
        )

    def snapshot(self) -> Dict[str, Any]:
        return {}  # nothing statistical to persist: the sim is rebuilt on resume

    def load_snapshot(self, state: Dict[str, Any]) -> None:
        del state


# The step programs donate params (arg 0) and optimizer state (arg 1): the
# update happens in place, so a step holds one copy of each, not two.
STATE_DONATION = (0, 1)

# Named scopes of the step's phases: each device op's ``op_name`` metadata
# carries one, so a profiler trace splits the step's device time by phase.
SCOPE_BACKWARD = "node_backward"        # the per-node backward
SCOPE_AGGREGATE = "eq9_aggregate"       # poison, sq-norms, guard, Eq. 9, |g|^2
SCOPE_LOSS = "loss_forward"             # the reported loss, from per-node means
SCOPE_UPDATE = "optimizer_update"
STEP_SCOPES = (SCOPE_BACKWARD, SCOPE_AGGREGATE, SCOPE_LOSS, SCOPE_UPDATE)


def composed_loss_terms(losses: Any, mask: Any, seq_len: int) -> Tuple[Any, Any]:
    """``(numerator, denominator)`` of the global mean loss composed from
    the per-node mean losses ``losses`` (n,) of a dense family.

    Node i's token-weight sum is ``mask_i.sum() * seq_len``
    (``registry._token_loss`` broadcasts the per-sample mask over the
    sequence dim), so ``losses_i * w_sum_i`` recovers its loss summand and
    an all-padding node adds 0.  The quotient equals the full-batch
    weighted-mean cross-entropy to float32 round-off; the caller divides,
    after any cross-shard ``psum`` of both terms."""
    import jax.numpy as jnp

    w_sum = mask.sum(axis=1) * jnp.float32(seq_len)
    return (losses.astype(jnp.float32) * w_sum).sum(), w_sum.sum()


def width_groups(widths: Sequence[int]) -> Tuple[Tuple[int, Tuple[int, ...]], ...]:
    """Node indices grouped by padded width, in order of first appearance:
    ``(16, 8, 8)`` gives ``((16, (0,)), (8, (1, 2)))``."""
    groups: Dict[int, List[int]] = {}
    for i, w in enumerate(widths):
        groups.setdefault(int(w), []).append(i)
    return tuple((w, tuple(idx)) for w, idx in groups.items())


def node_step_body(
    api: Any,
    optimizer: Any,
    *,
    outlier_factor: float,
    widths: Optional[Sequence[int]] = None,
) -> Callable:
    """The un-jitted single-device RealBackend step: vmapped per-node
    backward, anomaly-guarded Eq. (9) aggregate, one optimizer update.

    ``step(params, opt_state, tokens, labels, mask, r, lr_scale, poison)
    -> (params, opt_state, loss, sq_i, sq_g, valid)`` over the padded
    ``(n, b_max)`` node layout.  ``widths`` (static, one per node, each at
    most ``b_max``; None: ``b_max`` for every node) are the rows each node's
    backward computes: node i's real rows are ``[i, :b_i]`` with
    ``b_i <= widths[i]``, so the rows past its width carry zero weight and
    are dropped.  Nodes of equal width share one vmapped backward, each
    group at its own width; with one width the step is the plain vmap over
    the whole layout.  The groups' gradients are never concatenated: the
    Eq. (9) aggregate sums each group's weighted share.  An expert
    family's router counts every row it is given (capacity, load-balance
    statistics), so its node gradients are those at the node's own width.

    The reported ``loss`` is composed from the per-node mean losses the
    backward's own forward computes (:func:`composed_loss_terms`), so the
    step runs no second forward; where ``api.has_moe_stats`` the
    load-balance and z-loss terms are whole-batch statistics that do not
    compose, and the loss is one full-batch forward at the pre-update
    params.  Module-level so a compile-only check can build the exact
    program from abstract shapes.  Its phases run under the named scopes
    of ``STEP_SCOPES``."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregation import guard_weights
    from repro.optim.optimizers import global_norm

    def node_loss(params, tokens, labels, mask):
        # mean over the node's real samples (pads weighted 0).
        loss, _ = api.loss(
            params,
            {"tokens": tokens, "labels": labels, "weights": mask},
        )
        return loss

    val_grad = jax.value_and_grad(node_loss)

    def step(params, opt_state, tokens, labels, mask, r, lr_scale, poison):
        # tokens/labels: (n, b_max, S); mask: (n, b_max); r/poison: (n,)
        n, b_max = mask.shape
        ws = tuple(int(w) for w in widths) if widths is not None else (b_max,) * n
        if len(ws) != n or max(ws) > b_max:
            raise ValueError(f"widths {ws} do not fit the layout ({n}, {b_max})")
        groups = width_groups(ws)
        nodes = tuple(range(n))

        def pick(x, idx):
            # Static node rows ``idx`` of a node-major array.
            return x if idx == nodes else x[np.asarray(idx)]

        def rows(x, idx, width):
            x = pick(x, idx)
            return x if width == b_max else x[:, :width]

        inverse = np.argsort([i for _, idx in groups for i in idx])

        def in_node_order(parts):
            # (n,) in node order from one vector per group.
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)[inverse]

        with jax.named_scope(SCOPE_BACKWARD):
            outs = [
                jax.vmap(val_grad, in_axes=(None, 0, 0, 0))(
                    params,
                    rows(tokens, idx, width),
                    rows(labels, idx, width),
                    rows(mask, idx, width),
                )
                for width, idx in groups
            ]
            # The aggregate starts once the whole backward is done.  Left
            # free, XLA interleaves it with the backward, and chip_smoke's
            # fused epoch compiles to 15.3 GB for a v5e (14.4 GB with the
            # barrier).  The barrier is an identity, though XLA may then
            # round the aggregate's sums in another order.
            outs = jax.lax.optimization_barrier(outs)
        with jax.named_scope(SCOPE_AGGREGATE):
            grads, sq_parts = [], []
            for (_, idx), (_, g) in zip(groups, outs):
                p = pick(poison, idx)
                g = jax.tree_util.tree_map(
                    lambda a: a
                    * p.reshape((-1,) + (1,) * (a.ndim - 1)).astype(a.dtype),
                    g,
                )
                grads.append(g)
                sq_parts.append(jax.vmap(lambda g: global_norm(g) ** 2)(g))
            sq_i = in_node_order(sq_parts)
            losses = in_node_order([loss for loss, _ in outs])
            w, valid = guard_weights(sq_i, r, outlier_factor=outlier_factor)
            shares = [
                (pick(w, idx).astype(jnp.float32), pick(valid, idx))
                for _, idx in groups
            ]

            def weighted_sum(*leaves):
                # Eq. (9) for one leaf: each group's share, summed in f32.
                total = None
                for (w_g, valid_g), g in zip(shares, leaves):
                    part = jnp.tensordot(
                        w_g,
                        jnp.where(
                            valid_g.reshape((-1,) + (1,) * (g.ndim - 1)), g, 0
                        ).astype(jnp.float32),
                        axes=1,
                    )
                    total = part if total is None else total + part
                return total.astype(leaves[0].dtype)

            agg = jax.tree_util.tree_map(weighted_sum, *grads)
            sq_g = global_norm(agg) ** 2
        with jax.named_scope(SCOPE_LOSS):
            if api.has_moe_stats:
                loss, _ = api.loss(
                    params,
                    {
                        "tokens": tokens.reshape((-1,) + tokens.shape[2:]),
                        "labels": labels.reshape((-1,) + labels.shape[2:]),
                        "weights": mask.reshape(-1),
                    },
                )
            else:
                num, den = composed_loss_terms(losses, mask, tokens.shape[-1])
                loss = num / jnp.maximum(den, 1e-9)
        with jax.named_scope(SCOPE_UPDATE):
            new_params, new_opt = optimizer.update(agg, opt_state, params, lr_scale)
        return new_params, new_opt, loss, sq_i, sq_g, valid

    return step


def sharded_node_step_body(
    api: Any, optimizer: Any, mesh: Any, rules: Any, n: int, *, outlier_factor: float
) -> Callable:
    """The un-jitted multi-device step: the node axis split over the
    ``("nodes",)`` mesh via ``shard_map``, Eq. (9) aggregation as an
    on-device ``psum``.

    Each shard backprops its n/D nodes locally (the same vmapped
    per-node backward), all-gathers the (n,) gradient square-norms so
    every shard evaluates the *global* anomaly guard identically, then
    psums its weighted local partials into the replicated Eq. (9)
    aggregate.  The optimizer update runs outside shard_map on the
    replicated aggregate.  The global loss is composed from per-node
    means by their token-weight sums (:func:`composed_loss_terms`, both
    terms psum'd): for dense families it equals a full-batch forward (see
    models.registry._token_loss) to float32 roundoff; an MoE family's
    load-balance and z-loss terms are whole-batch statistics, which this
    composition leaves as per-node means.  The phases carry the scopes of
    ``STEP_SCOPES``; the composed loss runs under ``SCOPE_LOSS``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.aggregation import guard_weights
    from repro.optim.optimizers import global_norm
    from repro.train.step import node_step_specs

    shard = n // int(mesh.devices.size)
    specs = node_step_specs(rules)

    def node_loss(params, tokens, labels, mask):
        loss, _ = api.loss(
            params,
            {"tokens": tokens, "labels": labels, "weights": mask},
        )
        return loss

    val_grad = jax.value_and_grad(node_loss)

    def shard_body(params, tokens, labels, mask, r, poison):
        # Local shapes: tokens/labels (n/D, b_max, S); mask (n/D, b_max);
        # params/r/poison replicated.
        with jax.named_scope(SCOPE_BACKWARD):
            losses, grads = jax.vmap(val_grad, in_axes=(None, 0, 0, 0))(
                params, tokens, labels, mask
            )
        with jax.named_scope(SCOPE_AGGREGATE):
            lo = jax.lax.axis_index("nodes") * shard
            poison_local = jax.lax.dynamic_slice(poison, (lo,), (shard,))
            grads = jax.tree_util.tree_map(
                lambda g: g
                * poison_local.reshape((-1,) + (1,) * (g.ndim - 1)).astype(g.dtype),
                grads,
            )
            sq_local = jax.vmap(lambda g: global_norm(g) ** 2)(grads)
            sq_i = jax.lax.all_gather(sq_local, "nodes", tiled=True)  # (n,)
            w, valid = guard_weights(sq_i, r, outlier_factor=outlier_factor)
            w_local = jax.lax.dynamic_slice(w, (lo,), (shard,))
            valid_local = jax.lax.dynamic_slice(valid, (lo,), (shard,))
            agg = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(
                    jnp.tensordot(
                        w_local.astype(jnp.float32),
                        jnp.where(
                            valid_local.reshape((-1,) + (1,) * (g.ndim - 1)), g, 0
                        ).astype(jnp.float32),
                        axes=1,
                    ),
                    "nodes",
                ).astype(g.dtype),
                grads,
            )
            sq_g = global_norm(agg) ** 2
        with jax.named_scope(SCOPE_LOSS):
            num, den = composed_loss_terms(losses, mask, tokens.shape[-1])
            loss_num = jax.lax.psum(num, "nodes")
            loss_den = jax.lax.psum(den, "nodes")
            loss = loss_num / jnp.maximum(loss_den, 1e-9)
        return agg, loss, sq_i, sq_g, valid

    smapped = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(),
            specs["tokens"],
            specs["labels"],
            specs["mask"],
            specs["replicated"],
            specs["replicated"],
        ),
        out_specs=(P(), P(), P(), P(), P()),
        # The model's scans start from unvarying carries that the body
        # makes node-varying; every output is psum'd or all-gathered,
        # so replication holds without the varying-axes type check.
        check_vma=False,
    )

    def step(params, opt_state, tokens, labels, mask, r, lr_scale, poison):
        agg, loss, sq_i, sq_g, valid = smapped(
            params, tokens, labels, mask, r, poison
        )
        with jax.named_scope(SCOPE_UPDATE):
            new_params, new_opt = optimizer.update(agg, opt_state, params, lr_scale)
        return new_params, new_opt, loss, sq_i, sq_g, valid

    return step


def fused_epoch_body(step_body: Callable, n: int, *, gns_decay: float) -> Callable:
    """The un-jitted fused adaptive epoch over ``n`` nodes: lax.scan of
    ``step_body`` over the stacked steps, the on-device Theorem-4.1 GNS EMA,
    and the OptPerf goodput sweep + Eq. (6) argmax + water-fill partition
    on the final state.  ``epoch_fn(params, opt_state, toks, labs, msks, r,
    lr_scale, poison, b_vec, gns0, dc, cand, lo0, b0) -> (params,
    opt_state, telemetry)``."""
    import jax
    import jax.numpy as jnp

    from repro.core import optperf_jax
    from repro.core.gns import local_estimates

    decay = float(gns_decay)

    def epoch_fn(
        params, opt_state, toks, labs, msks, r, lr_scale, poison,
        b_vec, gns0, dc, cand, lo0, b0,
    ):
        total = b_vec.sum()

        def body(carry, xs):
            params, opt_state, (eg, es, cnt) = carry
            tok, lab, msk = xs
            params, opt_state, loss, sq_i, sq_g, valid = step_body(
                params, opt_state, tok, lab, msk, r, lr_scale, poison
            )
            if n >= 2:
                g_i, s_i = local_estimates(sq_i, sq_g, b_vec, total)
                # Theorem 4.1 minimum-variance weights, corrected
                # closed form (same as gns.estimate_gns): the host
                # tracker skips guarded/degenerate steps, so gate the
                # EMA on all-valid + finite estimates.
                w = (total - b_vec) / ((n - 1) * total)
                g_est = (w * g_i).sum()
                s_est = (w * s_i).sum()
                ok = valid.all() & jnp.isfinite(g_est) & jnp.isfinite(s_est)
            else:
                g_est = jnp.float32(0.0)
                s_est = jnp.float32(0.0)
                ok = jnp.bool_(False)
            eg = jnp.where(ok, decay * eg + (1.0 - decay) * g_est, eg)
            es = jnp.where(ok, decay * es + (1.0 - decay) * s_est, es)
            cnt = jnp.where(ok, cnt + 1, cnt)
            return (params, opt_state, (eg, es, cnt)), (loss, sq_i, sq_g, valid)

        (params, opt_state, (eg, es, cnt)), ys = jax.lax.scan(
            body, (params, opt_state, gns0), (toks, labs, msks)
        )
        losses, sq_is, sq_gs, valids = ys
        b_noise = jnp.where(
            (cnt > 0) & (eg > 0.0),
            jnp.maximum(es / jnp.where(eg > 0.0, eg, 1.0), 0.0),
            jnp.inf,
        )
        t_stars, sweep_iters = optperf_jax.solve_optperf_sweep_device(
            dc, cand, lo0
        )
        # Realized per-candidate OptPerf: finalize each partition and
        # take the max node time — at small totals a clamped node's
        # fixed floor sits above the bisected water level, and the host
        # oracle's goodput uses the realized time.
        parts = optperf_jax.device_partition(dc, t_stars[:, None], cand)
        opt_perfs = optperf_jax.device_node_times(dc, parts).max(-1)
        bn = jnp.maximum(b_noise, 0.0)
        eff = jnp.where(jnp.isfinite(b_noise), (bn + b0) / (bn + cand), 1.0)
        goodputs = (cand / opt_perfs) * eff
        best = jnp.argmax(goodputs)
        telemetry = (
            losses, sq_is, sq_gs, valids, eg, es, cnt, b_noise,
            opt_perfs, goodputs, best, parts[best], sweep_iters,
        )
        return params, opt_state, telemetry

    return epoch_fn


def _quantize(b: int, q: int = 8) -> int:
    """A node's padded width: ``b`` rounded up to a multiple of ``q`` (at
    least ``q``), so nearby splits map to one width and repartitioning
    from epoch to epoch reuses the compiled steps."""
    return max(q, ((b + q - 1) // q) * q)


class RealBackend:
    """The real-gradient engine (extracted from the old ``HeteroTrainer``).

    Per step: partition the global batch by the planned local batch sizes,
    run the vmapped per-node backward over the padded ``(n, b_max)``
    layout, each node at its own bucketed width (:func:`_quantize`), then
    aggregate g = sum r_i g_i (Eq. 9), update params once, feed
    (|g_i|^2, |g|^2, b) to the Theorem-4.1 GNS tracker, and advance the
    simulated cluster clock by the heterogeneous batch time.

    Only the *timing* is simulated (``cluster`` — rebound by ``configure``
    on every node-set change); params, optimizer state, GNS state, and the
    data-stream position are real and round-trip bit-exactly through
    ``snapshot``/``load_snapshot`` (and :meth:`checkpoint`/:meth:`restore`
    via :mod:`repro.train.checkpoint`) for preemption/resume.

    **Integrity hardening.**  The Eq. (9) aggregation runs behind the
    always-on anomaly guard (:func:`repro.core.aggregation.guard_weights`):
    a node whose per-step gradient is non-finite or a gross norm outlier is
    excluded from the aggregate (weights renormalized; the GNS tracker
    skips the step) and counted in ``ExecutionResult.grad_anomalies``.
    ``injector`` is the real-path fault seam: its ``poison_factors`` vector
    multiplies each node's gradient inside the jitted step (exactly 1.0
    when inactive — IEEE-exact, so no-fault replays stay bit-identical;
    the guard itself is always compiled in, so the program is the same
    with or without an injector), and its ``perturb`` post-transforms the
    timing measurement stream exactly as it does for :class:`SimBackend`
    (timing faults hit the real path too).

    **Observability.**  ``telemetry`` (:class:`~repro.runtime.telemetry.
    Telemetry`) counts steps, useful and padded rows, every array shipped
    and pulled with its bytes, and step programs built.  Each step of
    :meth:`execute` runs in a ``repro.step`` profiler span (``step_num``),
    its phases in ``repro.step.input``, ``.put``, ``.dispatch``, ``.pull``
    (the host's wait for the device) and ``.gns``; :meth:`execute_fused`
    uses ``repro.fused`` and the same phase names under it.
    """

    kind = "real"

    def __init__(
        self,
        api: Any,                        # ModelApi
        optimizer: Any,                  # repro.optim Optimizer
        data: Any,                       # SyntheticLM-compatible stream
        *,
        cluster: Optional[SimulatedCluster] = None,
        noise: float = 0.0,
        seed: int = 0,
        gns_decay: float = 0.9,
        injector: Any = None,            # Optional[FaultInjector]
        outlier_factor: Optional[float] = None,
        sharded: bool = False,
    ) -> None:
        import jax

        from repro.core.aggregation import ANOMALY_OUTLIER_FACTOR

        self.api = api
        self.optimizer = optimizer
        self.data = data
        self.cluster = cluster
        self.noise = noise
        self.injector = injector
        self.outlier_factor = (
            float(outlier_factor) if outlier_factor is not None
            else ANOMALY_OUTLIER_FACTOR
        )
        # One program each: initializing leaf by leaf compiles one RNG
        # program per parameter shape, which at OLMo-1B widths took 94 s of
        # compile on a v5e against 27 s for the whole init as one program.
        self.params = jax.jit(api.init)(jax.random.PRNGKey(seed))
        self.opt_state = jax.jit(optimizer.init)(self.params)
        self.gns = GNSState()
        self.gns_decay = gns_decay
        self.sim_time = 0.0
        self.steps_done = 0
        self.anomalous_steps = 0       # steps with >= 1 excluded node (lifetime)
        self.sharded = bool(sharded)
        self.telemetry = Telemetry()
        self._step_cache: Dict[Tuple[int, ...], Callable] = {}
        self._sharded_cache: Dict[Tuple[int, int], Callable] = {}
        self._fused_cache: Dict[Tuple[int, int], Callable] = {}
        self._meshes: Dict[int, Any] = {}        # shard count -> Mesh
        self._mesh_rules: Dict[int, Any] = {}    # shard count -> MeshRules
        self._mesh: Any = None                   # mesh for the configured node set
        self._job: Optional[str] = None
        self._node_ids: Tuple[int, ...] = ()

    # -- node-set binding ------------------------------------------------

    def configure(
        self, spec: JobSpec, node_ids: Sequence[int], *, seed: int = 0
    ) -> None:
        self.cluster = SimulatedCluster(
            _profiles_for(spec, node_ids), spec.comm, noise=self.noise, seed=seed
        )
        self._job = spec.name
        self._node_ids = tuple(int(n) for n in node_ids)
        if self.sharded and self._node_ids:
            # Rebuild the node mesh on node-set changes; compiled sharded
            # steps are keyed by (n, shard count) so a changed shard count
            # naturally re-traces while same-width reconfigurations reuse
            # the cached programs.
            self._mesh, _ = self._mesh_for(len(self._node_ids))

    def _mesh_for(self, n: int) -> Tuple[Any, Any]:
        """(Mesh, MeshRules) for an n-node sharded step, cached by shard
        count (the largest divisor of n that fits the local devices)."""
        from repro.launch.mesh import (
            make_node_mesh,
            mesh_axis_sizes,
            node_shard_count,
        )
        from repro.sharding.rules import MeshRules

        d = node_shard_count(n)
        mesh = self._meshes.get(d)
        if mesh is None:
            mesh = make_node_mesh(n)
            self._meshes[d] = mesh
            self._mesh_rules[d] = MeshRules(
                mesh_axes=mesh_axis_sizes(mesh), node_axis="nodes"
            )
        return mesh, self._mesh_rules[d]

    def _node_placer(self, n: int, *, stacked: bool = False) -> Callable:
        """device_put with the node-axis NamedSharding — the explicit h2d
        seam for sharded execution.  ``stacked`` handles the fused layout
        with a leading (steps,) scan dim before the node dim."""
        import jax
        from jax.sharding import NamedSharding

        mesh, rules = self._mesh_for(n)
        lead: List[Optional[str]] = [None] if stacked else []

        def place(arr: np.ndarray):
            axes = lead + ["nodes"] + [None] * (arr.ndim - len(lead) - 1)
            return jax.device_put(arr, NamedSharding(mesh, rules.spec(axes)))

        return place

    # -- gradient engine -------------------------------------------------

    def _node_grad_fn(self, widths: Tuple[int, ...]) -> Callable:
        """Jitted: per-node grads (× injected poison factors) + sq-norms +
        anomaly-guarded Eq.(9) aggregate + update, each node's backward at
        its padded width in ``widths``; one program per distinct ``widths``.

        The guard is *always* compiled in — the same program runs with and
        without an injector, so the no-fault bit-identity guarantee holds
        by construction: healthy poison factors are exactly 1.0 (IEEE-exact
        multiply) and the all-valid guard branch selects the original
        weight vector bitwise.  Invalid nodes' gradients are zeroed
        *before* the tensordot (0 × NaN = NaN otherwise) and the surviving
        weights renormalized; with every node invalid the aggregate is
        zero and the update a no-op."""
        if widths in self._step_cache:
            return self._step_cache[widths]
        import jax

        fn = jax.jit(self._build_step_body(widths), donate_argnums=STATE_DONATION)
        self._step_cache[widths] = fn
        self.telemetry.programs_built += 1
        return fn

    def _build_step_body(self, widths: Optional[Tuple[int, ...]] = None) -> Callable:
        """The un-jitted single-device step (vmapped per-node backward).
        Shared between :meth:`_node_grad_fn` (jitted directly, at the
        planned widths) and the fused epoch program (scanned inside one
        jit, over the uniform layout)."""
        return node_step_body(
            self.api, self.optimizer, outlier_factor=self.outlier_factor,
            widths=widths,
        )

    def _build_sharded_step_body(self, n: int) -> Callable:
        """The un-jitted multi-device step over the cached node mesh."""
        mesh, rules = self._mesh_for(n)
        return sharded_node_step_body(
            self.api, self.optimizer, mesh, rules, n,
            outlier_factor=self.outlier_factor,
        )

    def _node_grad_fn_sharded(self, n: int) -> Callable:
        """Jitted sharded step, cached by (n, shard count); padded-width
        shape changes retrace inside the cached jit wrapper as usual."""
        mesh, _ = self._mesh_for(n)
        key = (n, int(mesh.devices.size))
        if key in self._sharded_cache:
            return self._sharded_cache[key]
        import jax

        fn = jax.jit(
            self._build_sharded_step_body(n), donate_argnums=STATE_DONATION
        )
        self._sharded_cache[key] = fn
        self.telemetry.programs_built += 1
        return fn

    def execute(
        self, batches: Sequence[int], steps: int, *, lr_scale: float = 1.0
    ) -> ExecutionResult:
        if self.cluster is None:
            raise RuntimeError("RealBackend not configured with a cluster")
        import jax.numpy as jnp

        from repro.data.pipeline import HeteroBatchPartitioner

        batches = [int(b) for b in batches]
        b_arr = np.asarray(batches, np.int64)
        widths = tuple(_quantize(b) for b in batches)
        b_max = max(widths)
        n = len(batches)
        tel = self.telemetry
        r = tel.put(np.asarray(ratios(batches), np.float32), jnp.asarray)
        use_sharded = self.sharded and n > 0
        if use_sharded:
            # The node mesh computes the uniform layout.
            step_fn = self._node_grad_fn_sharded(n)
            place = self._node_placer(n)
            rows_computed, ragged = n * b_max, False
        else:
            step_fn = self._node_grad_fn(widths)
            place = jnp.asarray
            rows_computed, ragged = sum(widths), len(set(widths)) > 1

        node_ids = self._node_ids if len(self._node_ids) == n else tuple(range(n))
        if self.injector is not None:
            poison_np = self.injector.poison_factors(node_ids)
        else:
            poison_np = np.ones(n, np.float32)
        poison = tel.put(np.asarray(poison_np, np.float32), jnp.asarray)
        anomaly_counts = np.zeros(n, np.int64)

        losses: List[float] = []
        grad_obs: List[GradObservation] = []
        for _ in range(steps):
            step_num = self.steps_done
            with StepTraceAnnotation("repro.step", step_num=step_num):
                with TraceAnnotation("repro.step.input", step_num=step_num):
                    raw = self.data.batch(self.steps_done, int(b_arr.sum()))
                    self.steps_done += 1
                    padded, _ = HeteroBatchPartitioner.padded(raw, batches)
                    seq = padded["tokens"].shape[-1]
                    tok = np.zeros((n, b_max, seq), np.int32)
                    lab = np.zeros((n, b_max, seq), np.int32)
                    msk = np.zeros((n, b_max), np.float32)
                    w = padded["tokens"].shape[1]
                    tok[:, :w], lab[:, :w] = padded["tokens"], padded["labels"]
                    for i, b in enumerate(batches):
                        msk[i, :b] = 1.0
                tel.count_steps(1, int(b_arr.sum()), rows_computed, ragged=ragged)
                with TraceAnnotation("repro.step.put", step_num=step_num):
                    args = tuple(tel.put(a, place) for a in (tok, lab, msk))
                    lr = tel.put(np.float32(lr_scale), jnp.asarray)
                with TraceAnnotation("repro.step.dispatch", step_num=step_num):
                    self.params, self.opt_state, loss, sq_i, sq_g, valid = step_fn(
                        self.params, self.opt_state, *args, r, lr, poison
                    )
                # Blocks until the device has finished the step.
                with TraceAnnotation("repro.step.pull", step_num=step_num):
                    valid_np = np.asarray(tel.pull(valid), bool)
                    loss_np = tel.pull(loss)
                    sq_i_np = tel.pull(sq_i)
                    sq_g_np = tel.pull(sq_g)
                anomaly_counts += ~valid_np
                self.anomalous_steps += int(not valid_np.all())
                losses.append(float(loss_np))
                obs = GradObservation(
                    local_sqnorms=tuple(float(x) for x in sq_i_np),
                    global_sqnorm=float(sq_g_np),
                    batches=tuple(batches),
                    valid=tuple(bool(v) for v in valid_np),
                )
                grad_obs.append(obs)
                if obs.all_valid:
                    # Poisoned steps carry non-finite/outlier sq-norms: feeding
                    # them to the Theorem-4.1 tracker would corrupt b_noise.
                    with TraceAnnotation("repro.step.gns", step_num=step_num):
                        self._track_gns(obs)

        epoch_seconds, measurements = self.cluster.run_epoch(batches, steps)
        measurements = list(measurements)
        if self.injector is not None:
            # Timing faults (slowdowns/flaps) route through the same seam as
            # the sim backend: pure post-transform of the measurement stream.
            epoch_seconds, measurements = self.injector.perturb(
                self._job or "?", node_ids, epoch_seconds, measurements
            )
        self.sim_time += epoch_seconds
        return ExecutionResult(
            epoch_seconds=epoch_seconds,
            measurements=tuple(measurements),
            losses=tuple(losses),
            grad_observations=tuple(grad_obs),
            b_noise=self.gns.b_noise,
            grad_anomalies=tuple(int(c) for c in anomaly_counts),
        )

    # -- fused on-device epoch -------------------------------------------

    def _fused_epoch_fn(self, n: int) -> Callable:
        """One jitted program for a whole adaptive epoch: lax.scan over the
        train steps (the same step body as the two-program path), an
        on-device Theorem-4.1 GNS EMA, and the OptPerf goodput sweep +
        Eq. (6) argmax + water-fill partition on the final state.  The host
        touches the device exactly twice per epoch: one stacked-batch
        shipment in, one telemetry-bundle pull out."""
        use_sharded = self.sharded and n > 0
        if use_sharded:
            mesh, _ = self._mesh_for(n)
            key = (n, int(mesh.devices.size))
        else:
            key = (n, 0)
        if key in self._fused_cache:
            return self._fused_cache[key]
        import jax

        step_body = (
            self._build_sharded_step_body(n) if use_sharded
            else self._build_step_body()
        )
        epoch_fn = fused_epoch_body(step_body, n, gns_decay=self.gns_decay)
        fn = jax.jit(epoch_fn, donate_argnums=STATE_DONATION)
        self._fused_cache[key] = fn
        self.telemetry.programs_built += 1
        return fn

    def execute_fused(
        self,
        batches: Sequence[int],
        steps: int,
        *,
        lr_scale: float = 1.0,
        ctx: FusedSweepContext,
    ) -> Tuple[ExecutionResult, FusedProposal]:
        """Run one adaptive epoch as a single fused device program and
        return the on-device batch proposal for the *next* epoch alongside
        the usual :class:`ExecutionResult`.

        Exactly the same statistical semantics as :meth:`execute` (same
        step body scanned, same guard, same GNS gating — the EMA runs in
        float32 on device instead of float64 on host), plus the goodput
        sweep over ``ctx.candidates`` evaluated against the epoch-final
        noise scale.  The caller certifies the proposal off the critical
        path via :meth:`CannikinController.stage_fused_proposal`.
        """
        if self.cluster is None:
            raise RuntimeError("RealBackend not configured with a cluster")
        import jax.numpy as jnp

        from repro.data.pipeline import HeteroBatchPartitioner

        batches = [int(b) for b in batches]
        b_arr = np.asarray(batches, np.int64)
        b_max = _quantize(int(b_arr.max()))
        n = len(batches)
        use_sharded = self.sharded and n > 0

        node_ids = self._node_ids if len(self._node_ids) == n else tuple(range(n))
        if self.injector is not None:
            poison_np = self.injector.poison_factors(node_ids)
        else:
            poison_np = np.ones(n, np.float32)

        # Stage the whole epoch's data host-side, ship it once.
        tel = self.telemetry
        step0 = self.steps_done
        with TraceAnnotation("repro.fused", step_num=step0, steps=steps):
            with TraceAnnotation("repro.fused.input", step_num=step0):
                toks = labs = msks = None
                for s in range(steps):
                    raw = self.data.batch(self.steps_done, int(b_arr.sum()))
                    self.steps_done += 1
                    padded, _ = HeteroBatchPartitioner.padded(raw, batches)
                    seq = padded["tokens"].shape[-1]
                    if toks is None:
                        toks = np.zeros((steps, n, b_max, seq), np.int32)
                        labs = np.zeros((steps, n, b_max, seq), np.int32)
                        msks = np.zeros((steps, n, b_max), np.float32)
                        for i, b in enumerate(batches):
                            msks[:, i, :b] = 1.0
                    w = padded["tokens"].shape[1]
                    toks[s, :, :w], labs[s, :, :w] = padded["tokens"], padded["labels"]
            tel.count_steps(steps, int(b_arr.sum()), n * b_max)

            with TraceAnnotation("repro.fused.put", step_num=step0):
                place = (
                    self._node_placer(n, stacked=True) if use_sharded else jnp.asarray
                )
                toks_d, labs_d, msks_d = (tel.put(a, place) for a in (toks, labs, msks))
                gns0 = (
                    tel.put(np.float32(self.gns.ema_g), jnp.asarray),
                    tel.put(np.float32(self.gns.ema_s), jnp.asarray),
                    tel.put(np.int32(self.gns.count), jnp.asarray),
                )
                r = tel.put(np.asarray(ratios(batches), np.float32), jnp.asarray)
                lr = tel.put(np.float32(lr_scale), jnp.asarray)
                poison = tel.put(np.asarray(poison_np, np.float32), jnp.asarray)
                b_vec = tel.put(np.asarray(b_arr, np.float32), jnp.asarray)

            fused_fn = self._fused_epoch_fn(n)
            with TraceAnnotation("repro.fused.dispatch", step_num=step0):
                self.params, self.opt_state, telemetry = fused_fn(
                    self.params,
                    self.opt_state,
                    toks_d,
                    labs_d,
                    msks_d,
                    r,
                    lr,
                    poison,
                    b_vec,
                    gns0,
                    ctx.coeffs,
                    ctx.candidates,
                    float(ctx.lo0),
                    float(ctx.ref_batch),
                )
            # Blocks until the device has finished the epoch.
            with TraceAnnotation("repro.fused.pull", step_num=step0):
                (
                    losses_a, sq_is, sq_gs, valids, eg, es, cnt, b_noise_dev,
                    t_stars, goodputs, best, best_batches, sweep_iters,
                ) = tel.pull(telemetry)

        self.gns = GNSState(ema_g=float(eg), ema_s=float(es), count=int(cnt))
        anomaly_counts = (~np.asarray(valids, bool)).sum(axis=0)
        losses: List[float] = [float(x) for x in np.asarray(losses_a)]
        grad_obs: List[GradObservation] = []
        for s in range(steps):
            valid_np = np.asarray(valids[s], bool)
            self.anomalous_steps += int(not valid_np.all())
            grad_obs.append(
                GradObservation(
                    local_sqnorms=tuple(float(x) for x in np.asarray(sq_is[s])),
                    global_sqnorm=float(sq_gs[s]),
                    batches=tuple(batches),
                    valid=tuple(bool(v) for v in valid_np),
                )
            )

        epoch_seconds, measurements = self.cluster.run_epoch(batches, steps)
        measurements = list(measurements)
        if self.injector is not None:
            epoch_seconds, measurements = self.injector.perturb(
                self._job or "?", node_ids, epoch_seconds, measurements
            )
        self.sim_time += epoch_seconds

        result = ExecutionResult(
            epoch_seconds=epoch_seconds,
            measurements=tuple(measurements),
            losses=tuple(losses),
            grad_observations=tuple(grad_obs),
            b_noise=self.gns.b_noise,
            grad_anomalies=tuple(int(c) for c in anomaly_counts),
        )
        best_i = int(best)
        cand_np = np.asarray(ctx.candidates_np, np.float64)
        proposal = FusedProposal(
            best_index=best_i,
            total_batch=float(cand_np[best_i]),
            batches=np.asarray(best_batches, np.float64),
            t_star=float(np.asarray(t_stars)[best_i]),
            t_stars=np.asarray(t_stars, np.float64),
            goodputs=np.asarray(goodputs, np.float64),
            b_noise=float(b_noise_dev),
            sweep_iters=int(sweep_iters),
        )
        return result, proposal

    def _track_gns(self, obs: GradObservation) -> None:
        """Theorem-4.1 tracker (same guarded update the controller uses).

        Deliberately independent of any controller's tracker: the backend's
        ``b_noise`` serves baseline policies and standalone use, while a
        CannikinController re-ingests the same observations into its own
        state for planning.  The duplicate estimate is a host-side
        least-squares on an n-vector per step — noise next to the jitted
        training step."""
        try:
            _, g, s = estimate_gns(obs.local_sqnorms, obs.global_sqnorm, obs.batches)
        except (ValueError, np.linalg.LinAlgError):
            return
        self.gns = gns_update(self.gns, g, s, decay=self.gns_decay)

    # -- preemption state ------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The checkpointable pytree: everything that must survive
        preemption (params, opt-state, GNS state, stream counters).

        Params/opt-state are gathered to host numpy, so the snapshot (and
        the checkpoint generations built from it) is byte-identical across
        layouts, restores onto any later mesh, and stays valid after the
        next step donates the live device buffers."""
        import jax

        gather = lambda leaf: np.asarray(jax.device_get(leaf))  # noqa: E731
        return {
            "params": jax.tree_util.tree_map(gather, self.params),
            "opt_state": jax.tree_util.tree_map(gather, self.opt_state),
            "gns": {
                "ema_g": np.float64(self.gns.ema_g),
                "ema_s": np.float64(self.gns.ema_s),
                "count": np.int64(self.gns.count),
            },
            "counters": {
                "steps_done": np.int64(self.steps_done),
                "sim_time": np.float64(self.sim_time),
            },
        }

    def load_snapshot(self, state: Dict[str, Any]) -> None:
        import jax
        import jax.numpy as jnp

        # Fresh device copies: the step donates its params/opt-state, which
        # must never be buffers the caller's snapshot still holds.
        self.params = jax.tree_util.tree_map(jnp.array, state["params"])
        self.opt_state = jax.tree_util.tree_map(jnp.array, state["opt_state"])
        g = state["gns"]
        self.gns = GNSState(
            ema_g=float(g["ema_g"]), ema_s=float(g["ema_s"]), count=int(g["count"])
        )
        c = state["counters"]
        self.steps_done = int(c["steps_done"])
        self.sim_time = float(c["sim_time"])

    def checkpoint(self, path: str) -> None:
        from repro.train import checkpoint as ckpt  # lazy: avoids import cycle

        ckpt.save(path, self.snapshot())

    def restore(self, path: str) -> None:
        from repro.train import checkpoint as ckpt  # lazy: avoids import cycle

        self.load_snapshot(ckpt.restore(path, self.snapshot()))


# ---------------------------------------------------------------------------
# backend factory
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RealBackendConfig:
    """Recipe the runtime uses to build a :class:`RealBackend` for each job
    whose :class:`JobSpec` names ``backend="real"`` (model/data/optimizer
    are runtime-level concerns, not per-job spec payload).

    The model is ``arch`` at its published widths.  ``n_layers`` cuts its
    depth (to fit a chip's memory); ``reduced=True`` instead builds the
    arch's small CPU preset, which tests and CPU examples use."""

    arch: str = "olmo-1b"
    reduced: bool = False
    n_layers: Optional[int] = None
    seq_len: int = 512
    lr: float = 0.3
    gns_decay: float = 0.9
    sharded: bool = False

    def build(
        self, *, noise: float = 0.0, seed: int = 0, injector: Any = None
    ) -> RealBackend:
        from repro.configs import get_api
        from repro.data.pipeline import SyntheticLM
        from repro.optim.optimizers import constant_schedule, sgd

        api = get_api(self.arch, reduced=self.reduced, n_layers=self.n_layers)
        data = SyntheticLM(vocab=api.cfg.vocab, seq_len=self.seq_len, seed=seed)
        return RealBackend(
            api,
            sgd(constant_schedule(self.lr)),
            data,
            noise=noise,
            seed=seed,
            gns_decay=self.gns_decay,
            injector=injector,
            sharded=self.sharded,
        )


BACKENDS = ("sim", "real")


def make_backend(
    kind: str,
    *,
    noise: float = 0.0,
    seed: int = 0,
    real_config: Optional[RealBackendConfig] = None,
    injector: Any = None,
) -> "ExecutionBackend":
    """Build an execution backend by the name a :class:`JobSpec` carries."""
    if kind == "sim":
        return SimBackend(noise=noise, injector=injector)
    if kind == "real":
        return (real_config or RealBackendConfig()).build(
            noise=noise, seed=seed, injector=injector
        )
    raise ValueError(f"unknown execution backend {kind!r}; choose from {BACKENDS}")


# ---------------------------------------------------------------------------
# the policy loop (runtime-owned)
# ---------------------------------------------------------------------------


def run_backend_epoch(
    policy: Any,
    backend: "ExecutionBackend",
    *,
    steps: int,
    epoch_index: int = 0,
    last_measurement: Optional[StepMeasurement] = None,
    fixed_total: Optional[int] = None,
    fused: bool = False,
) -> Tuple[EpochRecord, ExecutionResult]:
    """One plan → execute → observe cycle over any backend.

    ``policy`` is a :class:`CannikinController` (plans epochs, ingests
    measurement + gradient telemetry) or a baseline partition policy
    (``partition(total, epoch, last_measurement)``).  Returns the unified
    :class:`EpochRecord` plus the raw :class:`ExecutionResult` (callers that
    loop feed ``result.measurements[-1]`` back as ``last_measurement``).

    ``fused=True`` (CannikinController + a backend with ``execute_fused``)
    runs the epoch as one fused device program: the plan consumes the
    proposal the *previous* fused epoch staged on device, and this epoch's
    program stages the next one.  Whenever the controller cannot supply a
    fused context (bootstrap, jax missing, certification failure) the cycle
    is exactly the two-program path — bit-compatible fallback.

    The cycle runs in a ``repro.epoch`` profiler span with ``epoch_index``
    as its argument, its phases in ``repro.epoch.plan``, ``.execute``,
    ``.observe`` and (fused) ``.stage``.
    """
    with TraceAnnotation("repro.epoch", epoch=epoch_index):
        if isinstance(policy, CannikinController):
            with TraceAnnotation("repro.epoch.plan", epoch=epoch_index):
                plan = policy.plan_epoch(prefer_fused=fused)
            epoch = plan.epoch
            batches = list(plan.batches)
            total = plan.total_batch
            lr_scale = plan.lr_scale
            predicted = plan.predicted_batch_time
            phase = plan.phase
        else:
            plan = None
            epoch = epoch_index
            total = getattr(policy, "total_batch", None) or fixed_total or 64
            with TraceAnnotation("repro.epoch.plan", epoch=epoch_index):
                batches = policy.partition(total, epoch, last_measurement)
            lr_scale, predicted, phase = 1.0, None, policy.name
        fused_ctx = None
        if (
            fused
            and isinstance(policy, CannikinController)
            and hasattr(backend, "execute_fused")
        ):
            fused_ctx = policy.fused_context()
        with TraceAnnotation("repro.epoch.execute", epoch=epoch_index):
            if fused_ctx is not None:
                result, proposal = backend.execute_fused(
                    batches, steps, lr_scale=lr_scale, ctx=fused_ctx
                )
            else:
                result = backend.execute(batches, steps, lr_scale=lr_scale)
                proposal = None
        if isinstance(policy, CannikinController):
            with TraceAnnotation("repro.epoch.observe", epoch=epoch_index):
                policy.observe_execution(result)
            if fused_ctx is not None and proposal is not None:
                with TraceAnnotation("repro.epoch.stage", epoch=epoch_index):
                    policy.stage_fused_proposal(fused_ctx, proposal)
            b_noise = policy.gns.b_noise
        else:
            b_noise = result.b_noise
    record = EpochRecord(
        epoch=epoch,
        backend=getattr(backend, "kind", "?"),
        total_batch=int(total),
        batches=tuple(int(b) for b in batches),
        lr_scale=float(lr_scale),
        phase=phase,
        predicted_batch_time=predicted,
        measured_batch_time=result.epoch_seconds / max(steps, 1),
        epoch_seconds=result.epoch_seconds,
        mean_loss=result.mean_loss,
        b_noise=b_noise,
        plan=plan,
    )
    return record, result


class EpochLoop:
    """The standalone policy loop: drive one (policy, backend) pair epoch by
    epoch, accumulating :class:`EpochRecord` history.  ``HeteroTrainer`` and
    the launch CLI are shells over this; ``JobHandle.advance`` runs the same
    :func:`run_backend_epoch` cycle under runtime lifecycle control."""

    def __init__(
        self,
        policy: Any,
        backend: "ExecutionBackend",
        *,
        steps_per_epoch: int = 8,
        fixed_total: Optional[int] = None,
        fused: bool = False,
    ) -> None:
        self.policy = policy
        self.backend = backend
        self.steps_per_epoch = steps_per_epoch
        self.fixed_total = fixed_total
        self.fused = fused
        self.epoch = 0
        self.history: List[EpochRecord] = []
        self.last_result: Optional[ExecutionResult] = None
        self._last_measurement: Optional[StepMeasurement] = None

    @property
    def sim_time(self) -> float:
        return self.backend.sim_time  # type: ignore[attr-defined]

    def run_epoch(self) -> EpochRecord:
        record, result = run_backend_epoch(
            self.policy,
            self.backend,
            steps=self.steps_per_epoch,
            epoch_index=self.epoch,
            last_measurement=self._last_measurement,
            fixed_total=self.fixed_total,
            fused=self.fused,
        )
        self.epoch += 1
        self.last_result = result
        if result.measurements:
            self._last_measurement = result.measurements[-1]
        self.history.append(record)
        return record

    def run(
        self, epochs: int, *, target_loss: Optional[float] = None
    ) -> List[EpochRecord]:
        for _ in range(epochs):
            record = self.run_epoch()
            if target_loss is not None and record.mean_loss <= target_loss:
                break
        return self.history
