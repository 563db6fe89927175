"""On-device OptPerf: the jit-compiled batched water-fill sweep engine.

Third engine next to the scalar oracle and the NumPy batched engine of
:mod:`repro.core.optperf`.  The whole goodput sweep — a ``(C,)`` bracket
vector bisected against the ``(C, n)`` feasible-batch matrix — runs inside
one ``jax.jit`` with

  * :func:`device_coeffs`: :attr:`ClusterPerfModel.coeffs` exported once as
    stacked device arrays (cached per (model, dtype); the model is frozen so
    the export can never go stale),
  * a bounded ``lax.while_loop`` for geometric bracket growth,
  * a fixed-trip-count ``lax.fori_loop`` for the bisection itself (no
    per-iteration host round-trip, no data-dependent control flow), and
  * donate-friendly bracket state: the ``(lo, hi)`` vectors are donated to
    the jitted sweep where the backend supports donation, so epoch-over-epoch
    re-solves reuse the same device buffers.

This lets the controller re-solve OptPerf on-device beside the training step
(§4–5 of the paper re-solve continuously as the gradient-noise scale drifts)
with zero host work inside the loop.

:func:`solve_optperf_stacked_jax` is the cluster-scale variant: C
*independent* stacked rows (each row its own node subset + comm model,
padded to a common width and masked) bisected in one jit call — the
multi-job scheduler's per-round kernel, where J x N (job, candidate-node)
marginal problems solve simultaneously.  Its coefficient export is cached
on the :class:`~repro.core.perf_model.StackedClusterModel` instance
(``stacked_device_coeffs``).  Every cached export carries a *content stamp*
(a checksum of the live coefficient arrays) that is re-checked at solve
time: an in-place coefficient refresh that forgot to call
``invalidate_device_cache()`` is detected and the stale export (plus every
derived cache) is dropped and rebuilt — the kernel can no longer silently
solve the old regime.

:func:`solve_optperf_sweep_device` is the *trace-compatible* entry: the
same bracket-growth + bisection kernel as the jitted standalone sweep, but
callable from inside another ``jax.jit`` (no host work, no jit boundary of
its own).  :class:`RealBackend <repro.runtime.backend.RealBackend>` uses it
to fuse train-step + GNS statistics + the goodput sweep into one compiled
epoch program; :func:`device_partition` is its on-device analogue of the
host finalizer (clamp + proportional rescale, no float64 certification —
certification stays a host-side, off-critical-path check).

Warm starts seed the device brackets from the previous epoch's ``t_stars``
(±``warm_delta`` relative) with on-device validation: a seeded bracket whose
lower edge already over-assigns is reset to the cold lower bound, so stale
warm starts stay correct while valid ones cut the fixed trip count from
``max_iter`` to ``warm_max_iter``.

Precision: the device sweep runs in float32 unless x64 is enabled (pass
``dtype`` or run under ``jax.enable_x64``).  The emitted
``t_stars`` are certified and finalized *on the host in float64* through the
exact same :func:`repro.core.optperf._finalize_batches` path as the NumPy
engine, so partitions sum exactly and the two engines agree to the device
dtype's resolution (<= 1e-5 relative for float32, ~1e-10 for float64);
winners re-solved by the scalar oracle are identical across all engines.
"""
from __future__ import annotations

import collections
import functools
import zlib
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.optperf import (
    BatchedOptPerfSolution,
    _finalize_batches,
    _p_assigned,
    _p_best_single_node_time,
    _p_compute_mask,
    _problem_from_model,
    _problem_from_stack,
    _validated_totals,
)
from repro.core.perf_model import ClusterPerfModel, StackedClusterModel

__all__ = [
    "DeviceCoeffs",
    "StackedDeviceCoeffs",
    "device_coeffs",
    "evict_device_coeffs",
    "model_stamp",
    "stack_stamp",
    "stacked_device_coeffs",
    "device_partition",
    "device_node_times",
    "solve_optperf_sweep_device",
    "solve_optperf_batch_jax",
    "solve_optperf_stacked_jax",
]

_GROWTH_ITERS = 64


def model_stamp(model: ClusterPerfModel) -> int:
    """Content checksum of the coefficient numbers a solve would consume.

    Computed over the (memoized) ``coeffs`` view plus the comm model — the
    exact arrays every solver reads — so a model whose coefficient arrays
    were refreshed in place, bypassing the frozen-dataclass contract,
    produces a different stamp than the one recorded at device-export
    time."""
    acc = 0
    c = model.coeffs
    for arr in (c.alphas, c.cs, c.betas, c.ds, c.ks, c.ms):
        acc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), acc)
    comm = np.array([model.comm.t_o, model.comm.t_u, model.comm.gamma], np.float64)
    return zlib.crc32(comm.tobytes(), acc)


def stack_stamp(stack: StackedClusterModel) -> int:
    """Content checksum of a stack's *live* coefficient arrays.

    The scheduler refreshes stacked rows in place between reconcile rounds;
    the stamp recorded at export time is re-checked on every solve so a
    refresh that forgot ``invalidate_device_cache()`` can no longer serve
    stale device coefficients."""
    acc = 0
    for arr in (stack.alphas, stack.cs, stack.betas, stack.ds, stack.ks,
                stack.ms, stack.t_o, stack.t_u, stack.gamma, stack.mask):
        acc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), acc)
    return acc


class DeviceCoeffs(NamedTuple):
    """Stacked device-array view of one cluster's OptPerf coefficients."""

    alphas: "jax.Array"       # (n,)
    cs: "jax.Array"           # (n,)
    safe_betas: "jax.Array"   # (n,) betas with 1.0 at degenerate slots
    degenerate: "jax.Array"   # (n,) bool: beta <= 0 (syncStart flat in b)
    ds: "jax.Array"           # (n,)
    t_u: "jax.Array"          # scalar
    t_comm: "jax.Array"       # scalar


# LRU-bounded single-model export cache.  A plain dict (not functools.
# lru_cache) so that membership changes can *evict* a model's entries —
# an elastic controller that drops/adds nodes must not leave the dead
# cluster's coefficient stack pinned on the device (see
# CannikinController.add_nodes/remove_nodes).  Entries are
# (content_stamp, DeviceCoeffs): the stamp recorded at export time is
# compared against the model's live stamp on every lookup, so an in-place
# coefficient refresh can never be served a stale export.
_DEVICE_COEFFS_LIMIT = 128
_DEVICE_COEFFS: "collections.OrderedDict[Tuple[ClusterPerfModel, str], Tuple[int, DeviceCoeffs]]" = (
    collections.OrderedDict()
)


def _device_coeffs_cached(model: ClusterPerfModel, dtype_name: str) -> DeviceCoeffs:
    key = (model, dtype_name)
    stamp = model_stamp(model)
    hit = _DEVICE_COEFFS.get(key)
    if hit is not None:
        if hit[0] == stamp:
            _DEVICE_COEFFS.move_to_end(key)
            return hit[1]
        # The model's numbers changed under a cached export: drop every
        # device export of this model *and* the memoized host views derived
        # from the old numbers (coeffs / problem / validation), then rebuild
        # and re-stamp from the clean views.
        evict_device_coeffs(model)
        for slot in ("coeffs", "_optperf_problem", "_validated"):
            model.__dict__.pop(slot, None)
        stamp = model_stamp(model)
    c = model.coeffs
    dt = jnp.dtype(dtype_name)
    degenerate = c.betas <= 0.0
    dc = DeviceCoeffs(
        alphas=jnp.asarray(c.alphas, dt),
        cs=jnp.asarray(c.cs, dt),
        safe_betas=jnp.asarray(np.where(degenerate, 1.0, c.betas), dt),
        degenerate=jnp.asarray(degenerate),
        ds=jnp.asarray(c.ds, dt),
        t_u=jnp.asarray(model.comm.t_u, dt),
        t_comm=jnp.asarray(model.comm.t_comm, dt),
    )
    _DEVICE_COEFFS[key] = (stamp, dc)
    while len(_DEVICE_COEFFS) > _DEVICE_COEFFS_LIMIT:
        _DEVICE_COEFFS.popitem(last=False)
    return dc


def evict_device_coeffs(model: ClusterPerfModel) -> int:
    """Drop every cached device export of ``model`` (all dtypes).

    Called on cluster-membership changes: the old membership's coefficient
    stack must neither stay pinned in device memory nor be served to a
    later sweep over the same (stale) model object.  Returns the number of
    entries evicted."""
    stale = [key for key in _DEVICE_COEFFS if key[0] == model]
    for key in stale:
        del _DEVICE_COEFFS[key]
    return len(stale)


def device_coeffs(model: ClusterPerfModel, dtype=None) -> DeviceCoeffs:
    """Export (and cache) a model's coefficient arrays on the device.

    ``dtype`` defaults to float64 under x64 and float32 otherwise.  The cache
    is keyed on the frozen model *and* the dtype, so flipping x64 mid-process
    (e.g. ``jax.enable_x64``) never serves stale-width arrays.
    """
    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    return _device_coeffs_cached(model, np.dtype(dtype).name)


@functools.lru_cache(maxsize=1)
def _donate_argnums() -> Tuple[int, ...]:
    # Donation is a no-op (with a warning per call site) on backends that do
    # not support it; keep the sweep warning-free on CPU.
    return () if jax.default_backend() == "cpu" else (0, 1)


def _device_feasible(tt, alphas, cs, safe_betas, degenerate, ds, t_u, t_comm):
    """Clamped per-node feasible batch at cluster time(s) ``tt``.

    Trace-compatible transliteration of :func:`repro.core.optperf._p_feasible`
    + the zero clamp; ``tt`` must already be broadcast-shaped against the
    ``(..., n)`` coefficient arrays."""
    b_compute = (tt - t_u - cs) / alphas
    slack = tt - t_comm - ds
    b_comm = jnp.where(
        degenerate,
        jnp.where(slack >= 0.0, jnp.inf, -jnp.inf),
        slack / safe_betas,
    )
    return jnp.maximum(jnp.minimum(b_compute, b_comm), 0.0)


def _sweep_body(
    lo, hi, lo0, totals, tol,
    alphas, cs, safe_betas, degenerate, ds, t_u, t_comm, mask,
    *, max_iter: int, warm: bool,
):
    """The bracket-growth + bisection kernel, shared by every device sweep.

    Pure jnp/lax — traceable from inside another jit (the fused epoch
    program) as well as under the standalone jitted wrappers built by
    :func:`_device_sweep` / :func:`_device_stacked_sweep`.  ``mask`` is
    ``None`` for single-model sweeps and the ``(C, n)`` padding mask for
    stacked rows; ``lo0`` may be a scalar (single model) or a per-row
    vector (stacked) — both broadcast identically.

    Cold sweeps (``warm=False``) use a fixed-trip ``lax.fori_loop`` of
    ``max_iter`` steps — iterating past float convergence is harmless (the
    midpoint rounds onto an endpoint and the state is a fixed point), so no
    per-iteration convergence predicate — and therefore no host
    synchronization — is needed.  Warm sweeps instead validate the seeded
    lower edge (a stale lo that already over-assigns is reset to the
    certified cold bound) and run a convergence-checked ``lax.while_loop``
    bounded by ``max_iter``: a valid ±delta seed exits after
    ~log2(2*delta/tol) steps, while a stale bracket that snapped open keeps
    halving until it converges anyway.
    """

    def assigned(t):
        b = _device_feasible(
            t[:, None], alphas, cs, safe_betas, degenerate, ds, t_u, t_comm
        )
        if mask is not None:
            b = jnp.where(mask, b, 0.0)
        return b.sum(axis=-1)

    if warm:
        # Warm-seeded lower edges must strictly under-assign; reset any
        # that do not (stale warm start) to the certified cold bound.
        lo = jnp.where(assigned(lo) >= totals, lo0, lo)

    def grow_cond(state):
        i, h = state
        return (i < _GROWTH_ITERS) & jnp.any(assigned(h) < totals)

    def grow_body(state):
        i, h = state
        h = jnp.where(assigned(h) < totals, lo0 + (h - lo0) * 2.0, h)
        return i + 1, h

    _, hi_grown = lax.while_loop(grow_cond, grow_body, (jnp.int32(0), hi))

    def bisect_step(lo, hi):
        mid = 0.5 * (lo + hi)
        ge = assigned(mid) >= totals
        return jnp.where(ge, lo, mid), jnp.where(ge, mid, hi)

    if warm:
        def cond(state):
            i, lo, hi = state
            unconverged = jnp.any(hi - lo > tol * jnp.maximum(1.0, jnp.abs(hi)))
            return (i < max_iter) & unconverged

        def body(state):
            i, lo, hi = state
            lo, hi = bisect_step(lo, hi)
            return i + 1, lo, hi

        iters, lo, hi = lax.while_loop(cond, body, (jnp.int32(0), lo, hi_grown))
    else:
        lo, hi = lax.fori_loop(
            0, max_iter, lambda _, s: bisect_step(*s), (lo, hi_grown)
        )
        iters = jnp.int32(max_iter)
    return lo, hi, iters


@functools.lru_cache(maxsize=8)
def _device_sweep(max_iter: int, warm: bool):
    """Build (and cache) the jitted sweep for a static trip count.

    A thin jitted wrapper over :func:`_sweep_body` mapping donated
    ``(lo, hi)`` bracket state plus the stacked coefficients to the refined
    ``(lo, hi)``.
    """

    def sweep(
        lo, hi, lo0, totals, tol, alphas, cs, safe_betas, degenerate, ds, t_u, t_comm
    ):
        return _sweep_body(
            lo, hi, lo0, totals, tol,
            alphas, cs, safe_betas, degenerate, ds, t_u, t_comm, None,
            max_iter=max_iter, warm=warm,
        )

    return jax.jit(sweep, donate_argnums=_donate_argnums())


def solve_optperf_sweep_device(
    coeffs: DeviceCoeffs,
    total_batches,
    lo0,
    *,
    tol=None,
    max_iter: int = 64,
    lo=None,
    hi=None,
    warm: bool = False,
):
    """Trace-compatible candidate sweep: ``(t_stars, iters)`` on device.

    The same kernel as :func:`solve_optperf_batch_jax`'s jitted sweep, but
    with no jit boundary of its own — callable from *inside* another
    ``jax.jit`` (the fused epoch program runs train-step + GNS statistics +
    this sweep as one compiled program).  No host work happens here: the
    float64 certification + exact-sum finalization that
    :func:`solve_optperf_batch_jax` performs on the host become the
    caller's responsibility, as an async off-critical-path check (see
    ``CannikinController.consume_fused_plan``).

    ``total_batches`` may be a tracer; ``lo0`` is the cold lower bracket
    bound (host float or tracer); ``warm``/``max_iter`` must be static.
    Returns the ``(C,)`` refined upper bracket edge ``t_stars`` — each
    entry a device-dtype OptPerf estimate for its candidate total — and the
    bisection trip count actually spent.
    """
    dt = coeffs.alphas.dtype
    totals = jnp.asarray(total_batches, dt)
    lo0_dev = jnp.asarray(lo0, dt)
    if tol is None:
        tol = 8.0 * float(jnp.finfo(dt).eps)
    tol_dev = jnp.asarray(tol, dt)
    if lo is None:
        lo = jnp.full(totals.shape, lo0_dev, dt)
    if hi is None:
        hi = lo + 1.0
    _, hi_out, iters = _sweep_body(
        lo, hi, lo0_dev, totals, tol_dev,
        coeffs.alphas, coeffs.cs, coeffs.safe_betas, coeffs.degenerate,
        coeffs.ds, coeffs.t_u, coeffs.t_comm, None,
        max_iter=int(max_iter), warm=warm,
    )
    return hi_out, iters


def device_partition(coeffs: DeviceCoeffs, t_star, total):
    """On-device batch partition at cluster time ``t_star``: clamp + rescale.

    Trace-compatible analogue of the host finalizer's rescale step: the
    clamped feasible batches at ``t_star`` are proportionally scaled so they
    sum exactly (to device precision) to ``total``.  Zero rows stay zero;
    the float64 certification of the host path is deliberately absent —
    callers certify asynchronously against the host engines."""
    b = _device_feasible(
        t_star, coeffs.alphas, coeffs.cs, coeffs.safe_betas,
        coeffs.degenerate, coeffs.ds, coeffs.t_u, coeffs.t_comm,
    )
    s = b.sum(axis=-1, keepdims=True)
    total = jnp.asarray(total, b.dtype)
    scale = jnp.where(s > 0.0, total[..., None] / s, 0.0)
    return b * scale


def device_node_times(coeffs: DeviceCoeffs, batches):
    """Per-node batch times ``max(alpha b + c + t_u, beta b + d + t_comm)``
    — the trace-compatible analogue of the host finalizer's node-time pass.

    The row maximum over a finalized partition is the candidate's realized
    OptPerf: at small totals the water level can sit *below* a clamped
    node's fixed floor, so the bisected bracket alone understates the batch
    time (the host engines finalize the same way)."""
    betas = jnp.where(coeffs.degenerate, 0.0, coeffs.safe_betas)
    return jnp.maximum(
        coeffs.alphas * batches + coeffs.cs + coeffs.t_u,
        betas * batches + coeffs.ds + coeffs.t_comm,
    )


def solve_optperf_batch_jax(
    model: ClusterPerfModel,
    total_batches: Sequence[float],
    *,
    tol: float = 1e-10,
    max_iter: int = 64,
    warm_start: Optional[np.ndarray] = None,
    warm_delta: float = 1e-3,
    dtype=None,
) -> BatchedOptPerfSolution:
    """Solve the whole candidate sweep on-device; finalize on host in f64.

    Contract-compatible with :func:`repro.core.optperf.solve_optperf_batch`:
    same solution type, exact-sum partitions, ``t_stars`` usable as the next
    epoch's ``warm_start``.  ``max_iter`` bounds the bisection: the cold
    sweep runs it as a fixed trip count (64 trips reach float32 resolution
    from any practical bracket); the warm sweep exits early on convergence
    (~log2(2*delta/tol) steps for a valid ±delta seed) and only spends the
    full budget when a stale seed forced the bracket open.
    """
    totals_np = _validated_totals(total_batches)
    model.validate()
    dc = device_coeffs(model, dtype)
    dt = dc.alphas.dtype
    p, lo0 = _problem_from_model(model)

    totals_dev = jnp.asarray(totals_np, dt)
    lo0_dev = jnp.asarray(lo0, dt)
    tol_dev = jnp.asarray(max(tol, 8.0 * float(jnp.finfo(dt).eps)), dt)
    if warm_start is None:
        lo = jnp.full(totals_np.shape, lo0, dt)
        hi = lo + 1.0
        sweep = _device_sweep(int(max_iter), False)
    else:
        w = np.asarray(warm_start, dtype=np.float64)
        if w.shape != totals_np.shape:
            raise ValueError("warm_start shape must match total_batches")
        # Clamp seeds to a computable optimum ceiling — the best *single*
        # node processing the whole batch — so a stale-high seed cannot
        # open an astronomically wide bracket the iteration bound cannot
        # close (the while_loop still converges any bracket this wide).
        t_ub = _p_best_single_node_time(p, totals_np)
        w = np.where(np.isfinite(w) & (w > lo0), np.minimum(w, t_ub), lo0 + 1.0)
        lo = jnp.maximum(jnp.asarray(w * (1.0 - warm_delta), dt), lo0_dev)
        hi = jnp.maximum(jnp.asarray(w * (1.0 + warm_delta), dt), lo0_dev)
        sweep = _device_sweep(int(max_iter), True)
    _, hi_out, sweep_iters = sweep(
        lo, hi, lo0_dev, totals_dev, tol_dev,
        dc.alphas, dc.cs, dc.safe_betas, dc.degenerate, dc.ds, dc.t_u, dc.t_comm,
    )

    # Host float64 certification: the device ran in its own dtype (and XLA's
    # own reduction order), so its hi may sit a rounding error *below* the
    # true optimum.  Nudge up by dtype-epsilon-scaled steps until the float64
    # upper invariant holds, then reuse the exact shared finalizer.
    t_star = np.asarray(hi_out, dtype=np.float64)
    nudge = 8.0 * float(np.finfo(np.dtype(dt.name)).eps)
    polish = 0
    for _ in range(64):
        deficit = _p_assigned(p, t_star) < totals_np
        polish += 1
        if not deficit.any():
            break
        t_star = np.where(deficit, t_star * (1.0 + nudge) + 1e-300, t_star)
    else:
        raise RuntimeError("jax sweep t_star failed float64 certification")

    batches, node_times = _finalize_batches(p, totals_np, t_star, tol=tol)
    opt_perfs = node_times.max(axis=-1)
    compute_mask = _p_compute_mask(p, batches)
    for arr in (totals_np, t_star, opt_perfs, batches, compute_mask):
        arr.flags.writeable = False
    return BatchedOptPerfSolution(
        total_batches=totals_np,
        opt_perfs=opt_perfs,
        batches=batches,
        compute_mask=compute_mask,
        method="waterfill/jax" if warm_start is None else "waterfill/jax+warm",
        t_stars=t_star,
        iterations=int(sweep_iters) + polish,
    )


# ---------------------------------------------------------------------------
# Stacked rows on device — the cluster-scale scheduler kernel
# ---------------------------------------------------------------------------


class StackedDeviceCoeffs(NamedTuple):
    """Device-array view of a :class:`StackedClusterModel`: C independent
    padded problem rows, each with its own node subset (``mask``) and its
    own communication model (the ``(C, 1)`` comm columns broadcast against
    the ``(C, n)`` coefficient arrays)."""

    alphas: "jax.Array"       # (C, n)
    cs: "jax.Array"           # (C, n)
    safe_betas: "jax.Array"   # (C, n) betas with 1.0 at degenerate slots
    degenerate: "jax.Array"   # (C, n) bool: beta <= 0
    ds: "jax.Array"           # (C, n)
    t_u: "jax.Array"          # (C, 1)
    t_comm: "jax.Array"       # (C, 1)
    mask: "jax.Array"         # (C, n) bool; False = padding slot


def stacked_device_coeffs(stack: StackedClusterModel, dtype=None) -> StackedDeviceCoeffs:
    """Export (and cache) a stack's coefficient arrays on the device.

    Cached in the stack's :meth:`~StackedClusterModel.device_cache` slot
    keyed by dtype, so repeated solves of a persistent stack (the scheduler
    re-runs the same seed stack on every reconcile) ship arrays once.  Each
    cached export records a content stamp of the live coefficient arrays,
    re-checked here on every call: a stack whose arrays were refreshed in
    place *without* ``invalidate_device_cache()`` trips the stamp, every
    derived cache (device exports, solver problem view, validation memo) is
    dropped, and a fresh export of the refreshed numbers is shipped.
    """
    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    key = np.dtype(dtype).name
    stamp = stack_stamp(stack)
    cache = stack.device_cache()
    hit = cache.get(key)
    if hit is not None and hit[0] != stamp:
        # In-place refresh under a live export: nuke the device exports AND
        # the host-side derived views memoized from the old numbers.
        stack.invalidate_device_cache()
        cache = stack.device_cache()
        hit = None
    if hit is None:
        dt = jnp.dtype(key)
        degenerate = stack.betas <= 0.0
        col = lambda v: v[:, None]  # noqa: E731
        sdc = StackedDeviceCoeffs(
            alphas=jnp.asarray(stack.alphas, dt),
            cs=jnp.asarray(stack.cs, dt),
            safe_betas=jnp.asarray(np.where(degenerate, 1.0, stack.betas), dt),
            degenerate=jnp.asarray(degenerate),
            ds=jnp.asarray(stack.ds, dt),
            t_u=jnp.asarray(col(stack.t_u), dt),
            t_comm=jnp.asarray(col(stack.t_comm), dt),
            mask=jnp.asarray(stack.mask),
        )
        cache[key] = (stamp, sdc)
        return sdc
    return hit[1]


@functools.lru_cache(maxsize=8)
def _device_stacked_sweep(max_iter: int, warm: bool):
    """Jitted stacked sweep for a static trip count (cached per
    (max_iter, warm); XLA re-specializes per (C, n) shape inside the jit).

    The same :func:`_sweep_body` kernel as :func:`_device_sweep` with three
    stacked generalizations flowing through its arguments: the
    feasible-batch kernel masks padding slots out of every row sum, the
    comm scalars are per-row ``(C, 1)`` columns, and the cold lower bound
    ``lo0`` is a per-row vector.
    """

    def sweep(
        lo, hi, lo0, totals, tol,
        alphas, cs, safe_betas, degenerate, ds, t_u, t_comm, mask,
    ):
        return _sweep_body(
            lo, hi, lo0, totals, tol,
            alphas, cs, safe_betas, degenerate, ds, t_u, t_comm, mask,
            max_iter=max_iter, warm=warm,
        )

    return jax.jit(sweep, donate_argnums=_donate_argnums())


def solve_optperf_stacked_jax(
    stack: StackedClusterModel,
    total_batches: Sequence[float],
    *,
    tol: float = 1e-10,
    max_iter: int = 64,
    warm_start: Optional[np.ndarray] = None,
    warm_delta: float = 1e-3,
    dtype=None,
) -> BatchedOptPerfSolution:
    """Water-fill C independent stacked rows on-device; finalize on host.

    Contract-compatible with :func:`repro.core.optperf.solve_optperf_stacked`
    (same solution type, exact-sum partitions, padding-aware extraction,
    ``t_stars`` usable as the next round's ``warm_start``).  The whole
    scheduler round — every (job, candidate-node) marginal problem — bisects
    as one jit call; host float64 certification and finalization go through
    the exact shared :func:`_finalize_batches` path, so the jax and NumPy
    stacked engines agree to the device dtype's resolution (<= 1e-5 relative
    in float32).
    """
    totals_np = _validated_totals(total_batches)
    if totals_np.shape[0] != stack.shape[0]:
        raise ValueError("total_batches length must match stack rows")
    stack.validate()
    sdc = stacked_device_coeffs(stack, dtype)
    dt = sdc.alphas.dtype
    p, lo0 = _problem_from_stack(stack)

    totals_dev = jnp.asarray(totals_np, dt)
    lo0_dev = jnp.asarray(lo0, dt)
    tol_dev = jnp.asarray(max(tol, 8.0 * float(jnp.finfo(dt).eps)), dt)
    if warm_start is None:
        lo = jnp.asarray(lo0, dt)
        hi = lo + 1.0
        sweep = _device_stacked_sweep(int(max_iter), False)
    else:
        w = np.asarray(warm_start, dtype=np.float64)
        if w.shape != totals_np.shape:
            raise ValueError("warm_start shape must match total_batches")
        # Same stale-seed safeguards as the single-model engine: clamp to the
        # per-row best-single-node ceiling (mask-aware) and reset unusable
        # seeds to just above the cold lower bound.
        t_ub = _p_best_single_node_time(p, totals_np)
        w = np.where(np.isfinite(w) & (w > lo0), np.minimum(w, t_ub), lo0 + 1.0)
        lo = jnp.maximum(jnp.asarray(w * (1.0 - warm_delta), dt), lo0_dev)
        hi = jnp.maximum(jnp.asarray(w * (1.0 + warm_delta), dt), lo0_dev)
        sweep = _device_stacked_sweep(int(max_iter), True)
    _, hi_out, sweep_iters = sweep(
        lo, hi, lo0_dev, totals_dev, tol_dev,
        sdc.alphas, sdc.cs, sdc.safe_betas, sdc.degenerate, sdc.ds,
        sdc.t_u, sdc.t_comm, sdc.mask,
    )

    # Host float64 certification — identical to the single-model jax path.
    t_star = np.asarray(hi_out, dtype=np.float64)
    nudge = 8.0 * float(np.finfo(np.dtype(dt.name)).eps)
    polish = 0
    for _ in range(64):
        deficit = _p_assigned(p, t_star) < totals_np
        polish += 1
        if not deficit.any():
            break
        t_star = np.where(deficit, t_star * (1.0 + nudge) + 1e-300, t_star)
    else:
        raise RuntimeError("stacked jax sweep t_star failed float64 certification")

    batches, node_times = _finalize_batches(p, totals_np, t_star, tol=tol)
    opt_perfs = node_times.max(axis=-1)
    compute_mask = _p_compute_mask(p, batches)
    node_mask = np.array(stack.mask, dtype=bool)  # copy: stacks may be reused
    for arr in (totals_np, t_star, opt_perfs, batches, compute_mask, node_mask):
        arr.flags.writeable = False
    return BatchedOptPerfSolution(
        total_batches=totals_np,
        opt_perfs=opt_perfs,
        batches=batches,
        compute_mask=compute_mask,
        method=(
            "waterfill/stacked-jax"
            if warm_start is None
            else "waterfill/stacked-jax+warm"
        ),
        t_stars=t_star,
        iterations=int(sweep_iters) + polish,
        node_mask=node_mask,
    )
