"""The backend's counters: steps, padded rows, host<->device transfers and
step programs built.

``RealBackend.telemetry`` is one :class:`Telemetry`.  The counts are taken
where the work happens, never written in by hand:

* the backend ships every host array it hands a step program through
  :meth:`Telemetry.put` and reads every result back through
  :meth:`Telemetry.pull`, which count each array and its ``nbytes``.
  Python scalars handed to ``jit`` as arguments are shipped by ``jit``
  itself and are not counted;
* each step adds the rows the plan assigned (``rows_useful``, the sum of
  the per-node batches) and the rows its backward computes
  (``rows_computed``: the sum of the per-node padded widths, ``n x b_max``
  where every node runs at ``b_max``), so padding is ``rows_computed -
  rows_useful``; ``steps_ragged`` counts the steps whose nodes ran at
  more than one width;
* each miss of a step-program cache (two-program, sharded, fused) adds one
  to ``programs_built``.  A cached jit that retraces for a new shape is
  not a miss.

A reader takes the difference of two :meth:`snapshot` calls around the
work it measures.  Host spans of the same work are ``jax.profiler``
annotations in ``repro.runtime.backend`` and live in the profiler's trace.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import numpy as np

__all__ = ["Telemetry"]


@dataclasses.dataclass
class Telemetry:
    """Counters of one backend since it was built or last :meth:`reset`."""

    steps: int = 0
    rows_useful: int = 0
    rows_computed: int = 0
    steps_ragged: int = 0
    h2d_arrays: int = 0
    h2d_bytes: int = 0
    d2h_arrays: int = 0
    d2h_bytes: int = 0
    programs_built: int = 0

    def put(self, host: Any, place: Callable[[np.ndarray], Any]) -> Any:
        """Ship one host array with ``place`` (``jnp.asarray`` or a sharded
        ``device_put``) and count it."""
        host = np.asarray(host)
        self.h2d_arrays += 1
        self.h2d_bytes += int(host.nbytes)
        return place(host)

    def pull(self, tree: Any) -> Any:
        """Read a device value, or a pytree of them, back to host numpy,
        counting each leaf; blocks until the device has produced them."""
        import jax

        host = jax.device_get(tree)
        for leaf in jax.tree_util.tree_leaves(host):
            self.d2h_arrays += 1
            self.d2h_bytes += int(np.asarray(leaf).nbytes)
        return host

    def count_steps(
        self, steps: int, rows_useful: int, rows_computed: int, *, ragged: bool = False
    ) -> None:
        """``steps`` steps, each over ``rows_useful`` planned rows padded to
        ``rows_computed``; ``ragged``: its nodes ran at more than one width."""
        self.steps += int(steps)
        self.steps_ragged += int(steps) * bool(ragged)
        self.rows_useful += int(steps) * int(rows_useful)
        self.rows_computed += int(steps) * int(rows_computed)

    def snapshot(self) -> Dict[str, int]:
        return dataclasses.asdict(self)

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)
