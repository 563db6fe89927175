"""Controller time per epoch on the critical path: the growth of the
program's own ``ControllerStats.overhead_seconds`` (its clock around
``plan_epoch`` and the host float64 certification; ``observe_execution``
is not inside it) over the window, per epoch of the window."""


def read(run):
    if not run["epochs"]:
        return None
    return 1e3 * run["controller_s"] / run["epochs"]
