"""Device idle time per step while the host builds the step's input (the
program's ``repro.step.input`` span: the token stream, the partitioner and
the ``(n, b_max)`` host arrays), from the traced window."""

SPAN = "repro.step.input"


def read(run):
    tr = run["trace"]
    idle = (tr or {}).get("idle_in_span") or {}
    if SPAN not in idle or not run["steps"]:
        return None
    return 1e3 * idle[SPAN] / run["steps"]
