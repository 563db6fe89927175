"""Device idle time per step while the host waits for the step's results
(the program's ``repro.step.pull`` span: the reads of ``valid``, the loss
and the square norms, which block until the device finishes), from the
traced window."""

SPAN = "repro.step.pull"


def read(run):
    tr = run["trace"]
    idle = (tr or {}).get("idle_in_span") or {}
    if SPAN not in idle or not run["steps"]:
        return None
    return 1e3 * idle[SPAN] / run["steps"]
