"""Device time per step of the vmapped per-node backward: the step
program's operations under the ``node_backward`` scope in the traced
window (``chipbench.phases``), per step."""


def read(run):
    scope_s = (run["trace"] or {}).get("scope_s")
    if not scope_s or not run["steps"]:
        return None
    return 1e3 * scope_s["node_backward"] / run["steps"]
