"""Device time per step of the step's second, full-batch loss forward: the
step program's operations under the ``loss_forward`` scope in the traced
window (``chipbench.phases``), per step."""


def read(run):
    scope_s = (run["trace"] or {}).get("scope_s")
    if not scope_s or not run["steps"]:
        return None
    return 1e3 * scope_s["loss_forward"] / run["steps"]
