"""Device time per step of Eq. 9 and the update: the step program's
operations under the ``eq9_aggregate`` (poison, node square norms, guard,
weighted sum, |g|^2) and ``optimizer_update`` scopes in the traced window
(``chipbench.phases``), per step."""


def read(run):
    scope_s = (run["trace"] or {}).get("scope_s")
    if not scope_s or not run["steps"]:
        return None
    return 1e3 * (scope_s["eq9_aggregate"] + scope_s["optimizer_update"]) / run["steps"]
