"""Share of the rows the step computes that are padding: 100 x (computed -
useful) / computed over the window, from the program's own counters
(``RealBackend.telemetry``: ``rows_computed`` adds ``n x b_max`` per step,
``rows_useful`` the plan's batches)."""


def read(run):
    prog = run.get("program")
    if not prog or not prog.get("rows_computed"):
        return None
    return 100.0 * (prog["rows_computed"] - prog["rows_useful"]) / prog["rows_computed"]
