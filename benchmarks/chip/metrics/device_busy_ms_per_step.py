"""Device time per training step: the union of the device's operation
intervals in the traced window (averaged over the chips), per step."""


def read(run):
    tr = run["trace"]
    if tr is None or not run["steps"]:
        return None
    return 1e3 * tr["busy_s"] / run["steps"]
