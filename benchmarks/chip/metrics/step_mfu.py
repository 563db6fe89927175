"""Model FLOP utilisation of the step while the device is busy: model
FLOPs of the useful tokens trained in the traced window (shape-derived,
``chipbench.flops``) over busy time x peak x chips."""


def read(run):
    tr = run["trace"]
    if tr is None or tr["busy_s"] <= 0 or not run["useful_tokens"]:
        return None
    flops = run["useful_tokens"] * run["flops_per_token"]
    return 100.0 * flops / (tr["busy_s"] * run["peak_flops"] * run["chips"])
