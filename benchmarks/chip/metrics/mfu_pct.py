"""The whole train step's share of the chips' peak: the model FLOPs the
traced steps require (families/<family>.py, no recomputation counted),
over chips x peak x the traced window (first program start to last
program end on the device, averaged over the chips)."""


def read(run: dict):
    t = run["record"].get("trace")
    if not t or t["window_s"] <= 0:
        return None
    work = run["flops_per_step"] * run["record"]["steps"]
    return 100.0 * work / (run["chips"] * run["peak"] * t["window_s"])
