"""Milliseconds per step in which a collective runs and no compute op
does: the aggregation not hidden behind compute, averaged over the
chips.  Nothing to read where no collective ran."""


def read(run: dict):
    t = run["record"].get("trace")
    if not t or t["collective_s"] <= 0:
        return None
    return 1e3 * t["exposed_s"] / run["record"]["steps"]
