"""Milliseconds per step in which a collective runs on the device (the
union of collective intervals, asynchronous ones from start to done),
averaged over the chips.  Nothing to read where no collective ran."""


def read(run: dict):
    t = run["record"].get("trace")
    if not t or t["collective_s"] <= 0:
        return None
    return 1e3 * t["collective_s"] / run["record"]["steps"]
