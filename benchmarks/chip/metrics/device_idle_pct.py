"""Share of the traced window in which no op runs on the device (leaf
ops, and asynchronous collectives from start to done), averaged over
the chips: the host holding the chip back."""


def read(run: dict):
    t = run["record"].get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
