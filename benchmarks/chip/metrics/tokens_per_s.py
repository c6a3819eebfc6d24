"""Training tokens of every step the timed window dispatched, over all
chips, divided by the window's host-clock seconds (dispatch of the first
step to the end of the last)."""


def read(run: dict):
    rec = run["record"]
    if "seconds" not in rec:
        return None
    return rec["steps"] * run["tokens_per_step"] / rec["seconds"]
