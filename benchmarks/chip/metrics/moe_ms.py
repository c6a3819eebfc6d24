"""Milliseconds per step of the expert layer on the device: ops under
the program's ``moe`` scope (router, dispatch, the held experts' grouped
matmuls, combine, and the shared experts) in the forward, the recompute
and the backward, averaged over the chips.  Nothing to read where the
program carries no such scope."""
import moe_scopes


def read(run: dict):
    s = moe_scopes.of_run(run)
    if s is None or moe_scopes.MOE not in s["present"]:
        return None
    return 1e3 * s["moe_s"] / run["record"]["steps"]
