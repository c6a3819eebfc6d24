"""Share of the attention core's device time that runs in Pallas
kernels: ops of HLO opcode ``custom-call`` under the program's ``sdpa``
scope, over every op under it (``attn_core_ms``'s ops), in every phase
and summed over the chips.  The rest is layout copies, casts and the
backward's delta.  Nothing to read where the program carries no such
scope; 0 where the attention core holds no kernel."""
import scopes
import trace_reduce as tr

KEY = "attn_kernel_pct"


def share(trace: dict, parsed: dict):
    """Percent of the ``sdpa`` ops' summed device time in custom calls,
    over ``trace["per_device"]``; None where no op is under ``sdpa``."""
    instrs = parsed["instrs"]
    core = kernel = 0.0
    for dev in trace["per_device"]:
        for name, sec in dev["per_op_s"].items():
            ins = instrs.get(name)
            if ins is None or \
                    scopes.ATTN_CORE not in scopes.names(ins["op_name"]):
                continue
            core += sec
            if ins["opcode"] == "custom-call":
                kernel += sec
    return 100.0 * kernel / core if core > 0 else None


def read(run: dict):
    t = run["record"].get("trace")
    if not t:
        return None
    if KEY not in t:
        text = scopes.step_hlo(run["cell"])
        if "scopes" not in t:
            t["scopes"] = scopes.reduce(t, tr.read_hlo(text))
        t[KEY] = share(t, tr.parse_hlo(text))
    return t[KEY]
