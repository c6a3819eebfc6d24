"""Device time of the expert layer and of the attention kernels, by the
program's named scopes, for the readers ``moe_ms``, ``expert_roofline``
and ``attn_kernel_roofline``.

Each non-collective op of the traced step counts by the ``op_name``
path the compiled HLO keeps for it (a fusion by its root's), whatever
its phase: under ``moe`` (``models/moe.py``: ``router``, ``dispatch``,
``experts``, ``combine``; the shared experts' ``mlp`` inside it too), or
a Pallas custom call under ``sdpa``.  Seconds are summed over a
device's ops and averaged over the devices.  The step's HLO is compiled
again for the cell (as ``scopes.step_hlo`` does) once per run and the
result kept in the run's trace record.
"""
from __future__ import annotations

import json
import os

import scopes
import trace_reduce as tr

MOE = "moe"
MOE_PARTS = ("router", "dispatch", "experts", "combine")
KEY = "moe_scopes"


def reduce(trace: dict, parsed: dict) -> dict:
    """{"moe_s", "parts_s": {part: s}, "attn_kernel_s"} of a trace
    record (``per_device``) read against the parsed HLO."""
    instrs = parsed["instrs"]
    n = len(trace["per_device"])
    moe = kernel = 0.0
    parts = dict.fromkeys(MOE_PARTS, 0.0)
    present = set()
    for dev in trace["per_device"]:
        for name, sec in dev["per_op_s"].items():
            ins = instrs.get(name)
            if ins is None or any(ins["opcode"].startswith(c)
                                  for c in tr.COLLECTIVES):
                continue
            found = scopes.names(ins["op_name"])
            present |= found & {MOE, scopes.ATTN_CORE}
            if MOE in found:
                moe += sec / n
                for p in MOE_PARTS:
                    if p in found:
                        parts[p] += sec / n
            if scopes.ATTN_CORE in found and ins["opcode"] == "custom-call":
                kernel += sec / n
    return {"moe_s": moe, "parts_s": parts, "attn_kernel_s": kernel,
            "present": sorted(present)}


def of_run(run: dict):
    """The record of a traced run, made once and kept in its trace
    record; None where the run has no trace."""
    t = run["record"].get("trace")
    if not t:
        return None
    if KEY not in t:
        t[KEY] = reduce(t, tr.parse_hlo(scopes.step_hlo(run["cell"])))
    return t[KEY]


def roofline_pct(run: dict, flops: float, bytes_: float, seconds: float):
    """Percent of the roofline: the least time the chip could take,
    the larger of ``flops`` over the FLOP peak and ``bytes_`` over the
    HBM bandwidth of the run's device (``peaks.json``, by its FLOP
    peak), over the measured ``seconds``."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    bw = [e["hbm_bytes_per_s"] for e in table.values()
          if e["bf16_flops_per_s"] == run["peak"]]
    if len(bw) != 1:
        raise KeyError(f"no one device in peaks.json has the FLOP peak "
                       f"{run['peak']}")
    return 100.0 * max(flops / run["peak"], bytes_ / bw[0]) / seconds
