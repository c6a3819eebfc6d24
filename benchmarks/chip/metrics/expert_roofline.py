"""The held experts' grouped matmuls' share of their roofline: the least
time the traced steps need of them, the larger of their FLOPs over the
peak and their bytes over the HBM bandwidth (``expert_flops_per_step``
and ``expert_bytes_per_step`` of the cell's family: T·k·held/E pairs a
MoE layer, the balanced share, each 24·d·f_e FLOPs with remat's
recompute: gate, up and down in the forward, twice that in the
backward, the forward again), over the device time of the ops under
the program's ``experts`` scope, per chip.  Nothing to read where the
family counts no expert work or the program carries no such scope."""
import moe_scopes


def read(run: dict):
    fam = run["cell"].family
    s = moe_scopes.of_run(run)
    if not hasattr(fam, "expert_flops_per_step") or s is None \
            or s["parts_s"]["experts"] <= 0:
        return None
    cell, steps = run["cell"], run["record"]["steps"]
    shape = (cell.cfg, cell.mix["batch_per_chip"], cell.seq)
    return moe_scopes.roofline_pct(
        run, fam.expert_flops_per_step(*shape) * steps,
        fam.expert_bytes_per_step(*shape) * steps, s["parts_s"]["experts"])
