"""The attention kernels' share of their roofline: the least time the
traced steps need of them, the larger of their FLOPs over the peak and
their bytes over the HBM bandwidth (``attn_kernel_flops_per_step`` and
``attn_kernel_bytes_per_step`` of the cell's family, from the shapes).
Counted: the forward's q·kᵀ and p·v, the backward's four matmuls
dP = dO·vᵀ, dV = pᵀ·dO, dQ = dS·k, dK = dSᵀ·q plus the scores q·kᵀ
computed again, and remat's forward again; the kernels' second
recompute of the scores and dP in the dk/dv pass, and the masked half
of the diagonal blocks, are executed but not counted.  Over the device
time of the Pallas custom calls under the program's ``sdpa`` scope, per
chip.  Nothing to read where the family counts no such work or no
kernel ran."""
import moe_scopes


def read(run: dict):
    fam = run["cell"].family
    s = moe_scopes.of_run(run)
    if not hasattr(fam, "attn_kernel_flops_per_step") or s is None \
            or s["attn_kernel_s"] <= 0:
        return None
    cell, steps = run["cell"], run["record"]["steps"]
    shape = (cell.cfg, cell.mix["batch_per_chip"], cell.seq)
    return moe_scopes.roofline_pct(
        run, fam.attn_kernel_flops_per_step(*shape) * steps,
        fam.attn_kernel_bytes_per_step(*shape) * steps, s["attn_kernel_s"])
