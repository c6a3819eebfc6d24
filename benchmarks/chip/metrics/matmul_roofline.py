"""The matmul kernels' share of their roofline: the FLOPs that the ops
holding a convolution or dot executed in the traced steps (counted from
the compiled HLO per execution, so remat's recomputation and attention's
masked blocks are in), over peak x the summed device time of those ops,
per chip.  Matmuls at these shapes are bound by compute, so the FLOP
peak is the roofline."""


def read(run: dict):
    t = run["record"].get("trace")
    if not t or t["matmul_s"] <= 0:
        return None
    return 100.0 * t["matmul_flops"] / (run["peak"] * t["matmul_s"])
