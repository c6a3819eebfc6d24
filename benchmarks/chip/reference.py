"""The plain reference of a training cell, and the control.

The reference repeats the cell's checked steps from the same seed: the
family's float32 loss (matmuls at HIGHEST precision), its gradient over
the global batch in blocks of rows, clipping by the global norm, and
AdamW as the mix states it.  It runs on one chip after the window, once
the program's state is freed.  It imports nothing of the program.

The control is the same computation one precision below the program's
bfloat16: each matmul operand rounded to float8 e4m3 with a per-tensor
scale (``round_fp8``), the step a later change might be tempted by.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import bench

FP8_MAX = 448.0      # largest finite float8 e4m3fn


def round_fp8(x):
    """x rounded to float8 e4m3 under a per-tensor absmax scale; the
    gradient passes straight through."""
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


class RefTrainer:
    """The reference's training step on one device, for one cell."""

    def __init__(self, cell, device, mm=None):
        fam = cell.family
        self.cell = cell
        self.device = device
        self.sh = jax.sharding.SingleDeviceSharding(device)
        self.names = sorted(fam.leaf_shapes(cell.cfg))
        mm = mm or fam.make_mm()
        o = cell.mix["optimizer"]
        clip = cell.mix["clip_norm"]
        names = self.names

        def loss(p, t, lab):
            return fam.loss(p, t, lab, cell.cfg, mm)

        self.grad = jax.jit(jax.value_and_grad(loss))
        self.add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.add, a, b), donate_argnums=0)

        def update(p, state, g, blocks):
            g = jax.tree_util.tree_map(lambda x: x / blocks, g)
            raw = bench._tree_norms(g, names)
            norm = jnp.sqrt(jnp.sum(jnp.square(raw)))
            scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
            g = jax.tree_util.tree_map(lambda x: x * scale, g)
            count = state["count"] + 1
            c = count.astype(jnp.float32)
            bc1, bc2 = 1.0 - o["b1"] ** c, 1.0 - o["b2"] ** c
            m = jax.tree_util.tree_map(
                lambda m_, g_: o["b1"] * m_ + (1 - o["b1"]) * g_,
                state["m"], g)
            v = jax.tree_util.tree_map(
                lambda v_, g_: o["b2"] * v_ + (1 - o["b2"]) * g_ * g_,
                state["v"], g)
            p = jax.tree_util.tree_map(
                lambda p_, m_, v_: p_ - o["lr"] * (
                    (m_ / bc1) / (jnp.sqrt(v_ / bc2) + o["eps"])
                    + o["weight_decay"] * p_), p, m, v)
            return p, {"m": m, "v": v, "count": count}, raw

        self.update = jax.jit(update, donate_argnums=(0, 1))

    def opt_init(self, params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"m": zeros,
                "v": jax.tree_util.tree_map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32)}

    def step(self, params, state, batch):
        """One step over the global batch, in blocks of
        ``ref_block_rows`` rows.  Returns (params, state, metrics) with
        the raw gradient's leaf norms under ``grad_raw``."""
        rows = batch["tokens"].shape[0]
        blk = self.cell.mix["ref_block_rows"]
        if rows % blk:
            raise ValueError(f"{rows} rows in blocks of {blk}")
        batch = jax.device_put(batch, self.sh)
        total, acc = 0.0, None
        for r in range(0, rows, blk):
            loss, g = self.grad(params, batch["tokens"][r:r + blk],
                                batch["labels"][r:r + blk])
            total = total + loss
            acc = g if acc is None else self.add(acc, g)
        blocks = rows // blk
        params, state, raw = self.update(params, state, acc, blocks)
        return params, state, {"loss": total / blocks, "grad_raw": raw}


def control_program(cell, devices) -> "bench.Program":
    """The control in the program's place: the reference's step with its
    matmul operands in float8."""
    trainer = RefTrainer(cell, devices[0],
                         cell.family.make_mm(round_fp8))
    return bench.Program(trainer.step, trainer.sh, trainer.sh, trainer.sh,
                         trainer.opt_init)


def readings(cell, seed: int, device, host_pool: list, mm=None) -> dict:
    """The reference's readings of the checked steps from ``seed``, on
    ``device``: losses, each leaf's norm of the first clipped gradient
    and of the raw one, the first clipped gradient itself (host arrays),
    each leaf's norm of the change."""
    trainer = RefTrainer(cell, device, mm)
    prog = bench.Program(trainer.step, trainer.sh, trainer.sh, trainer.sh,
                         trainer.opt_init)
    with jax.default_matmul_precision("highest"):
        makers = bench.makers(prog, cell)
        words = bench.seed_words(seed)
        params, state = makers.state(words)
        losses, grad, raw, tree = [], None, None, None
        for i in range(cell.mix["checked_steps"]):
            params, state, m = trainer.step(params, state, host_pool[i])
            losses.append(float(m["loss"]))
            if i == 0:
                raw = np.asarray(m["grad_raw"], np.float64)
                grad = np.asarray(makers.first_grad(state), np.float64)
                tree = bench.first_grad_tree(state, cell)
        delta = np.asarray(makers.delta(params, words), np.float64)
    return {"losses": np.array(losses), "grad": grad, "grad_raw": raw,
            "grad_tree": tree, "delta": delta}
