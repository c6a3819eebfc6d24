#!/usr/bin/env python3
"""Rehearse a cell without the chip: compile its train step, and the
reference's gradient and update, for a described v5e and print what
``memory_analysis()`` says each needs per device.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot.py <workload> \
        [--remat 0|1]

A compile that passes is not a chip run; it says nothing of time.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import bench  # noqa: E402
import reference  # noqa: E402

GIB = 2 ** 30


def describe(name, compiled):
    m = compiled.memory_analysis()
    args, temp = m.argument_size_in_bytes, m.temp_size_in_bytes
    out, alias = m.output_size_in_bytes, m.alias_size_in_bytes
    print(f"{name}: arguments {args / GIB:.3f} GiB, temporaries "
          f"{temp / GIB:.3f} GiB, outputs {out / GIB:.3f} GiB, aliased "
          f"{alias / GIB:.3f} GiB; peak about "
          f"{(args + temp + out - alias) / GIB:.3f} GiB", flush=True)


def main(argv=None):
    from jax.experimental import topologies

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--remat", type=int, choices=(0, 1), default=None)
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    cell = bench.load_cell(args.workload)
    if args.remat is not None:
        cell.cfg["program"]["remat"] = bool(args.remat)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)
    prog = bench.build_program(cell, devices)

    def structs(tree, sh):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sh)

    words = bench.seed_words(0)
    p = jax.eval_shape(lambda w: bench.weights(w, cell), words)
    o = jax.eval_shape(prog.opt_init, p)
    b = jax.eval_shape(lambda w: bench.batches(w, cell, 0, 1)[0], words)
    sh_p = prog.param_sh
    sh_o = prog.opt_sh
    sh_b = prog.batch_sh
    lowered = prog.step.lower(structs(p, sh_p), structs(o, sh_o),
                              structs(b, sh_b))
    describe(f"{cell.name} train step "
             f"(remat={cell.cfg['program']['remat']})", lowered.compile())

    one = jax.sharding.SingleDeviceSharding(devices[0])
    ref = reference.RefTrainer(cell, devices[0])
    blk = cell.mix["ref_block_rows"]
    rows = jax.ShapeDtypeStruct((blk, cell.seq), jnp.int32, sharding=one)
    ps = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), p)
    describe(f"{cell.name} reference gradient ({blk} rows)",
             ref.grad.lower(ps, rows, rows).compile())
    st = jax.eval_shape(ref.opt_init, p)
    sts = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), st)
    describe(f"{cell.name} reference update",
             ref.update.lower(ps, sts, ps, 1).compile())


if __name__ == "__main__":
    main()
