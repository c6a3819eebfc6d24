#!/usr/bin/env python3
"""Record the small trace the trace-reduction tests read: a two-layer
model at smollm's widths, data parallel over every chip present (so the
trace holds the aggregation's collectives), two traced steps through the
harness's own dispatch loop.  Writes ``<out>/<name>.xplane.pb.gz`` and
the step's compiled HLO text as ``<out>/<name>.hlo.txt.gz``.

    python3 benchmarks/chip/record_trace.py --out <dir> --name dp4_tiny
"""
import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


import bench  # noqa: E402


def tiny_cell(chips: int) -> bench.Cell:
    with open(os.path.join(HERE, "configs", "smollm-360m.json")) as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 2
    with open(os.path.join(HERE, "mixes", "dp4-b4s1024.json")) as f:
        mix = json.load(f)
    mix.update(mesh={"data": chips, "model": 1}, batch_per_chip=1,
               seq_len=128, pool_batches=4)
    family = bench._load_module(
        os.path.join(HERE, "families", "dense_lm.py"), "family_dense_lm")
    return bench.Cell("tiny", chips, cfg, mix, family, {}, [], [])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--name", default="dp4_tiny")
    args = ap.parse_args(argv)
    devices = bench.require_devices(1)
    cell = tiny_cell(len(devices))
    prog = bench.build_program(cell, devices)
    params, opt, pool, _, done = bench.set_up(prog, cell, 5)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        params, opt, _, _ = bench.traced_window(prog, params, opt, pool,
                                                done, tmp, steps=2)
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        os.makedirs(args.out, exist_ok=True)
        with open(path, "rb") as src, gzip.open(os.path.join(
                args.out, args.name + ".xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
        with gzip.open(os.path.join(args.out, args.name + ".hlo.txt.gz"),
                       "wt") as dst:
            dst.write(prog.hlo_text(params, opt, pool[0]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"devices": len(devices),
                      "kind": devices[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
