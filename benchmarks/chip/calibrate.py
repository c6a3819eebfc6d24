#!/usr/bin/env python3
"""Readings the correctness limits of a cell are set from, in one
process on the cell's chips: the program on many seeds (the lower
reading of each number is the largest of these), the control on a few
(the upper reading is the smallest), and each planted fault on a few.

    python3 benchmarks/chip/calibrate.py <workload> --seeds 12 \
        --control-seeds 4 --faults half_batch,no_exchange --out <file>

Each program run goes through the same set-up, step object and
comparison as a benchmark run; the reference runs once per seed.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import jax  # noqa: E402

import bench  # noqa: E402
import reference  # noqa: E402

# Seeds of the calibration: large, as the driver's are, and none of them
# a seed a benchmark run is given by hand.
SEED0 = 3_000_000_007


def program_readings(prog, cell, seed):
    params, opt, pool, readings, _ = bench.set_up(prog, cell, seed)
    host_pool = [jax.device_get(b) for b in pool[:cell.mix[
        "checked_steps"]]]
    del params, opt, pool
    return readings, host_pool


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = bench.load_cell(args.workload)
    devices = bench.require_devices(cell.chips)
    bench.configure_cache()
    prog = bench.build_program(cell, devices)
    control = reference.control_program(cell, devices)
    faults = [f for f in args.faults.split(",") if f]
    broken = {f: bench.build_program(cell, devices, f) for f in faults}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rows = []
    with open(args.out, "w") as out:
        for i in range(args.seeds):
            seed = SEED0 + 7919 * i
            t0 = time.perf_counter()
            got, host_pool = program_readings(prog, cell, seed)
            ref = reference.readings(cell, seed, devices[0], host_pool)
            runs = [("program", got)]
            if i < args.control_seeds:
                runs.append(("control",
                             program_readings(control, cell, seed)[0]))
            if i < args.fault_seeds:
                runs += [(f, program_readings(p, cell, seed)[0])
                         for f, p in broken.items()]
            for who, r in runs:
                checks = bench.compare(r, ref, {})
                row = {"seed": seed, "who": who,
                       **{k: c["value"] for k, c in checks.items()},
                       "losses": r.losses.tolist(),
                       "ref_losses": ref["losses"].tolist()}
                rows.append(row)
                out.write(json.dumps(row) + "\n")
                out.flush()
                print(json.dumps(row), flush=True)
            print(f"seed {i}: {time.perf_counter() - t0:.1f} s",
                  flush=True)
    summary = {}
    for who in sorted({r["who"] for r in rows}):
        mine = [r for r in rows if r["who"] == who]
        summary[who] = {k: {"min": min(r[k] for r in mine),
                            "max": max(r[k] for r in mine)}
                        for k in rows[0] if k.endswith("_gap")}
    print(json.dumps({"workload": cell.name, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
