"""Serving driver: batched greedy decode of synthetic prompts.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --batch 4 --prompt-len 16 --new-tokens 32

The mesh defaults to (data=<devices present>, model=1); ``--host-devices
N`` runs on N forced CPU host devices instead.
"""
import argparse
import sys
import time

import jax

from repro.configs import get_spec
from repro.data.synthetic import SyntheticText, extra_inputs
from repro.launch.mesh import dp_axes_of, mesh_from_arg, use_host_devices
from repro.launch.runtime import configure_compile_cache, device_line
from repro.models import build_model
from repro.serve import ServeEngine
from repro.serve.engine import ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--mesh", default="",
                    help="DxM or PxDxM (default: data over every "
                         "device, model=1)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="run on this many forced CPU host devices")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.host_devices:
        use_host_devices(args.host_devices)
    print(device_line(), flush=True)
    configure_compile_cache()
    mesh = mesh_from_arg(args.mesh)

    spec = get_spec(args.arch)
    if not args.full:
        spec = spec.reduced()
    model = build_model(spec)
    params = model.init(jax.random.PRNGKey(args.seed))

    data = SyntheticText(spec.vocab_size, batch=args.batch,
                         seq_len=args.prompt_len, seed=args.seed)
    batch = {"tokens": data.batch_at(0)["tokens"],
             **extra_inputs(spec, args.batch)}
    cfg = ServeConfig(max_new_tokens=args.new_tokens,
                      max_seq=args.prompt_len + args.new_tokens + 1)
    engine = ServeEngine(model, params, mesh, dp_axes_of(mesh), cfg)
    t0 = time.perf_counter()
    out = engine.generate(batch)
    dt = time.perf_counter() - t0
    total = out.shape[0] * out.shape[1]
    print(f"arch={spec.name} generated {out.shape} tokens "
          f"in {dt:.2f}s ({total / dt:.1f} tok/s incl. compile)")
    print("first row:", out[0][:16].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
