"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 200 --batch 8 --seq 128 --strategy rhd_rsa

The mesh defaults to (data=<devices present>, model=1).  ``--host-devices
N`` runs on N forced CPU host devices instead (e.g. ``--host-devices 8
--mesh 4x2``).  The model is the assigned architecture's REDUCED variant
by default (--full for the real config — only sensible on real hardware).
"""
import argparse
import sys

from repro.configs import get_spec
from repro.core import AggregatorConfig
from repro.data.synthetic import SyntheticText, extra_inputs
from repro.launch.mesh import dp_axes_of, mesh_from_arg, use_host_devices
from repro.launch.runtime import configure_compile_cache, device_line
from repro.models import build_model
from repro.optim import adamw, cosine_warmup, sgd
from repro.train import Trainer, TrainerConfig, TrainStepConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="",
                    help="DxM or PxDxM, e.g. 4x2 or 2x2x2 (default: "
                         "data over every device, model=1)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="run on this many forced CPU host devices")
    ap.add_argument("--strategy", default="rhd_rsa")
    ap.add_argument("--fusion-mb", type=float, default=4.0)
    ap.add_argument("--no-fuse", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", choices=("adamw", "sgd"),
                    default="adamw")
    ap.add_argument("--full", action="store_true",
                    help="full (not reduced) architecture")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--log-every", type=int, default=10)
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
    print(f"arch={spec.name} family={spec.family} "
          f"mesh={dict(mesh.shape)} "
          f"strategy={args.strategy}")

    data = SyntheticText(spec.vocab_size, batch=args.batch,
                         seq_len=args.seq, seed=args.seed)
    extras = extra_inputs(spec, args.batch)

    def batch_fn(step):
        return {**data.batch_at(step), **extras}

    lr = cosine_warmup(args.lr, max(args.steps // 20, 1), args.steps)
    opt = adamw(lr) if args.optimizer == "adamw" else sgd(lr)
    cfg = TrainerConfig(
        steps=args.steps, log_every=args.log_every,
        ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        step=TrainStepConfig(
            aggregator=AggregatorConfig(
                strategy=args.strategy,
                fusion_threshold_mb=args.fusion_mb,
                fuse=not args.no_fuse),
            dp_axes=dp_axes_of(mesh)))
    trainer = Trainer(model, opt, mesh, batch_fn, cfg)
    _, _, history = trainer.run()
    final = history[-1]["loss"] if history else float("nan")
    print(f"final loss: {final:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
