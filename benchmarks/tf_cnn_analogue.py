"""tf_cnn_benchmarks analogue (paper Sec. IV): REAL distributed training
of ResNet-50 (reduced input size) on synthetic data across 8 host
devices, one run per gradient-aggregation design — warm-up then timed
iterations, exactly the paper's methodology ("after a number of warm-up
iterations, a set of ten iterations determines the image throughput").

Absolute images/sec are CPU-bound; the *ranking* (allreduce designs vs
PS gather) and the per-step collective structure are the reproduction.
Runs in a subprocess (device-count isolation).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import AggregatorConfig, GradientAggregator
from repro.core.compat import make_mesh, shard_map
from repro.models import cnn
from repro.data import SyntheticImages

IMG, BATCH = 32, 16     # global batch over 8 data shards
mesh = make_mesh((8,), ("data",))
spec = cnn.CnnSpec("resnet50", image_size=IMG)
params = cnn.mobilenet_params(jax.random.PRNGKey(0)) if False else \
    cnn.resnet50_params(jax.random.PRNGKey(0))
data = SyntheticImages(batch=BATCH, image_size=IMG)

out = {{}}
for strategy in ["psum", "ring_rsa", "rhd_rsa", "ps_gather"]:
    agg = GradientAggregator(AggregatorConfig(strategy=strategy), ("data",))

    def local_step(p, batch):
        loss, grads = jax.value_and_grad(
            lambda q: cnn.cnn_loss(cnn.resnet50_forward, q, batch,
                                   spec)[0])(p)
        grads = agg(grads)
        p = jax.tree_util.tree_map(lambda a, g: a - 0.05 * g, p, grads)
        return p, jax.lax.pmean(loss, "data")

    bspec = {{"images": P("data", None, None, None), "labels": P("data")}}
    step = jax.jit(shard_map(
        local_step, mesh, in_specs=(P(), bspec),
        out_specs=(P(), P()), axis_names={{"data"}}, check_vma=False))
    p = params
    b = data.batch_at(0)
    for i in range(2):                      # warm-up
        p, loss = step(p, data.batch_at(i))
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    iters = 5
    for i in range(iters):
        p, loss = step(p, data.batch_at(i + 2))
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / iters
    out[strategy] = {{"step_s": dt, "images_per_s": BATCH / dt,
                      "loss": float(loss)}}
print(json.dumps(out))
"""


def run(csv=True):
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"   # CPU host devices; never the chip
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET.format(src=src)],
        capture_output=True, text=True, timeout=1800, env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    lines = []
    for strategy, r in data.items():
        lines.append(f"tf_cnn_analogue.resnet50.{strategy},"
                     f"{r['step_s'] * 1e6:.0f},"
                     f"images_per_s={r['images_per_s']:.1f} "
                     f"loss={r['loss']:.3f} host-cpu 8dev")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
