"""Training loop: data pipeline + train step + checkpointing + metrics."""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro import telemetry
from repro.checkpoint import restore, save
from repro.core import AggregatorConfig
from repro.models import ModelApi
from repro.optim import Optimizer
from .step import TrainStepConfig, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0            # 0 = no checkpointing
    ckpt_dir: str = "checkpoints"
    step: TrainStepConfig = dataclasses.field(default_factory=TrainStepConfig)


class Trainer:
    def __init__(self, model: ModelApi, optimizer: Optimizer, mesh,
                 data_iter_fn: Callable[[int], dict],
                 cfg: TrainerConfig):
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.data_iter_fn = data_iter_fn
        self.cfg = cfg
        example = data_iter_fn(0)
        self.step_fn, self.shardings = make_train_step(
            model, optimizer, mesh, cfg.step, example)

    def init_state(self, seed: int = 0):
        params = self.model.init(jax.random.PRNGKey(seed))
        opt_state = self.optimizer.init(params)
        return params, opt_state

    def run(self, params=None, opt_state=None, start_step: int = 0):
        """Train from ``start_step`` to ``cfg.steps``; returns (params,
        opt_state, history).  Each step runs inside a
        ``jax.profiler.StepTraceAnnotation("train", step_num=step)``.

        The rate clock starts once this call's first step has completed,
        so its compilation is not counted: ``tokens_per_s`` in a log
        entry covers the steps after the first, and the entry has none
        until such a step has run.  Steps are dispatched without waiting;
        a log step's ``float()`` of its metrics is the only other sync."""
        if params is None:
            params, opt_state = self.init_state()
        history = []
        t0 = None
        tokens_seen = 0
        for step in range(start_step, self.cfg.steps):
            batch = self.data_iter_fn(step)
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                params, opt_state, metrics = self.step_fn(params,
                                                          opt_state, batch)
            if t0 is None:
                jax.block_until_ready(metrics)
                t0 = time.perf_counter()
            elif "tokens" in batch:
                tokens_seen += int(np.prod(batch["tokens"].shape))
            if (step + 1) % self.cfg.log_every == 0 or \
                    step == self.cfg.steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                if telemetry.enabled():
                    telemetry.metrics.record_moe_step(m)
                m["step"] = step + 1
                if step > start_step:
                    dt = time.perf_counter() - t0
                    m["tokens_per_s"] = tokens_seen / max(dt, 1e-9)
                history.append(m)
                print(f"step {step + 1:5d} "
                      + " ".join(f"{k}={v:.4g}" for k, v in m.items()
                                 if k != "step"), flush=True)
            if self.cfg.ckpt_every and (step + 1) % self.cfg.ckpt_every == 0:
                save(self.cfg.ckpt_dir, step + 1,
                     {"params": params, "opt": opt_state})
        return params, opt_state, history
