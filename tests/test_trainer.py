"""``Trainer.run``: the rate it logs leaves out the first step, which
compiles, and each step is a step of the profiler's trace."""
import glob
import types

import jax
import numpy as np
import pytest

from repro.configs import get_spec
from repro.core.compat import make_mesh
from repro.data.synthetic import SyntheticText
from repro.models import build_model
from repro.optim import sgd
from repro.train import Trainer, TrainerConfig, TrainStepConfig
from repro.train import trainer as trainer_mod


def _trainer(steps: int, log_every: int):
    spec = get_spec("smollm-360m").reduced()
    model = build_model(spec)
    data = SyntheticText(spec.vocab_size, batch=2, seq_len=8)
    mesh = make_mesh((1,), ("data",))
    cfg = TrainerConfig(steps=steps, log_every=log_every,
                        step=TrainStepConfig(dp_axes=("data",)))
    return Trainer(model, sgd(1e-2), mesh, data.batch_at, cfg), 2 * 8


def test_tokens_per_s_excludes_the_first_step(monkeypatch):
    """On a clock that only the steps move: the first step takes 100 s
    (its compilation), every later one 1 s.  The rate is one step's
    tokens per second at every log step, so the first step is in
    neither its tokens nor its time."""
    trainer, tokens = _trainer(steps=5, log_every=2)
    clock = [0.0]
    monkeypatch.setattr(trainer_mod, "time", types.SimpleNamespace(
        perf_counter=lambda: clock[0]))
    step, calls = trainer.step_fn, []

    def timed_step(*args):
        out = step(*args)
        clock[0] += 100.0 if not calls else 1.0
        calls.append(1)
        return out

    trainer.step_fn = timed_step
    _, _, history = trainer.run()
    assert [m["step"] for m in history] == [2, 4, 5]
    for m in history:
        assert m["tokens_per_s"] == pytest.approx(tokens)
        assert np.isfinite(m["loss"])


def test_steps_are_profiler_steps(tmp_path):
    """Each step runs inside ``StepTraceAnnotation("train")``: the
    profiler's host plane holds one ``train`` event per step; a run of
    one step logs no rate."""
    trainer, _ = _trainer(steps=3, log_every=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    (host,) = [p for p in jax.profiler.ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    steps = [e for line in host.lines for e in line.events
             if e.name == "train"]
    assert len(steps) == 3
    one, _ = _trainer(steps=1, log_every=1)
    _, _, history = one.run()
    assert "tokens_per_s" not in history[0]
