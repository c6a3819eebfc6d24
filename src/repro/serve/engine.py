"""Batched serving engine: prefill a batch of prompts, decode greedily.

Small but real: fixed-batch continuous decode with per-row stop handling,
the serving-side driver used by examples/serve_decode.py and the decode
dry-run shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.models import ModelApi
from .step import make_decode_step, make_prefill_step


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    max_seq: int = 256
    eos_id: int = -1              # -1 = never stop early
    greedy: bool = True
    temperature: float = 1.0


class ServeEngine:
    def __init__(self, model: ModelApi, params, mesh, dp_axes=(),
                 cfg: Optional[ServeConfig] = None):
        self.model = model
        self.params = params
        self.mesh = mesh
        self.dp_axes = tuple(dp_axes)
        self.cfg = cfg if cfg is not None else ServeConfig()
        self._prefill = None
        self._prefill_key = None
        self._decode = None
        self._decode_key = None

    @staticmethod
    def _batch_key(batch: dict):
        return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in batch.items()))

    def generate(self, batch: dict, rng=None) -> np.ndarray:
        """batch: {"tokens": (B, S_prompt)} (+frames for audio).
        Returns (B, max_new_tokens) int32 generations."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b = tokens.shape[0]
        prompt_len = int(tokens.shape[1])
        if prompt_len + cfg.max_new_tokens > cfg.max_seq:
            raise ValueError(
                f"prompt_len ({prompt_len}) + max_new_tokens "
                f"({cfg.max_new_tokens}) = "
                f"{prompt_len + cfg.max_new_tokens} exceeds "
                f"ServeConfig.max_seq ({cfg.max_seq}): the decode cache "
                f"is allocated at max_seq positions and token "
                f"{cfg.max_seq - prompt_len} would write past it.  "
                f"Raise max_seq, shorten the prompt, or lower "
                f"max_new_tokens.")

        tracer = telemetry.get_tracer()
        pkey = (self._batch_key(batch), cfg.max_seq)
        if self._prefill_key != pkey:
            self._prefill = make_prefill_step(
                self.model, self.mesh, self.dp_axes, batch, cfg.max_seq)
            self._prefill_key = pkey
        with tracer.span("serve.prefill", cat="wall", batch=int(b),
                         prompt_len=int(tokens.shape[1])):
            logits, cache = self._prefill(self.params, batch)

        key = (b, cfg.max_seq)
        if self._decode_key != key:
            self._decode = make_decode_step(self.model, self.mesh,
                                            self.dp_axes, b, cfg.max_seq)
            self._decode_key = key

        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # Split BEFORE the first sample: the prefill sample consumes a
        # subkey, never a key the loop will split again (key reuse would
        # correlate the first generated token with the second).
        rng, sub = jax.random.split(rng)
        out = []
        eos = jnp.int32(cfg.eos_id)
        finished = jnp.zeros((b,), bool) if cfg.eos_id >= 0 else None
        cur = self._sample(logits, sub)
        for t in range(cfg.max_new_tokens):
            if finished is not None:
                # rows that already emitted EOS keep emitting it
                cur = jnp.where(finished, eos, cur)
            out.append(np.asarray(cur))
            if finished is not None:
                finished = finished | (cur == eos)
                if bool(finished.all()):
                    # every row is done: pad the remaining positions
                    # without running the (shape-cached) decode step
                    pad = np.full((b,), cfg.eos_id, np.int32)
                    out.extend(pad for _ in
                               range(cfg.max_new_tokens - len(out)))
                    break
            with tracer.span("serve.decode", cat="wall", token=t):
                logits, cache = self._decode(self.params, cache,
                                             cur[:, None])
                rng, sub = jax.random.split(rng)
                cur = self._sample(logits, sub)
        return np.stack(out, axis=1)

    def _sample(self, logits, rng):
        if self.cfg.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / self.cfg.temperature, axis=-1).astype(jnp.int32)
