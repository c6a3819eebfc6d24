"""Decode correctness: prefill + step-by-step decode must reproduce the
full-forward logits at every generated position, for every arch family.
The MoE layer drops no token, so MoE archs are held to the same
equivalence, computed in float32: greedy top-k routing is discontinuous,
and the absorbed-latent decode rounds differently from the expanded
forward, so in bfloat16 a near tie between two experts can flip (seen:
probabilities 0.2715 and 0.2695 trading places) and move the logits by a
whole expert's output."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_spec, list_archs
from repro.data.synthetic import extra_inputs
from repro.models import build_model, encdec, hybrid, ssm_lm, transformer


@pytest.mark.parametrize("arch", list_archs())
def test_decode_matches_forward(arch):
    spec = get_spec(arch).reduced()
    if spec.num_experts:
        spec = dataclasses.replace(spec, dtype="float32")
    model = build_model(spec)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, S_PROMPT, S_TOTAL = 2, 8, 12
    tokens = jax.random.randint(key, (B, S_TOTAL), 0, spec.vocab_size)
    batch = {"tokens": tokens[:, :S_PROMPT], **extra_inputs(spec, B)}
    n_img = spec.num_image_tokens if spec.family == "vlm" else 0

    _, cache = model.prefill(params, batch, S_TOTAL + n_img)
    logits_d = None
    for t in range(S_PROMPT, S_TOTAL):
        logits_d, cache = model.decode_step(params, cache,
                                            tokens[:, t:t + 1])

    if spec.family in ("dense", "moe", "vlm"):
        full, _, _ = transformer.forward(params, tokens, spec,
                                         patches=batch.get("patches"))
    elif spec.family == "hybrid":
        full, _ = hybrid.forward(params, tokens, spec)
    elif spec.family == "ssm":
        full, _ = ssm_lm.forward(params, tokens, spec)
    else:
        enc = encdec.encode(params, batch["frames"], spec)
        full, _, _ = encdec.decoder_forward(params, tokens, enc, spec)

    want = np.asarray(full[:, -1], np.float32)
    got = np.asarray(logits_d, np.float32)
    err = np.max(np.abs(want - got)) / (np.max(np.abs(want)) + 1e-9)
    assert err < 0.05, f"{arch}: rel err {err}"


def test_sliding_window_decode_ring_buffer():
    """SWA ring-buffer decode == full forward with windowed mask."""
    spec = dataclasses.replace(get_spec("gemma-7b").reduced(),
                               sliding_window=8)
    model = build_model(spec)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, S = 1, 24
    tokens = jax.random.randint(key, (B, S), 0, spec.vocab_size)
    full, _, _ = transformer.forward(params, tokens, spec)
    _, cache = model.prefill(params, {"tokens": tokens[:, :16]}, 24)
    logits = None
    for t in range(16, S):
        logits, cache = model.decode_step(params, cache, tokens[:, t:t + 1])
    want = np.asarray(full[:, -1], np.float32)
    got = np.asarray(logits, np.float32)
    err = np.max(np.abs(want - got)) / (np.max(np.abs(want)) + 1e-9)
    assert err < 0.05, err
