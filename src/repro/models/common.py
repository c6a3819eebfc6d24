"""Shared model substrate: spec dataclass, norms, embeddings, RoPE, init."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN RoPE scaling (DeepSeek-V2's ``rope_scaling`` of type yarn):
    frequencies blended between interpolated (÷ ``factor``) and original
    by a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position`` positions;
    ``mscale``/``mscale_all_dim`` scale cos/sin and the softmax."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Architecture hyper-parameters. One instance per config file."""
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio | cnn
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    mlp_type: str = "swiglu"       # swiglu | geglu | gelu
    norm_type: str = "rmsnorm"     # rmsnorm | layernorm
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = None
    tie_embeddings: bool = False
    scale_embed: bool = False      # gemma-style sqrt(d_model) embed scaling

    # attention
    attention_type: str = "gqa"    # gqa | mla
    sliding_window: int = 0        # >0 -> sliding-window attention
    attn_chunk: int = 1024         # q-chunk for online-softmax attention
    attn_full_seq_max: int = 2048  # off the Pallas kernel (CPU, or a
                                   # length its blocks do not divide):
                                   # seqs <= this use plain attention,
                                   # longer ones the chunked jnp path

    # MLA (DeepSeek-V2)
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0

    # MoE: the router scores all ``num_experts``; this chip holds the
    # routed experts [expert_first, expert_first + experts_held)
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0
    dense_d_ff: int = 0            # d_ff of the leading dense layers
    expert_first: int = 0
    experts_held: int = 0          # 0 -> all num_experts
    norm_topk_prob: bool = True    # renormalise the top-k weights
    router_aux: str = "switch"     # switch (global batch) | seq (DeepSeek)
    router_aux_weight: float = 0.01
    # The loss's gradient reaches the router through the top-k weights;
    # False stops it there, leaving the router to the aux loss.  For a
    # chip that holds some experts and exchanges no tokens: its held
    # experts alone would give that gradient, pulling tokens to them.
    gate_grad: bool = True

    # SSM (Mamba2)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    ssm_chunk: int = 256

    # hybrid (zamba2): shared attention block applied every `attn_every`
    attn_every: int = 0

    # xLSTM: every `slstm_every`-th block is sLSTM (rest mLSTM)
    slstm_every: int = 0
    mlstm_chunk: int = 0           # >0: chunkwise-parallel mLSTM (§Perf A1)
                                   # — materializes (C,n,m) only at chunk
                                   # boundaries instead of every timestep

    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0           # audio frames after conv frontend (stub)

    # VLM: image patch embeddings prepended (stub frontend)
    num_image_tokens: int = 0

    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "float32"
    remat: bool = False            # checkpoint blocks (recompute in bwd)
    seq_parallel: bool = False     # shard residual-stream seq dim over
                                   # 'model' between blocks (Megatron-SP
                                   # style; §Perf C3)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows: vocab rounded up to a multiple of 256 so
        the vocab dim shards evenly on the model axis (rows beyond
        vocab_size are never produced by the tokenizer; standard TPU
        practice, noted in DESIGN.md)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def reduced(self) -> "ModelSpec":
        """Smoke-test variant: same family/code path, tiny sizes
        (<=2 layers, d_model<=512, <=4 experts per the mandate)."""
        r = {
            "name": self.name + "-reduced",
            "num_layers": min(self.num_layers, 2),
            "d_model": min(self.d_model, 256),
            "num_heads": min(self.num_heads, 4),
            "num_kv_heads": min(self.num_kv_heads, 2),
            "d_ff": min(self.d_ff, 512) if self.d_ff else 0,
            "vocab_size": min(self.vocab_size, 512),
            "head_dim": 64 if self.head_dim else 0,
            "attn_full_seq_max": 64,
            "attn_chunk": 16,
            "ssm_chunk": 16,
        }
        if self.num_experts:
            r.update(num_experts=4, top_k=min(self.top_k, 2), moe_d_ff=64,
                     expert_first=0, experts_held=0,
                     first_dense_layers=min(self.first_dense_layers, 1),
                     dense_d_ff=min(self.dense_d_ff, 256) if self.dense_d_ff else 0)
        if self.kv_lora_rank:
            r.update(kv_lora_rank=32, qk_rope_dim=16, qk_nope_dim=32,
                     v_head_dim=32)
        if self.ssm_heads:
            r.update(ssm_heads=4, ssm_state=16, ssm_head_dim=32)
        if self.attn_every:
            r.update(attn_every=1, num_layers=2)
        if self.slstm_every:
            r.update(slstm_every=2, num_layers=2)
        if self.encoder_layers:
            r.update(encoder_layers=1, encoder_seq=32)
        if self.num_image_tokens:
            r.update(num_image_tokens=8)
        if self.sliding_window:
            r.update(sliding_window=32)
        return dataclasses.replace(self, **r)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """LeCun-normal over the input dimension."""
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias=None, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


@jax.named_scope("norm")
def norm(x, params, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params.get("bias"))


def norm_params(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations, dim, theta, max_pos):
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) \
        / (2 * math.log(theta))


def rope_frequencies(dim: int, theta: float,
                     scaling: Optional[YarnScaling] = None):
    """Inverse frequencies of the ``dim // 2`` rotated pairs; under YaRN
    (``DeepseekV2YarnRotaryEmbedding``) the pairs below the ``beta_fast``
    correction dim keep theirs, those above the ``beta_slow`` one are
    divided by ``factor``, and a linear ramp blends the two between."""
    extra = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    if scaling is None:
        return extra
    m = scaling.original_max_position
    low = max(math.floor(_yarn_correction_dim(scaling.beta_fast, dim, theta,
                                              m)), 0)
    high = min(math.ceil(_yarn_correction_dim(scaling.beta_slow, dim, theta,
                                              m)), dim - 1)
    ramp = (np.arange(dim // 2, dtype=np.float32) - low) / \
        max(high - low, 0.001)
    keep = 1.0 - np.clip(ramp, 0.0, 1.0)
    return (extra / scaling.factor * (1.0 - keep) + extra * keep) \
        .astype(np.float32)


def rope_mscale(scaling: Optional[YarnScaling]) -> float:
    """YaRN's factor on cos and sin (1 without scaling)."""
    if scaling is None:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale) / \
        yarn_mscale(scaling.factor, scaling.mscale_all_dim)


def apply_rope(x, positions, theta: float,
               scaling: Optional[YarnScaling] = None):
    """x: (..., seq, heads, head_dim); positions: (..., seq); rotates the
    two halves of each head."""
    dim = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(dim, theta, scaling))  # (dim/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dim/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    ms = rope_mscale(scaling)
    if ms != 1.0:
        cos, sin = cos * ms, sin * ms
    cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int):
    pos = np.arange(seq, dtype=np.float32)[:, None]
    i = np.arange(dim // 2, dtype=np.float32)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    return jnp.asarray(
        np.concatenate([np.sin(angle), np.cos(angle)], axis=-1))


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, mask=None):
    """Token-mean CE; logits (..., V) any dtype, stats in fp32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
