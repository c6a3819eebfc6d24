"""Decoder-only transformer LM assembly.

Covers the dense (gemma/granite/smollm/deepseek-7b), MoE (granite-moe,
deepseek-v2-lite incl. MLA) and VLM (phi-3-vision backbone) families.
Layer parameters are stacked along a leading layer dim and the stack runs
under ``lax.scan`` — essential to keep the HLO small enough that 40-layer
models lower quickly for the 512-device dry-run.

Heterogeneous stacks (DeepSeek-V2's leading dense layers before the MoE
stack) are split into an unrolled dense prefix + a scanned uniform body.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import attention, common, moe as moe_lib
from .common import ModelSpec, cross_entropy, embed_init, norm, norm_params
from .mlp import mlp_forward, mlp_params


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def _layer_params(key, spec: ModelSpec, is_moe: bool, dense_ff: int = 0):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": norm_params(spec.d_model, spec.norm_type),
        "ln2": norm_params(spec.d_model, spec.norm_type),
    }
    if spec.attention_type == "mla":
        p["attn"] = attention.mla_params(k1, spec)
    else:
        p["attn"] = attention.gqa_params(k1, spec)
    if is_moe:
        p["moe"] = moe_lib.moe_params(k2, spec)
    else:
        p["mlp"] = mlp_params(k3, spec.d_model, dense_ff or spec.d_ff,
                              spec.mlp_type)
    return p


def init_params(key, spec: ModelSpec):
    keys = jax.random.split(key, 4)
    n_dense_prefix = spec.first_dense_layers if spec.num_experts else 0
    n_body = spec.num_layers - n_dense_prefix
    body_is_moe = spec.num_experts > 0

    body_keys = jax.random.split(keys[0], n_body)
    body = jax.vmap(lambda k: _layer_params(k, spec, body_is_moe))(body_keys)

    params = {
        "embed": embed_init(keys[1], (spec.padded_vocab, spec.d_model)),
        "body": body,
        "ln_f": norm_params(spec.d_model, spec.norm_type),
    }
    if n_dense_prefix:
        pk = jax.random.split(keys[2], n_dense_prefix)
        params["prefix"] = jax.vmap(
            lambda k: _layer_params(k, spec, False,
                                    dense_ff=spec.dense_d_ff or spec.d_ff)
        )(pk)
    if not spec.tie_embeddings:
        params["lm_head"] = embed_init(keys[3],
                                       (spec.d_model, spec.padded_vocab))
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _seq_shard(x, spec: ModelSpec):
    if not spec.seq_parallel:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(None, "model", None))


_NO_MOE = {"aux": 0.0, "held_pairs": 0.0, "load_ratio": 0.0}


def _merge_moe(acc, stats):
    """Per-step MoE counters over layers: aux loss and held pairs summed,
    the load ratio the largest of any layer."""
    return {"aux": acc["aux"] + stats["aux"],
            "held_pairs": acc["held_pairs"] + stats["held_pairs"],
            "load_ratio": jnp.maximum(acc["load_ratio"],
                                      stats["load_ratio"])}


def _block_forward(lp, h, positions, spec: ModelSpec, is_moe: bool):
    """One pre-norm block, full sequence. Returns (h, kv, moe stats)."""
    h = _seq_shard(h, spec)
    a_in = norm(h, lp["ln1"], spec.norm_type)
    if spec.attention_type == "mla":
        a_out, kv = attention.mla_forward(lp["attn"], a_in, positions, spec)
    else:
        a_out, kv = attention.gqa_forward(lp["attn"], a_in, positions, spec)
    h = _seq_shard(h + a_out, spec)
    m_in = norm(h, lp["ln2"], spec.norm_type)
    if is_moe:
        m_out, stats = moe_lib.moe_forward(lp["moe"], m_in, spec)
    else:
        m_out = mlp_forward(lp["mlp"], m_in, spec.mlp_type)
        stats = {k: jnp.zeros((), jnp.float32) for k in _NO_MOE}
    return h + m_out, kv, stats


def _block_decode(lp, h, cache_layer, pos, spec: ModelSpec, is_moe: bool):
    a_in = norm(h, lp["ln1"], spec.norm_type)
    if spec.attention_type == "mla":
        a_out, new_cache = attention.mla_decode(
            lp["attn"], a_in, cache_layer["k"], cache_layer["v"], pos, spec)
    else:
        a_out, new_cache = attention.gqa_decode(
            lp["attn"], a_in, cache_layer["k"], cache_layer["v"], pos, spec)
    h = h + a_out
    m_in = norm(h, lp["ln2"], spec.norm_type)
    if is_moe:
        m_out, _ = moe_lib.moe_forward(lp["moe"], m_in, spec)
    else:
        m_out = mlp_forward(lp["mlp"], m_in, spec.mlp_type)
    return h + m_out, {"k": new_cache[0], "v": new_cache[1]}


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def embed_tokens(params, tokens, spec: ModelSpec, patches=None):
    cd = spec.compute_dtype
    h = params["embed"].astype(cd)[tokens]
    if spec.scale_embed:
        h = h * jnp.sqrt(jnp.asarray(spec.d_model, jnp.float32)).astype(cd)
    if patches is not None:
        # VLM: prepend stub image-patch embeddings (frontend carve-out).
        h = jnp.concatenate([patches.astype(cd), h], axis=1)
    return h


@jax.named_scope("head")
def lm_logits(params, h, spec: ModelSpec):
    cd = spec.compute_dtype
    if spec.tie_embeddings or "lm_head" not in params:
        return h @ params["embed"].astype(cd).T
    return h @ params["lm_head"].astype(cd)


# ---------------------------------------------------------------------------
# full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------

def forward(params, tokens, spec: ModelSpec, patches=None,
            collect_cache: bool = False):
    """Returns (logits, cache|None, moe stats). tokens (B,S)."""
    b = tokens.shape[0]
    h = embed_tokens(params, tokens, spec, patches=patches)
    s = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    caches = []
    stats = {k: jnp.asarray(v, jnp.float32) for k, v in _NO_MOE.items()}
    block = _block_forward
    if spec.remat:
        # recompute block activations in the backward pass: trades ~1.3x
        # block FLOPs for not streaming saved residuals through HBM
        # (EXPERIMENTS.md §Perf C1); the dense prefix layers too
        block = jax.checkpoint(_block_forward, static_argnums=(3, 4))

    if "prefix" in params:
        n_prefix = jax.tree_util.tree_leaves(params["prefix"])[0].shape[0]
        for i in range(n_prefix):
            lp = jax.tree_util.tree_map(lambda x: x[i], params["prefix"])
            h, kv, _ = block(lp, h, positions, spec, False)
            caches.append(kv)

    body_is_moe = spec.num_experts > 0

    def scan_body(carry, lp):
        h, acc = carry
        h, kv, st = block(lp, h, positions, spec, body_is_moe)
        out = kv if collect_cache else None
        return (h, _merge_moe(acc, st)), out

    (h, stats), body_kv = jax.lax.scan(scan_body, (h, stats),
                                       params["body"])

    h = norm(h, params["ln_f"], spec.norm_type)
    logits = lm_logits(params, h, spec)

    cache = None
    if collect_cache:
        cache = {"prefix": caches, "body": body_kv}
    return logits, cache, stats


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(params, batch, spec: ModelSpec):
    patches = batch.get("patches")
    logits, _, moe = forward(params, batch["tokens"], spec, patches=patches)
    if patches is not None:
        logits = logits[:, patches.shape[1]:]       # only text positions
    with jax.named_scope("head"):
        loss = cross_entropy(logits, batch["labels"], batch.get("mask"))
    aux = spec.router_aux_weight * moe["aux"]
    metrics = {"ce": loss, "aux": aux}
    if spec.num_experts:
        n_moe = spec.num_layers - spec.first_dense_layers
        pairs = batch["tokens"].size * spec.top_k * n_moe
        metrics.update(moe_held_pairs=moe["held_pairs"],
                       moe_held_share=moe["held_pairs"] / pairs,
                       moe_load_ratio=moe["load_ratio"])
    return loss + aux, metrics


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

def cache_len(spec: ModelSpec, seq: int) -> int:
    return min(seq, spec.sliding_window) if spec.sliding_window else seq


def init_cache(spec: ModelSpec, batch: int, seq: int):
    """Zeros cache (also used as ShapeDtypeStruct template in the dry-run)."""
    s = cache_len(spec, seq)
    cd = spec.compute_dtype
    n_prefix = spec.first_dense_layers if spec.num_experts else 0
    n_body = spec.num_layers - n_prefix
    if spec.attention_type == "mla":
        k_shape = (batch, s, spec.kv_lora_rank)
        v_shape = (batch, s, spec.qk_rope_dim)
    else:
        k_shape = (batch, s, spec.num_kv_heads, spec.resolved_head_dim)
        v_shape = k_shape
    body = {"k": jnp.zeros((n_body,) + k_shape, cd),
            "v": jnp.zeros((n_body,) + v_shape, cd)}
    cache = {"body": body, "pos": jnp.zeros((), jnp.int32)}
    if n_prefix:
        cache["prefix"] = {"k": jnp.zeros((n_prefix,) + k_shape, cd),
                           "v": jnp.zeros((n_prefix,) + v_shape, cd)}
    return cache


def prefill(params, tokens, spec: ModelSpec, patches=None, max_seq=None):
    """Run the prompt, build the cache, return last-position logits."""
    logits, kv, _ = forward(params, tokens, spec, patches=patches,
                            collect_cache=True)
    b, s = tokens.shape
    if patches is not None:
        s += patches.shape[1]
    max_seq = max_seq or s
    cache = init_cache(spec, b, max_seq)
    cl = cache_len(spec, max_seq)

    def seed(buf, kv_seq):
        # kv_seq: (B, S, ...); keep the trailing window if SWA
        take = kv_seq[:, -cl:] if kv_seq.shape[1] > cl else kv_seq
        return jax.lax.dynamic_update_slice_in_dim(
            buf, take.astype(buf.dtype), 0, axis=1)

    if spec.attention_type == "mla":
        body_k, body_v = kv["body"]
    else:
        body_k, body_v = kv["body"]
    cache["body"]["k"] = jax.vmap(seed)(cache["body"]["k"], body_k)
    cache["body"]["v"] = jax.vmap(seed)(cache["body"]["v"], body_v)
    if "prefix" in cache:
        for i, (pk, pv) in enumerate(kv["prefix"]):
            cache["prefix"]["k"] = cache["prefix"]["k"].at[i].set(
                seed(cache["prefix"]["k"][i], pk))
            cache["prefix"]["v"] = cache["prefix"]["v"].at[i].set(
                seed(cache["prefix"]["v"][i], pv))
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits[:, -1], cache


def decode_step(params, cache, tokens, spec: ModelSpec):
    """One decode step. tokens (B,1) int32. Returns (logits (B,V), cache)."""
    b = tokens.shape[0]
    pos = cache["pos"]
    h = embed_tokens(params, tokens, spec)

    if "prefix" in cache:
        n_prefix = cache["prefix"]["k"].shape[0]
        new_pk, new_pv = [], []
        for i in range(n_prefix):
            lp = jax.tree_util.tree_map(lambda x: x[i], params["prefix"])
            cl = {"k": cache["prefix"]["k"][i], "v": cache["prefix"]["v"][i]}
            h, nc = _block_decode(lp, h, cl, pos, spec, False)
            new_pk.append(nc["k"])
            new_pv.append(nc["v"])
        cache = dict(cache)
        cache["prefix"] = {"k": jnp.stack(new_pk), "v": jnp.stack(new_pv)}

    body_is_moe = spec.num_experts > 0

    def scan_body(h, xs):
        lp, cl = xs
        h, nc = _block_decode(lp, h, cl, pos, spec, body_is_moe)
        return h, nc

    h, new_body = jax.lax.scan(scan_body, h,
                               (params["body"], cache["body"]))
    h = norm(h, params["ln_f"], spec.norm_type)
    logits = lm_logits(params, h, spec)[:, 0]
    cache = dict(cache)
    cache["body"] = new_body
    cache["pos"] = pos + 1
    return logits, cache
