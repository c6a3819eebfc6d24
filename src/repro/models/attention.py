"""Attention layers: GQA/MQA/MHA, sliding-window, MLA, KV caches.

Three execution modes share the same parameters:
  * full-sequence (training / prefill) — on a TPU, causal self-attention
    runs the Pallas flash kernel (``repro.kernels.flash_attention``:
    bf16 MXU operands, causal block skipping, its own backward). Elsewhere,
    and for shapes the kernel does not take, plain masked attention for
    short sequences and chunked online-softmax (flash-style, `lax.scan`
    over query chunks) for long ones.
  * decode — one query token against a KV cache.

Caches are dicts of stacked-over-layers arrays so the layer stack can
`lax.scan` over them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import flash_attention
from ..kernels.backend import on_tpu
from . import common
from .common import ModelSpec, apply_rope, dense_init, norm_params, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def gqa_params(key, spec: ModelSpec):
    d, h, kv, hd = spec.d_model, spec.num_heads, spec.num_kv_heads, \
        spec.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * hd)),
        "wk": dense_init(ks[1], (d, kv * hd)),
        "wv": dense_init(ks[2], (d, kv * hd)),
        "wo": dense_init(ks[3], (h * hd, d)),
    }


def mla_params(key, spec: ModelSpec):
    d, h = spec.d_model, spec.num_heads
    r, rd, nd, vd = spec.kv_lora_rank, spec.qk_rope_dim, spec.qk_nope_dim, \
        spec.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], (d, h * (nd + rd))),
        "wdkv": dense_init(ks[1], (d, r + rd)),       # latent + shared rope key
        "kv_norm": norm_params(r, "rmsnorm"),         # on the latent
        "wuk": dense_init(ks[2], (r, h * nd)),
        "wuv": dense_init(ks[3], (r, h * vd)),
        "wo": dense_init(ks[4], (h * vd, d)),
    }


# ---------------------------------------------------------------------------
# masked softmax-attention cores
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, window: int):
    """(Sq, Sk) additive mask: causal, optionally sliding-window."""
    m = q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(m, 0.0, NEG_INF)


def sdpa_full(q, k, v, q_pos, k_pos, window: int = 0, scale=None):
    """Plain attention. q, k (B,S,H|KV,dh); v (B,Sk,KV,dv). fp32 softmax;
    scores scaled by ``scale`` (default 1/sqrt(dh))."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(dh) if scale is None else scores * scale
    scores = scores + _mask_bias(q_pos, k_pos, window)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _chunk(x, n, c, axis=1):
    shape = x.shape[:axis] + (n, c) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, q_pos, k_pos, window: int, chunk: int, scale: float):
    out, _ = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, chunk, scale)
    return out


def _flash_fwd_impl(q, k, v, q_pos, k_pos, window, chunk, scale):
    """FlashAttention-2 forward: online softmax over kv chunks inside a
    scan over q chunks. q,k (B,S,H,dh), v (B,S,H,dv) (kv already
    head-repeated); fp32 accumulation. Returns (out, lse)."""
    b, sq, h, dh = q.shape
    dv = v.shape[-1]
    sk = k.shape[1]
    nq, nk = sq // chunk, sk // chunk
    qs = _chunk(q, nq, chunk)                        # (nq,B,C,H,dh)
    qps = q_pos.reshape(nq, chunk)
    ks = _chunk(k, nk, chunk)
    vs = _chunk(v, nk, chunk)
    kps = k_pos.reshape(nk, chunk)

    def q_step(_, qc):
        qb, qp = qc

        def k_step(carry, kc_):
            acc, m, l = carry
            kb, vb, kp = kc_
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
            s = s * scale + _mask_bias(qp, kp, window)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, h, chunk, dv), jnp.float32)
        m0 = jnp.full((b, h, chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(k_step, (acc0, m0, l0), (ks, vs, kps))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).astype(q.dtype)
        lse = m + jnp.log(l_safe)
        return None, (out.transpose(0, 2, 1, 3), lse)  # (B,C,H,dh),(B,H,C)

    _, (outs, lses) = jax.lax.scan(q_step, None, (qs, qps))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, dv)
    lse = jnp.moveaxis(lses, 0, 2).reshape(b, h, sq)
    return out, lse


def _flash_fwd(q, k, v, q_pos, k_pos, window, chunk, scale):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, chunk, scale)
    return out, (q, k, v, q_pos, k_pos, out, lse)


def _flash_bwd(window, chunk, scale, res, dout):
    """FlashAttention-2 backward: recompute p per block from saved lse."""
    q, k, v, q_pos, k_pos, out, lse = res
    b, sq, h, dh = q.shape
    v_dim = v.shape[-1]
    sk = k.shape[1]
    nq, nk = sq // chunk, sk // chunk
    delta = jnp.einsum("bshd,bshd->bhs",
                       dout.astype(jnp.float32), out.astype(jnp.float32))

    qs = _chunk(q, nq, chunk)
    qps = q_pos.reshape(nq, chunk)
    dos = _chunk(dout, nq, chunk)
    lses = jnp.moveaxis(lse.reshape(b, h, nq, chunk), 2, 0)
    deltas = jnp.moveaxis(delta.reshape(b, h, nq, chunk), 2, 0)
    ks = _chunk(k, nk, chunk)
    vs = _chunk(v, nk, chunk)
    kps = k_pos.reshape(nk, chunk)

    # pass 1: dq — scan q chunks, inner scan over kv chunks
    def dq_qstep(_, xs):
        qb, qp, dob, lse_b, del_b = xs

        def kstep(dq_acc, kc_):
            kb, vb, kp = kc_
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
            s = s * scale + _mask_bias(qp, kp, window)
            p = jnp.exp(s - lse_b[..., None])
            dp = jnp.einsum("bqhd,bkhd->bhqk", dob.astype(jnp.float32),
                            vb.astype(jnp.float32))
            ds = p * (dp - del_b[..., None]) * scale
            dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                         kb.astype(jnp.float32))
            return dq_acc, None

        dq0 = jnp.zeros((b, chunk, h, dh), jnp.float32)
        dqc, _ = jax.lax.scan(kstep, dq0, (ks, vs, kps))
        return None, dqc

    _, dqs = jax.lax.scan(dq_qstep, None, (qs, qps, dos, lses, deltas))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(b, sq, h, dh).astype(q.dtype)

    # pass 2: dk/dv — scan kv chunks, inner scan over q chunks
    def dkv_kstep(_, kc_):
        kb, vb, kp = kc_

        def qstep(carry, xs):
            dk_acc, dv_acc = carry
            qb, qp, dob, lse_b, del_b = xs
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
            s = s * scale + _mask_bias(qp, kp, window)
            p = jnp.exp(s - lse_b[..., None])
            dv_acc = dv_acc + jnp.einsum(
                "bhqk,bqhd->bkhd", p, dob.astype(jnp.float32))
            dp = jnp.einsum("bqhd,bkhd->bhqk", dob.astype(jnp.float32),
                            vb.astype(jnp.float32))
            ds = p * (dp - del_b[..., None]) * scale
            dk_acc = dk_acc + jnp.einsum("bhqk,bqhd->bkhd", ds,
                                         qb.astype(jnp.float32))
            return (dk_acc, dv_acc), None

        (dkc, dvc), _ = jax.lax.scan(
            qstep, (jnp.zeros((b, chunk, h, dh), jnp.float32),
                    jnp.zeros((b, chunk, h, v_dim), jnp.float32)),
            (qs, qps, dos, lses, deltas))
        return None, (dkc, dvc)

    _, (dks, dvs) = jax.lax.scan(dkv_kstep, None, (ks, vs, kps))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, sk, h, dh).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, sk, h, v_dim).astype(v.dtype)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def sdpa_chunked(q, k, v, q_pos, k_pos, window: int, q_chunk: int,
                 scale=None):
    """Memory-efficient (flash) attention: custom-VJP online softmax,
    O(chunk²) score memory in both passes; the path for long sequences
    where the Pallas kernel is not taken."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    k = jnp.repeat(k, h // kvh, axis=2)   # grads sum back over rep groups
    v = jnp.repeat(v, h // kvh, axis=2)
    sk = k.shape[1]
    pad_q = (-sq) % q_chunk
    pad_k = (-sk) % q_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.concatenate(
            [q_pos, jnp.full((pad_q,), -1, q_pos.dtype)])
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.concatenate(
            [k_pos, jnp.full((pad_k,), 2 ** 30, k_pos.dtype)])
    scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
    out = _flash(q, k, v, q_pos, k_pos, window, q_chunk, float(scale))
    return out[:, :sq]


def _unpartitioned() -> bool:
    """Whether the computation being traced runs whole on each device,
    which a Pallas kernel needs (the compiler cannot partition it):
    inside a ``shard_map`` whose automatic axes all have size 1, or
    outside any mesh on a one-device backend."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    return all(n == 1 or a in mesh.manual_axes
               for a, n in mesh.shape.items())


def sdpa(q, k, v, q_pos, k_pos, spec: ModelSpec, window: int = 0,
         scale=None):
    """Causal attention core; v may have its own head size, and the
    scores are scaled by ``scale`` (default 1/sqrt of q's head size). On
    a TPU, self-attention over one shared positions array (callers pass
    positions 0..S-1) runs the Pallas flash kernel where its block sizes
    divide S; otherwise plain attention up to ``spec.attn_full_seq_max``
    positions, the chunked path beyond."""
    if on_tpu() and q_pos is k_pos and _unpartitioned() and \
            flash_attention.block_sizes(q.shape[1], q.shape[3]):
        return flash_attention.flash_attention(q, k, v, window=window,
                                               scale=scale)
    if q.shape[1] <= spec.attn_full_seq_max and \
            k.shape[1] <= spec.attn_full_seq_max:
        return sdpa_full(q, k, v, q_pos, k_pos, window, scale)
    return sdpa_chunked(q, k, v, q_pos, k_pos, window, spec.attn_chunk,
                        scale)


# ---------------------------------------------------------------------------
# GQA layer (covers MHA / MQA by kv-head count); optional sliding window
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def gqa_forward(params, x, positions, spec: ModelSpec,
                rope: bool = True):
    """Full-sequence GQA. x (B,S,d). Returns (out, kv) with kv for cache
    seeding at prefill."""
    b, s, d = x.shape
    h, kv, hd = spec.num_heads, spec.num_kv_heads, spec.resolved_head_dim
    cd = spec.compute_dtype
    q = (x @ params["wq"].astype(cd)).reshape(b, s, h, hd)
    k = (x @ params["wk"].astype(cd)).reshape(b, s, kv, hd)
    v = (x @ params["wv"].astype(cd)).reshape(b, s, kv, hd)
    if rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    pos = positions[0]
    with jax.named_scope("sdpa"):
        out = sdpa(q, k, v, pos, pos, spec, window=spec.sliding_window)
    out = out.reshape(b, s, h * hd) @ params["wo"].astype(cd)
    return out, (k, v)


def gqa_decode(params, x, cache_k, cache_v, pos, spec: ModelSpec,
               rope: bool = True):
    """One-token decode. x (B,1,d); cache_k/v (B,S,KV,dh) ring/linear
    buffer; pos scalar int32 (current position). Returns out, (new_k, new_v)."""
    b, _, d = x.shape
    h, kvh, hd = spec.num_heads, spec.num_kv_heads, spec.resolved_head_dim
    cd = spec.compute_dtype
    smax = cache_k.shape[1]
    q = (x @ params["wq"].astype(cd)).reshape(b, 1, h, hd)
    k = (x @ params["wk"].astype(cd)).reshape(b, 1, kvh, hd)
    v = (x @ params["wv"].astype(cd)).reshape(b, 1, kvh, hd)
    pos_arr = jnp.full((b, 1), pos, jnp.int32)
    if rope:
        q = apply_rope(q, pos_arr, spec.rope_theta)
        k = apply_rope(k, pos_arr, spec.rope_theta)
    window = spec.sliding_window
    slot = pos % smax if window else jnp.minimum(pos, smax - 1)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
    # Grouped-query attention WITHOUT materializing the head repeat: a
    # repeated cache forces GSPMD to re-shard + all-gather the whole KV
    # cache every layer (measured 40 GiB/step on granite-3-2b decode;
    # EXPERIMENTS.md §Perf it.0b). Group dim stays implicit instead.
    rep = h // kvh
    qg = q.reshape(b, 1, kvh, rep, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, cache_k) \
        .astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    idx = jnp.arange(smax)
    if window:
        valid = (idx[None, :] <= slot) | (pos >= smax)   # ring buffer full
    else:
        valid = idx[None, :] <= pos
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, cache_v)
    out = out.reshape(b, 1, h * hd) @ params["wo"].astype(cd)
    return out, (cache_k, cache_v)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2); latent KV cache
# ---------------------------------------------------------------------------

def mla_scale(spec: ModelSpec) -> float:
    """Softmax scale of MLA's scores: 1/sqrt(qk_nope + qk_rope), times
    YaRN's mscale(factor, mscale_all_dim) squared where it is set."""
    scale = 1.0 / np.sqrt(spec.qk_nope_dim + spec.qk_rope_dim)
    y = spec.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= common.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return float(scale)


def _mla_latent(params, x, positions, spec: ModelSpec):
    """(q_nope, q_rope, normed latent c_kv, k_rope) of x (B, S, d); RoPE
    (YaRN where the spec scales it) on the rope dims."""
    b, s, _ = x.shape
    h, r, rd, nd = spec.num_heads, spec.kv_lora_rank, spec.qk_rope_dim, \
        spec.qk_nope_dim
    cd = spec.compute_dtype
    q = (x @ params["wq"].astype(cd)).reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, spec.rope_theta,
                        spec.rope_scaling)
    dkv = x @ params["wdkv"].astype(cd)                  # (B,S,r+rd)
    with jax.named_scope("kv_norm"):
        c_kv = rmsnorm(dkv[..., :r], params["kv_norm"]["scale"])
    k_rope = apply_rope(dkv[..., None, r:], positions, spec.rope_theta,
                        spec.rope_scaling)[:, :, 0, :]   # shared across heads
    return q_nope, q_rope, c_kv, k_rope


@jax.named_scope("attention")
def mla_forward(params, x, positions, spec: ModelSpec):
    """Full-sequence MLA (non-absorbed expansion): q = [q_nope, q_rope]
    and k = [k_nope, k_rope over every head], head size nope + rope, v of
    ``v_head_dim``, through ``sdpa`` (the flash kernel on a TPU).
    Returns (out, latents) with latents = (normed c_kv, k_rope) for cache
    seeding."""
    b, s, d = x.shape
    h, nd, vd = spec.num_heads, spec.qk_nope_dim, spec.v_head_dim
    cd = spec.compute_dtype
    q_nope, q_rope, c_kv, k_rope = _mla_latent(params, x, positions, spec)
    k_nope = (c_kv @ params["wuk"].astype(cd)).reshape(b, s, h, nd)
    v = (c_kv @ params["wuv"].astype(cd)).reshape(b, s, h, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, k_rope.shape[-1]))], axis=-1)
    pos = positions[0]
    with jax.named_scope("sdpa"):
        out = sdpa(q, k, v, pos, pos, spec, scale=mla_scale(spec))
    out = out.reshape(b, s, h * vd) @ params["wo"].astype(cd)
    return out, (c_kv, k_rope)


def mla_decode(params, x, cache_c, cache_kr, pos, spec: ModelSpec):
    """Absorbed-weight MLA decode: attention runs in the latent space so
    the cache stores only (normed c_kv, k_rope) — (r + rd) per token
    instead of 2*h*hd. This is DeepSeek-V2's inference-time memory
    optimization and the reason the arch can run `long_500k`."""
    b = x.shape[0]
    h = spec.num_heads
    r, nd, vd = spec.kv_lora_rank, spec.qk_nope_dim, spec.v_head_dim
    cd = spec.compute_dtype
    smax = cache_c.shape[1]
    pos_arr = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope, c_new, kr_new = _mla_latent(params, x, pos_arr, spec)
    slot = jnp.minimum(pos, smax - 1)
    cache_c = jax.lax.dynamic_update_slice_in_dim(cache_c, c_new, slot, 1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(cache_kr, kr_new, slot, 1)

    wuk = params["wuk"].astype(cd).reshape(r, h, nd)
    # Absorb k up-projection into the query: q' = q_nope @ wuk^T (per head)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, wuk)    # (B,1,H,r)
    sc = (jnp.einsum("bqhr,bkr->bhqk", q_lat, cache_c)
          + jnp.einsum("bqhd,bkd->bhqk", q_rope, cache_kr)
          ).astype(jnp.float32) * mla_scale(spec)
    valid = jnp.arange(smax)[None, :] <= pos
    sc = jnp.where(valid[None, None, :], sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1).astype(cache_c.dtype)
    out_lat = jnp.einsum("bhqk,bkr->bqhr", probs, cache_c)  # (B,1,H,r)
    wuv = params["wuv"].astype(cd).reshape(r, h, vd)
    out = jnp.einsum("bqhr,rhv->bqhv", out_lat, wuv)
    out = out.reshape(b, 1, h * vd) @ params["wo"].astype(cd)
    return out, (cache_c, cache_kr)
