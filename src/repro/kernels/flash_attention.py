"""Pallas TPU kernel: causal flash attention, forward and FlashAttention-2
backward, tied together by a ``jax.custom_vjp``.

On a TPU backend ``models.attention.sdpa`` routes full-sequence causal
self-attention here (training, remat's recompute and serve prefill);
the CPU backend keeps the jnp paths in ``models.attention``, and tests
run this kernel in interpret mode.

Layout: q and k (B, H|KV, S, dh), v (B, KV, S, dv), H a multiple of KV
(v's head size may differ from q's and k's: MLA's 128 against 192);
each grid step holds one (block_q, dh) tile of q against one
(block_k, dh) tile of k and (block_k, dv) tile of v, a query head
reading its kv head ``h // (H // KV)`` through the ``index_map`` (no
repeated kv heads).  The scores' scale is set by the caller (default
1/sqrt(dh)) and folded into q before the kernels.

Precision: the MXU takes its operands in the inputs' dtype (bf16 in the
models) and accumulates in f32: q·kᵀ, p·v, dO·vᵀ, pᵀ·dO, dS·k, dSᵀ·q.
Scores, the running max and sum, lse, delta, dP and dS before their
cast, and every accumulator are f32.

Causal block skipping: a kv block wholly above the diagonal (or wholly
outside the sliding window) is neither computed (``pl.when``) nor
fetched (the ``index_map`` clamps a skipped step to the block already
resident, so no DMA is issued). A block that straddles the diagonal or
the window's edge runs whole under the iota mask, or (the backward's
passes) in tiles that skip what no query sees and mask only where they
straddle.

Block and tile sizes are a function of the sequence length and head
size (``block_sizes``), swept on a TPU v5e at head_dim 64 and at MLA's
192/128.

Grids: forward and dq pass (B, H, n_q, n_kv) with the kv axis innermost;
dk/dv pass (B, KV, n_kv, rep·n_q), the inner axis running over the
group's query heads and the q blocks, so dk and dv accumulate a kv
head's whole group in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG_INF = -1e30
LANES = 128
# (block_q, block_k, tile) of the forward, the dq pass and the dk/dv
# pass, by (sequence length, head_dim), else by sequence length (swept
# on a TPU v5e, at head_dim 64 and at MLA's 192 with v 128; PERF.md).
# Other shapes take the largest power-of-two multiple of 128 up to
# _DEFAULT that divides the length (larger blocks won at 2048 and 4096),
# the forward's straddling blocks whole and the backward's in tiles of
# _BWD_TILE (each won its pass).
_TABLE = {1024: ((512, 512, 512),) * 3,
          (8192, 192): ((2048, 1024, 1024), (2048, 1024, 512),
                        (1024, 1024, 512))}
_DEFAULT = 1024
_BWD_TILE = 512
# Scoped VMEM the kernels may use: (block_q, block_k) f32 temporaries of
# 1024 x 1024 are 4 MiB each.
_VMEM_LIMIT = 64 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b


def _fit(seq: int, cap: int):
    """The largest power-of-two multiple of 128, at most ``cap``, that
    divides ``seq``; None where 128 does not."""
    b = cap
    while b >= LANES:
        if seq % b == 0:
            return b
        b //= 2
    return None


def block_sizes(seq: int, head_dim: int):
    """((bq, bk, tile) of the forward, of the dq pass, of the dk/dv pass)
    for a sequence length and head size, or None where the kernel does
    not take the shape (``seq`` not a multiple of 128, ``head_dim`` not a
    multiple of 8)."""
    if head_dim % 8:
        return None
    for key in ((seq, head_dim), seq):
        if key in _TABLE:
            return _TABLE[key]
    b = _fit(seq, _DEFAULT)
    if b is None:
        return None
    return ((b, b, b),) + ((b, b, min(b, _BWD_TILE)),) * 2


# ---------------------------------------------------------------------------
# which blocks run
# ---------------------------------------------------------------------------

def _kv_range(qi, bq, bk, causal, window, n_kv):
    """First and last kv block that q block ``qi`` sees."""
    if not causal:
        return 0, n_kv - 1
    last = (qi * bq + bq - 1) // bk
    first = 0
    if window > 0:
        first = jnp.maximum(qi * bq - window + 1, 0) // bk
    return first, last


def _q_range(kj, bq, bk, causal, window, n_q):
    """First and last q block that sees kv block ``kj``."""
    if not causal:
        return 0, n_q - 1
    first = (kj * bk) // bq
    last = n_q - 1
    if window > 0:
        last = jnp.minimum((kj * bk + bk - 2 + window) // bq, n_q - 1)
    return first, last


def _visible(q0, k0, tq, tk, causal, window):
    """Whether some query of [q0, q0 + tq) sees some key of
    [k0, k0 + tk)."""
    if not causal:
        return True
    v = k0 <= q0 + tq - 1
    if window > 0:
        v = v & (q0 - (k0 + tk - 1) < window)
    return v


def _partial(q0, k0, tq, tk, causal, window):
    """Whether some (query, key) pair of the tile is masked out."""
    if not causal:
        return False
    m = k0 + tk - 1 > q0
    if window > 0:
        m = m | (q0 + tq - 1 - k0 >= window)
    return m


def _mask(q0, k0, shape, q_axis, causal, window):
    """Boolean keep-mask of a tile whose first query is ``q0`` and first
    key ``k0``; queries run along ``q_axis``."""
    q = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = q >= k if causal else jnp.ones(shape, jnp.bool_)
    if window > 0:
        keep &= (q - k) < window
    return keep


def _each_tile(q0, k0, bq, bk, tile, causal, window, update):
    """``update(r, c, tq, tk, masked)`` over the (query rows r.., key rows
    c..) tiles of a (bq, bk) block at (q0, k0) that some query sees: the
    whole block where nothing in it is masked, else tiles of at most
    ``tile`` a side, each masked only where it straddles the diagonal or
    the window's edge, so the masked corner of a block is skipped."""
    run = _visible(q0, k0, bq, bk, causal, window)
    part = _partial(q0, k0, bq, bk, causal, window)
    if part is False:
        pl.when(run)(lambda: update(0, 0, bq, bk, False))
        return
    pl.when(run & jnp.logical_not(part))(
        lambda: update(0, 0, bq, bk, False))
    tq, tk = min(bq, tile), min(bk, tile)
    for r in range(0, bq, tq):
        for c in range(0, bk, tk):
            vis = run & part & _visible(q0 + r, k0 + c, tq, tk, causal,
                                        window)
            edge = _partial(q0 + r, k0 + c, tq, tk, causal, window)
            pl.when(vis & jnp.logical_not(edge))(
                functools.partial(update, r, c, tq, tk, False))
            pl.when(vis & edge)(functools.partial(update, r, c, tq, tk,
                                                  True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                bq, bk, tile, causal, window, n_kv):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def update(r, c, tq, tk, masked):
        rows, keys = pl.ds(r, tq), pl.ds(c, tk)
        v = v_ref[keys, :]
        s = jax.lax.dot_general(q_ref[rows, :], k_ref[keys, :], _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(qi * bq + r, kj * bk + c, s.shape, 0, causal,
                                window), s, NEG_INF)
        m_prev = m_sc[rows, :]                              # (tq, 128)
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_sc[rows, :] = alpha * l_sc[rows, :] + p.sum(axis=1, keepdims=True)
        acc_sc[rows, :] = acc_sc[rows, :] * alpha[:, :1] + \
            jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                preferred_element_type=jnp.float32)
        m_sc[rows, :] = m_next

    _each_tile(qi * bq, kj * bk, bq, bk, tile, causal, window, update)

    @pl.when(kj == n_kv - 1)
    def _finish():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l)                        # (bq, 128)
        lse_ref[...] = lse.T[:1, :]                         # (1, bq)


def _fwd(q, k, v, causal, window, blocks, interpret):
    """(out (B,H,S,dv), lse (B,H,1,S) f32) of pre-scaled q."""
    b, h, s, dh = q.shape
    dv = v.shape[-1]
    kvh = k.shape[1]
    rep = h // kvh
    bq, bk, tile = blocks
    n_q, n_kv = s // bq, s // bk

    def kv_map(bi, hi, qi, kj):
        first, last = _kv_range(qi, bq, bk, causal, window, n_kv)
        return bi, hi // rep, jnp.clip(kj, first, last), 0

    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, tile=tile,
                               causal=causal, window=window, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, None, bq, dh),
                         lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, bk, dh), kv_map),
            pl.BlockSpec((None, None, bk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, dv),
                         lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, 1, bq),
                         lambda bi, hi, qi, kj: (bi, hi, 0, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),   # m
                        pltpu.VMEM((bq, LANES), jnp.float32),   # l
                        pltpu.VMEM((bq, dv), jnp.float32)],     # acc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dq pass, then dk/dv pass
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_sc, *, bq, bk, tile, causal, window, n_kv):
    qi, kj = pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def update(r, c, tq, tk, masked):
        rows, keys = pl.ds(r, tq), pl.ds(c, tk)
        k = k_ref[keys, :]
        s = jax.lax.dot_general(q_ref[rows, :], k, _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(qi * bq + r, kj * bk + c, s.shape, 0, causal,
                                window), s, NEG_INF)
        lse = jnp.expand_dims(lse_ref[0, rows], -1)          # (tq, 1)
        delta = jnp.expand_dims(delta_ref[0, rows], -1)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do_ref[rows, :], v_ref[keys, :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_sc[rows, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _each_tile(qi * bq, kj * bk, bq, bk, tile, causal, window, update)

    @pl.when(kj == n_kv - 1)
    def _finish():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_sc, dv_sc, *, bq, bk, tile, causal, window, n_q,
                rep):
    kj, t = pl.program_id(2), pl.program_id(3)
    qi = t % n_q

    @pl.when(t == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def update(r, c, tq, tk, masked):
        # transposed scores: keys along sublanes, queries along lanes,
        # so lse and delta broadcast as rows
        rows, keys = pl.ds(r, tq), pl.ds(c, tk)
        q, do = q_ref[rows, :], do_ref[rows, :]
        st = jax.lax.dot_general(k_ref[keys, :], q, _NT,
                                 preferred_element_type=jnp.float32)
        if masked:
            st = jnp.where(_mask(qi * bq + r, kj * bk + c, st.shape, 1,
                                 causal, window), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[:, rows])                 # (tk, tq)
        dv_sc[keys, :] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[keys, :], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[:, rows])
        dk_sc[keys, :] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _each_tile(qi * bq, kj * bk, bq, bk, tile, causal, window, update)

    @pl.when(t == rep * n_q - 1)
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


_BWD_PARAMS = dict(dimension_semantics=("parallel", "parallel", "parallel",
                                        "arbitrary"),
                   vmem_limit_bytes=_VMEM_LIMIT)


def _dq(q, k, v, do, lse, delta, causal, window, blocks, interpret):
    b, h, s, dh = q.shape
    dv = v.shape[-1]
    rep = h // k.shape[1]
    bq, bk, tile = blocks
    n_q, n_kv = s // bq, s // bk

    def kv_map(bi, hi, qi, kj):
        first, last = _kv_range(qi, bq, bk, causal, window, n_kv)
        return bi, hi // rep, jnp.clip(kj, first, last), 0

    def q_map(bi, hi, qi, kj):
        return bi, hi, qi, 0

    def row_map(bi, hi, qi, kj):
        return bi, hi, 0, qi

    return pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, tile=tile,
                          causal=causal, window=window, n_kv=n_kv),
        grid=(b, h, n_q, n_kv),
        in_specs=[pl.BlockSpec((None, None, bq, dh), q_map),
                  pl.BlockSpec((None, None, bk, dh), kv_map),
                  pl.BlockSpec((None, None, bk, dv), kv_map),
                  pl.BlockSpec((None, None, bq, dv), q_map),
                  pl.BlockSpec((None, None, 1, bq), row_map),
                  pl.BlockSpec((None, None, 1, bq), row_map)],
        out_specs=pl.BlockSpec((None, None, bq, dh), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**_BWD_PARAMS),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)


def _dkv(q, k, v, do, lse, delta, causal, window, blocks, interpret):
    b, h, s, dh = q.shape
    dv = v.shape[-1]
    kvh = k.shape[1]
    rep = h // kvh
    bq, bk, tile = blocks
    n_q, n_kv = s // bq, s // bk

    def q_head(gi, t, kj):
        first, last = _q_range(kj, bq, bk, causal, window, n_q)
        return gi * rep + t // n_q, jnp.clip(t % n_q, first, last)

    def q_map(bi, gi, kj, t):
        hi, qi = q_head(gi, t, kj)
        return bi, hi, qi, 0

    def row_map(bi, gi, kj, t):
        hi, qi = q_head(gi, t, kj)
        return bi, hi, 0, qi

    def kv_map(bi, gi, kj, t):
        return bi, gi, kj, 0

    return pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, tile=tile,
                          causal=causal, window=window, n_q=n_q, rep=rep),
        grid=(b, kvh, n_kv, rep * n_q),
        in_specs=[pl.BlockSpec((None, None, bq, dh), q_map),
                  pl.BlockSpec((None, None, bk, dh), kv_map),
                  pl.BlockSpec((None, None, bk, dv), kv_map),
                  pl.BlockSpec((None, None, bq, dv), q_map),
                  pl.BlockSpec((None, None, 1, bq), row_map),
                  pl.BlockSpec((None, None, 1, bq), row_map)],
        out_specs=[pl.BlockSpec((None, None, bk, dh), kv_map),
                   pl.BlockSpec((None, None, bk, dv), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(**_BWD_PARAMS),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)


def _bwd(q, k, v, o, lse, do, causal, window, blocks, interpret):
    """(dq, dk, dv) of pre-scaled q; every array in the kernel layout."""
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                 # (B,H,1,S)
    args = (q, k, v, do, lse, delta, causal, window)
    dq = _dq(*args, blocks[0], interpret)
    dk, dv = _dkv(*args, blocks[1], interpret)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# the differentiable kernel
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, blocks, interpret):
    return _fwd(q, k, v, causal, window, blocks[0], interpret)[0]


def _flash_fwd(q, k, v, causal, window, blocks, interpret):
    o, lse = _fwd(q, k, v, causal, window, blocks[0], interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, blocks, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal, window, blocks[1:], interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, blocks=None,
                    interpret: bool | None = None):
    """Softmax attention of q (B, S, H, dh) over k (B, S, KV, dh) and
    v (B, S, KV, dv), H a multiple of KV, positions 0..S-1 on both sides;
    differentiable.  Scores are scaled by ``scale`` (default 1/sqrt(dh)).
    ``blocks``, (bq, bk, tile) of each kernel, defaults to
    :func:`block_sizes`; ``interpret=None``
    interprets everywhere but on a TPU.  Returns (B, S, H, dv)."""
    b, s, h, dh = q.shape
    blocks = blocks or block_sizes(s, dh)
    if blocks is None or h % k.shape[2] or v.shape[-1] % 8 or any(
            s % bq or s % bk or bq % min(bq, t) or bk % min(bk, t)
            for bq, bk, t in blocks):
        raise ValueError(f"flash_attention takes no shape {q.shape} "
                         f"with kv {k.shape}, v {v.shape} and blocks "
                         f"{blocks}")
    scale = 1.0 / np.sqrt(dh) if scale is None else scale
    qt = (q.astype(jnp.float32) * scale).astype(q.dtype).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal, window, tuple(blocks),
                 resolve_interpret(interpret))
    return out.transpose(0, 2, 1, 3)
