"""Pallas kernel: fused codec'd reduction hop — the paper's GDR-Opt.

The paper's "truly CUDA-Aware" allreduce wins 5-17x on small/medium
messages by fusing the per-hop work into a single device kernel instead
of staged eager ops.  Our unfused executor lowers each codec'd hop as
separate dequantize -> add -> requantize XLA ops: three HBM round trips
per hop over the same bytes.  This module is the TPU analogue of the
paper's fused kernel: one VMEM-tiled pass per side of the hop —

``hop_encode``      absmax (tiled max-of-partial-maxes) + quantize in
                    one kernel pass, producing the wire payload + scale
``hop_decode_add``  decode(received) * scale + local partial, fp32
                    internal, in one kernel pass (the accumulate is
                    FUSED into the decode — no separate add op)

The quantize/clamp arithmetic is a bit-for-bit twin of
``core/codec.py``'s :func:`~repro.core.codec.encode` /
:func:`~repro.core.codec.decode` (same safe-absmax substitution, same
subnormal ``tiny`` clamp, same clip/round grid), so a fused schedule
carries exactly the unfused schedule's derived tolerance — the SV009
contract.  The absmax is computed as a max of per-tile partial maxes,
which equals the global max exactly (max is exact in fp), so even the
scale scalar is bit-identical to the unfused encoder's.

Tiling: in compiled (TPU) mode the flat payload is tiled ``block_n``
lanes per grid step.  In interpret mode the grid loop runs at TRACE
time, so the block covers the whole (flat) array — one program
instance — keeping trace time O(1) in the buffer size.  ``interpret``
is auto-detected from the backend (see ``backend.resolve_interpret``)
so the same call site runs interpreted here and compiled on TPU.

Auto-detected non-TPU callers get one further lowering: the SAME
kernel bodies run directly on whole arrays through duck-typed refs
(``_HostRef``) with no ``pallas_call`` at all.  The Pallas
interpreter's pad/mask/slice emulation costs extra memory passes per
call — enough to erase the fused route's win on a 14-hop ring — while
the direct lowering leaves XLA free to fuse each hop into the minimal
op count.  Because it executes the identical kernel body on the
identical values, it is bit-exact with ``interpret=True`` (a
property pinned in tests/test_fused_hop.py); pass an explicit
``interpret=True`` to force the Pallas interpreter (kernel-body
validation through the real BlockSpec/grid plumbing).

This module deliberately does NOT import ``repro.core`` — the codec's
fused permuter imports us lazily, and a cycle would force eager kernel
imports on every core user.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import on_tpu, resolve_interpret

# Names/semantics mirror core/codec.py (kept import-free; see module
# docstring).  fp8 is gated on the running jax exactly like the codec.
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)

HOP_CODECS = ("none", "bf16", "int8", "fp8_e4m3")


def _check_name(name: str) -> None:
    if name not in HOP_CODECS:
        raise ValueError(f"unknown hop codec {name!r}; one of {HOP_CODECS}")


def _direct(interpret: bool | None) -> bool:
    """True when the auto-detected non-TPU path should run the kernel
    bodies directly (no pallas_call) — see the module docstring.  An
    explicit bool always goes through Pallas."""
    return interpret is None and not on_tpu()


class _HostRef:
    """Duck-typed stand-in for a Pallas ref: ``ref[...]`` reads the
    whole array, ``ref[...] = v`` stores it, ``ref[0]`` indexes (the
    scale scalar), ``.dtype`` is the declared output dtype.  Lets the
    direct lowering execute the UNMODIFIED kernel bodies eagerly."""

    def __init__(self, val=None, dtype=None):
        self.val = val
        self.dtype = dtype if dtype is not None else getattr(
            val, "dtype", None)

    def __getitem__(self, idx):
        if idx is Ellipsis:
            return self.val
        return self.val[idx]

    def __setitem__(self, idx, value):
        self.val = value


def _tile(x: jax.Array, block_n: int, interpret: bool):
    """Flatten to 1-D and pad to the block grid.

    Returns ``(flat_padded, n, grid, block)``.  Interpret mode uses one
    whole-array block (grid loops run at trace time there); compiled
    mode tiles ``block_n`` lanes per grid step.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    block = max(n, 1) if interpret else block_n
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    grid = (flat.shape[0] // block,)
    return flat, n, grid, block


def _elemwise(kernel, out_dtype, flat, n, grid, block, interpret,
              scale=None, add=None):
    """Run an elementwise kernel over the tiled flat payload.

    Operand order is (scale?, payload, add?) matching the kernel
    factories below; returns the unpadded (n,) output.
    """
    tile = pl.BlockSpec((block,), lambda i: (i,))
    specs, args = [], []
    if scale is not None:
        specs.append(pl.BlockSpec((1,), lambda i: (0,)))
        args.append(scale.reshape(1))
    specs.append(tile)
    args.append(flat)
    if add is not None:
        specs.append(tile)
        args.append(add)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(flat.shape, out_dtype),
        interpret=interpret,
    )(*args)
    return out[:n]


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

# One grid step's partial max, broadcast over a whole (8, 128) vreg
# tile: Mosaic only accepts output blocks aligned to the TPU tiling, and
# a (1,)-per-step block is not.
_PARTIAL_TILE = (1, 8, 128)


def _absmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.full(_PARTIAL_TILE, jnp.max(jnp.abs(x)))


def _bf16_encode_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.bfloat16)


def _int8_encode_kernel(s_ref, x_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / s_ref[0]), -127.0, 127.0)
    o_ref[...] = q.astype(jnp.int8)


def _fp8_encode_kernel(s_ref, x_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    o_ref[...] = (xf / s_ref[0]).astype(o_ref.dtype)


def _make_decode_add(scaled: bool, has_add: bool):
    """Decode(+accumulate) kernel body: fp32 internal, one pass.

    Branching (rather than passing a unit scale / zero addend) keeps
    the no-scale and no-add paths bit-identical to the unfused
    reference: ``x + 0.0`` flips ``-0.0`` and a multiply is one more
    flop the reference never executes.
    """
    if scaled and has_add:
        def kern(s_ref, p_ref, a_ref, o_ref):
            out = p_ref[...].astype(jnp.float32) * s_ref[0] \
                + a_ref[...].astype(jnp.float32)
            o_ref[...] = out.astype(o_ref.dtype)
    elif scaled:
        def kern(s_ref, p_ref, o_ref):
            o_ref[...] = (p_ref[...].astype(jnp.float32) * s_ref[0]) \
                .astype(o_ref.dtype)
    elif has_add:
        def kern(p_ref, a_ref, o_ref):
            out = p_ref[...].astype(jnp.float32) \
                + a_ref[...].astype(jnp.float32)
            o_ref[...] = out.astype(o_ref.dtype)
    else:
        def kern(p_ref, o_ref):
            o_ref[...] = p_ref[...].astype(jnp.float32).astype(o_ref.dtype)
    return kern


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def hop_absmax(x: jax.Array, *, block_n: int = 2048,
               interpret: bool | None = None) -> jax.Array:
    """Global absmax as a max of per-tile partial maxes (exact)."""
    if _direct(interpret):
        o = _HostRef(dtype=jnp.float32)
        _absmax_kernel(_HostRef(x.reshape(-1)), o)
        return o.val[0, 0, 0]
    interpret = resolve_interpret(interpret)
    flat, _, grid, block = _tile(x, block_n, interpret)
    partial = pl.pallas_call(
        _absmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec(_PARTIAL_TILE, lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (grid[0],) + _PARTIAL_TILE[1:], jnp.float32),
        interpret=interpret,
    )(flat)
    return jnp.max(partial)


def hop_encode(name: str, x: jax.Array, *, block_n: int = 2048,
               interpret: bool | None = None):
    """``(payload, scale)`` for the wire — fused twin of codec.encode.

    The scale arithmetic (safe absmax, subnormal ``tiny`` clamp,
    /127 int8 and /448 fp8 grids) copies codec.py verbatim so the
    scalar — and therefore every quantized element — is bit-identical
    to the unfused encoder's output.
    """
    _check_name(name)
    if name == "none":
        return x, None
    direct = _direct(interpret)
    if not direct:
        interpret = resolve_interpret(interpret)
        flat, n, grid, block = _tile(x, block_n, interpret)
    if name == "bf16":
        if direct:
            o = _HostRef(dtype=jnp.bfloat16)
            _bf16_encode_kernel(_HostRef(x), o)
            return o.val, None
        out = _elemwise(_bf16_encode_kernel, jnp.bfloat16,
                        flat, n, grid, block, interpret)
        return out.reshape(x.shape), None
    # Padding contributes |0| to the max, which never raises it.
    absmax = hop_absmax(x, block_n=block_n, interpret=interpret)
    safe = jnp.where(absmax > 0, absmax, 1.0).astype(jnp.float32)
    tiny = jnp.float32(jnp.finfo(jnp.float32).tiny)
    if name == "int8":
        scale = jnp.maximum(safe / 127.0, tiny)
        if direct:
            o = _HostRef(dtype=jnp.int8)
            _int8_encode_kernel(_HostRef(scale.reshape(1)),
                                _HostRef(x), o)
            return o.val, scale
        out = _elemwise(_int8_encode_kernel, jnp.int8,
                        flat, n, grid, block, interpret, scale=scale)
        return out.reshape(x.shape), scale
    if _FP8_DTYPE is None:
        raise NotImplementedError(
            "this jax has no float8_e4m3fn dtype; the fp8_e4m3 codec "
            "can be planned/verified but not executed here")
    scale = jnp.maximum(safe / 448.0, tiny)
    if direct:
        o = _HostRef(dtype=_FP8_DTYPE)
        _fp8_encode_kernel(_HostRef(scale.reshape(1)), _HostRef(x), o)
        return o.val, scale
    out = _elemwise(_fp8_encode_kernel, _FP8_DTYPE,
                    flat, n, grid, block, interpret, scale=scale)
    return out.reshape(x.shape), scale


def hop_decode_add(name: str, payload: jax.Array, scale,
                   add: jax.Array | None = None, *, block_n: int = 2048,
                   interpret: bool | None = None) -> jax.Array:
    """decode(payload)·scale (+ add) in ONE kernel pass, fp32 internal.

    With ``add`` this is the paper's fused hop body: the received
    chunk is dequantized and accumulated onto the local partial
    without materializing the decoded intermediate.  The result dtype
    matches the unfused ``add + decode(...)`` promotion so fused and
    unfused stage walks stay interchangeable.
    """
    _check_name(name)
    if name == "none" and add is None:
        return payload
    decoded_dtype = payload.dtype if name == "none" else jnp.float32
    if add is not None:
        out_dtype = jnp.promote_types(decoded_dtype, add.dtype)
        if add.shape != payload.shape:
            raise ValueError(f"hop add shape {add.shape} != payload "
                             f"shape {payload.shape}")
    else:
        out_dtype = decoded_dtype
    kern = _make_decode_add(scaled=scale is not None,
                            has_add=add is not None)
    if _direct(interpret):
        refs = []
        if scale is not None:
            refs.append(_HostRef(scale.reshape(1)))
        refs.append(_HostRef(payload))
        if add is not None:
            refs.append(_HostRef(add))
        o = _HostRef(dtype=out_dtype)
        kern(*refs, o)
        return o.val
    interpret = resolve_interpret(interpret)
    flat, n, grid, block = _tile(payload, block_n, interpret)
    add_flat = None
    if add is not None:
        add_flat, _, _, _ = _tile(add, block_n, interpret)
    out = _elemwise(kern, out_dtype, flat, n, grid, block, interpret,
                    scale=scale, add=add_flat)
    return out.reshape(payload.shape)


def hop_roundtrip_add(name: str, x: jax.Array,
                      add: jax.Array | None = None, *,
                      block_n: int = 2048,
                      interpret: bool | None = None) -> jax.Array:
    """encode -> decode(+add) without a wire in between — the local
    half of a loopback hop; test/benchmark convenience."""
    payload, scale = hop_encode(name, x, block_n=block_n,
                                interpret=interpret)
    return hop_decode_add(name, payload, scale, add, block_n=block_n,
                          interpret=interpret)
