"""Ahead-of-time v5e compiles of the aggregation kernels and the flash
attention kernel at real widths.

Off the chip the hop kernels run through the ``_HostRef`` direct
lowering or the Pallas interpreter, neither of which applies Mosaic's
layout rules (tile-aligned blocks, VMEM limits).  These tests compile
each kernel for a described v5e chip, so a kernel Mosaic would refuse
fails here instead of on the first chip run.  Nothing executes.

The topology is described inside a module-scoped fixture and never at
import: only one process may load the TPU library, and every xdist
worker imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import fused_hop as fh
from repro.kernels.fused_reduce import fused_reduce

# A 16 MiB f32 bucket plus a tail that is not a multiple of any block.
N = 4 * 2 ** 20 + 1000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — reported as the skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_hop_absmax_compiles(one_chip):
    x = _sds((N,), jnp.float32, one_chip)
    txt = _compile_text(lambda v: fh.hop_absmax(v, interpret=False), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("name", ["bf16", "int8", "fp8_e4m3"])
def test_hop_encode_compiles(one_chip, name):
    x = _sds((N,), jnp.float32, one_chip)
    txt = _compile_text(
        lambda v: fh.hop_encode(name, v, interpret=False), x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("variant", ["scaled_add", "scaled", "add_only"])
def test_hop_decode_add_compiles(one_chip, variant):
    f32 = _sds((N,), jnp.float32, one_chip)
    scale = _sds((), jnp.float32, one_chip)
    i8 = _sds((N,), jnp.int8, one_chip)
    if variant == "scaled_add":
        fn, args = (lambda p, s, a: fh.hop_decode_add(
            "int8", p, s, a, interpret=False)), (i8, scale, f32)
    elif variant == "scaled":
        fn, args = (lambda p, s: fh.hop_decode_add(
            "int8", p, s, None, interpret=False)), (i8, scale)
    else:
        fn, args = (lambda p, a: fh.hop_decode_add(
            "none", p, None, a, interpret=False)), (f32, f32)
    assert "tpu_custom_call" in _compile_text(fn, *args)


def test_fused_reduce_compiles(one_chip):
    x = _sds((4, N), jnp.float32, one_chip)
    txt = _compile_text(lambda v: fused_reduce(v, interpret=False), x)
    assert "tpu_custom_call" in txt


# The attention cells' shapes (B, S, H, KV, dh): smollm-360m at 6 x 2048
# and 4 x 1024, granite-3-2b at 1 x 4096.
@pytest.mark.parametrize("b,s,h,kv,dh", [(6, 2048, 15, 5, 64),
                                         (4, 1024, 15, 5, 64),
                                         (1, 4096, 32, 8, 64)])
def test_flash_attention_compiles(one_chip, b, s, h, kv, dh):
    """Forward and both backward kernels at the blocks the shape
    picks."""
    q = _sds((b, s, h, dh), jnp.bfloat16, one_chip)
    k = _sds((b, s, kv, dh), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, interpret=False), q, k, v)
        return out, vjp(do)
    txt = _compile_text(fwd_bwd, q, k, k, q)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in txt
    assert txt.count("tpu_custom_call") == 3


def test_flash_attention_compiles_at_mla_shape(one_chip):
    """deepseek-v2-lite's attention at 2 x 8192: q and k of head size
    192, v of 128, YaRN's scale; the blocks its (8192, 192) entry picks."""
    b, s, h = 2, 8192, 16
    q = _sds((b, s, h, 192), jnp.bfloat16, one_chip)
    v = _sds((b, s, h, 128), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, scale=0.1147, interpret=False), q, k, v)
        return out, vjp(do)
    txt = _compile_text(fwd_bwd, q, q, v, v)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in txt
    assert txt.count("tpu_custom_call") == 3
