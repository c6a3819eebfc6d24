"""Attention layer: flash (custom-VJP chunked) and the Pallas kernel vs
the naive oracle, fwd+bwd; which path ``sdpa`` takes; GQA decode; MLA
decode (absorbed) vs MLA forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_spec
from repro.kernels import flash_attention as fa
from repro.models import attention as A


@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("kv", [2, 4])
def test_flash_vs_full_fwd_bwd(window, kv):
    key = jax.random.PRNGKey(0)
    B, S, H, DH = 2, 64, 4, 16
    q = jax.random.normal(key, (B, S, H, DH))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, kv, DH))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, kv, DH))
    pos = jnp.arange(S, dtype=jnp.int32)

    o1 = A.sdpa_full(q, k, v, pos, pos, window)
    o2 = A.sdpa_chunked(q, k, v, pos, pos, window, 16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=1e-4)

    g1 = jax.grad(lambda *a: A.sdpa_full(*a, pos, pos, window).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: A.sdpa_chunked(*a, pos, pos, window, 16)
                  .sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_flash_odd_length_padding():
    key = jax.random.PRNGKey(0)
    B, S, H, DH = 2, 50, 4, 16
    q = jax.random.normal(key, (B, S, H, DH))
    k = jax.random.normal(key, (B, S, 2, DH))
    v = jax.random.normal(key, (B, S, 2, DH))
    pos = jnp.arange(S, dtype=jnp.int32)
    o1 = A.sdpa_full(q, k, v, pos, pos, 0)
    o2 = A.sdpa_chunked(q, k, v, pos, pos, 0, 16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=1e-4)


# The TPU path of ``sdpa`` (the Pallas flash kernel, interpreted here)
# against plain attention. 512 positions in blocks of 128 or 256: each
# q block meets kv blocks it skips, its diagonal block and full blocks;
# tiles of 128 split the 256-blocks that straddle the diagonal or the
# window's edge. Blocks: (bq, bk, tile) of the forward, dq, dk/dv.
_B128 = ((128, 128, 128),) * 3
_MIXED = ((256, 128, 256), (128, 256, 256), (256, 128, 256))
_TILED = ((256, 256, 128),) * 3
_MIXED_TILED = ((256, 128, 128), (128, 256, 128), (256, 128, 128))


@pytest.mark.parametrize("h,kv,window,blocks", [
    (4, 4, 0, _B128),           # MHA
    (6, 2, 0, _B128),           # GQA, rep 3 (smollm's 15/5)
    (8, 2, 0, _MIXED),          # GQA, rep 4 (granite's 32/8), unequal blocks
    (6, 2, 200, _B128),         # sliding window: blocks outside it skipped
    (4, 1, 100, _MIXED),        # MQA, window inside a block
    (6, 2, 0, _TILED),          # diagonal blocks in tiles
    (8, 2, 200, _MIXED_TILED),  # window edges in tiles, unequal blocks
])
def test_flash_kernel_vs_full_fwd_bwd(h, kv, window, blocks):
    B, S, DH = 1, 512, 32
    ks = jax.random.split(jax.random.PRNGKey(h * kv + window), 4)
    q = jax.random.normal(ks[0], (B, S, h, DH))
    k = jax.random.normal(ks[1], (B, S, kv, DH))
    v = jax.random.normal(ks[2], (B, S, kv, DH))
    do = jax.random.normal(ks[3], (B, S, h, DH))
    pos = jnp.arange(S, dtype=jnp.int32)

    def kernel(q, k, v):
        return (fa.flash_attention(q, k, v, window=window, blocks=blocks)
                * do).sum()

    def full(q, k, v):
        return (A.sdpa_full(q, k, v, pos, pos, window) * do).sum()

    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, window=window,
                                      blocks=blocks)),
        np.asarray(A.sdpa_full(q, k, v, pos, pos, window)),
        atol=2e-5, rtol=1e-4)
    g1 = jax.grad(kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(full, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3, err_msg=name)


def _spec(**kw):
    return dataclasses.replace(get_spec("smollm-360m").reduced(), **kw)


@pytest.mark.parametrize("tpu,seq,shared,kernel", [
    (False, 256, True, False),   # CPU, long: the chunked path
    (False, 48, True, False),    # CPU, short: plain attention
    (True, 256, True, True),     # TPU, blocks divide S: the kernel
    (True, 200, True, False),    # TPU, S not a multiple of 128: jnp path
    (True, 256, False, False),   # TPU, q and k positions not shared
])
def test_sdpa_dispatch(tpu, seq, shared, kernel, monkeypatch):
    """``sdpa`` takes the Pallas kernel only on a TPU, for one shared
    positions array and a length its blocks divide; otherwise the jnp
    path ``attn_full_seq_max`` picks, with the same numbers."""
    monkeypatch.setattr(A, "on_tpu", lambda: tpu)
    spec = _spec()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, seq, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, seq, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, seq, 2, 64))
    pos = jnp.arange(seq, dtype=jnp.int32)
    k_pos = pos if shared else jnp.arange(seq, dtype=jnp.int32)
    jaxpr = str(jax.make_jaxpr(
        lambda q, k, v: A.sdpa(q, k, v, pos, k_pos, spec))(q, k, v))
    assert ("pallas_call" in jaxpr) == kernel
    if seq <= spec.attn_full_seq_max:
        want = A.sdpa_full(q, k, v, pos, pos, 0)
    else:
        want = A.sdpa_chunked(q, k, v, pos, pos, 0, spec.attn_chunk)
    np.testing.assert_allclose(
        np.asarray(A.sdpa(q, k, v, pos, k_pos, spec)), np.asarray(want),
        atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("types,shape,whole", [
    (("Manual", "Auto"), (2, 1), True),     # the train step's shard_map
    (("Manual", "Auto"), (2, 4), False),    # model axis left to GSPMD
    (("Explicit", "Auto"), (2, 1), False),  # a sharded jit, no shard_map
])
def test_kernel_only_where_nothing_is_partitioned(types, shape, whole):
    """A Pallas kernel cannot be partitioned by the compiler: ``sdpa``
    takes it only where every mesh axis of more than one device is
    manual (or, outside any mesh, on one device)."""
    from jax.sharding import AbstractMesh, AxisType
    mesh = AbstractMesh(shape, ("data", "model"),
                        axis_types=tuple(getattr(AxisType, t)
                                         for t in types))
    assert A._unpartitioned()
    with jax.sharding.use_abstract_mesh(mesh):
        assert A._unpartitioned() == whole
