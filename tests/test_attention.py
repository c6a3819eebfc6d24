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


# MLA's shape, small: q and k of head size nope + rope, v of its own
# size, the scores scaled by YaRN's mscale² / sqrt(q's head size).
@pytest.mark.parametrize("h,kv,dqk,dv,scale,blocks", [
    (4, 4, 48, 32, 0.2, _B128),       # MLA: every head its own kv
    (4, 4, 48, 32, 0.2, _TILED),      # diagonal blocks in tiles
    (6, 2, 32, 16, None, _MIXED),     # GQA, v narrower, default scale
])
def test_flash_kernel_v_head_and_scale(h, kv, dqk, dv, scale, blocks):
    B, S = 1, 512
    ks = jax.random.split(jax.random.PRNGKey(dqk + dv), 4)
    q = jax.random.normal(ks[0], (B, S, h, dqk))
    k = jax.random.normal(ks[1], (B, S, kv, dqk))
    v = jax.random.normal(ks[2], (B, S, kv, dv))
    do = jax.random.normal(ks[3], (B, S, h, dv))
    pos = jnp.arange(S, dtype=jnp.int32)

    def plain(q, k, v):
        kr, vr = (jnp.repeat(x, h // kv, axis=2) for x in (k, v))
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kr, precision="highest")
        sc = sc * (1 / np.sqrt(dqk) if scale is None else scale)
        sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vr,
                          precision="highest")

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, scale=scale, blocks=blocks)

    paths = {"kernel": kernel,
             "full": lambda q, k, v: A.sdpa_full(q, k, v, pos, pos, 0,
                                                 scale),
             "chunked": lambda q, k, v: A.sdpa_chunked(q, k, v, pos, pos,
                                                       0, 128, scale)}
    want = plain(q, k, v)
    gw = jax.grad(lambda *a: (plain(*a) * do).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for name, f in paths.items():
        got = f(q, k, v)
        assert got.shape == (B, S, h, dv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4, err_msg=name)
        g = jax.grad(lambda *a: (f(*a) * do).sum(), argnums=(0, 1, 2))(
            q, k, v)
        for a, b, which in zip(g, gw, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-3,
                                       err_msg=name + " d" + which)


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


# ---------------------------------------------------------------------------
# MLA: the normed latent, YaRN RoPE and its softmax scale
# ---------------------------------------------------------------------------

def _yarn_reference(dim, theta, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies, transcribed."""
    def corr(rot):
        return dim * np.log(orig / (rot * 2 * np.pi)) / (2 * np.log(theta))
    low = max(int(np.floor(corr(beta_fast))), 0)
    high = min(int(np.ceil(corr(beta_slow))), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return extra / factor * ramp + extra * (1 - ramp), low, high


def test_yarn_frequencies_and_scale_at_published_sizes():
    from repro.models import common
    spec = get_spec("deepseek-v2-lite-16b")
    y = spec.rope_scaling
    got = common.rope_frequencies(64, spec.rope_theta, y)
    want, low, high = _yarn_reference(64, 10000.0, 40, 4096, 32, 1)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:10], 1.0 / 10000 ** (
        np.arange(0, 20, 2) / 64), rtol=1e-6)         # extrapolated
    np.testing.assert_allclose(got[23:], 1.0 / 10000 ** (
        np.arange(46, 64, 2) / 64) / 40, rtol=1e-6)   # interpolated
    assert common.rope_mscale(y) == 1.0                # mscale = all_dim
    m = 0.1 * 0.707 * np.log(40) + 1
    assert A.mla_scale(spec) == pytest.approx(m * m / np.sqrt(192))


def _mla_spec(**kw):
    return dataclasses.replace(get_spec("deepseek-v2-lite-16b").reduced(),
                               **kw)


def _mla_reference(p, x, spec):
    """Float64 MLA: RMSNorm on the latent, YaRN on the rope dims (the two
    halves rotated), q·k over nope + rope, scaled, causal softmax."""
    f = {k: np.asarray(v, np.float64) for k, v in p.items()
         if k != "kv_norm"}
    x = np.asarray(x, np.float64)
    b, s, _ = x.shape
    h, r, nd, rd, vd = spec.num_heads, spec.kv_lora_rank, \
        spec.qk_nope_dim, spec.qk_rope_dim, spec.v_head_dim
    y = spec.rope_scaling
    inv, _, _ = _yarn_reference(rd, spec.rope_theta, y.factor,
                                y.original_max_position, y.beta_fast,
                                y.beta_slow)
    ang = np.arange(s)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]

    def rope(t):
        a, c = np.split(t, 2, axis=-1)
        return np.concatenate([a * cos - c * sin, a * sin + c * cos], -1)

    q = (x @ f["wq"]).reshape(b, s, h, nd + rd)
    dkv = x @ f["wdkv"]
    c = dkv[..., :r] / np.sqrt(np.mean(dkv[..., :r] ** 2, -1,
                                       keepdims=True) + 1e-6)
    c = c * (1 + np.asarray(p["kv_norm"]["scale"], np.float64))
    k_rope = rope(dkv[..., None, r:])
    k_nope = (c @ f["wuk"]).reshape(b, s, h, nd)
    v = (c @ f["wuv"]).reshape(b, s, h, vd)
    m = 0.1 * y.mscale_all_dim * np.log(y.factor) + 1
    sc = (np.einsum("bqhd,bkhd->bhqk", q[..., :nd], k_nope)
          + np.einsum("bqhd,bkd->bhqk", rope(q[..., nd:]), k_rope[:, :, 0])
          ) * m * m / np.sqrt(nd + rd)
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", pr, v).reshape(b, s, h * vd)
    return o @ f["wo"]


def test_mla_forward_matches_float32_reference():
    spec = _mla_spec(dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    p = dict(A.mla_params(ks[0], spec))
    p["kv_norm"] = {"scale": 0.3 * jax.random.normal(
        ks[1], (spec.kv_lora_rank,))}
    x = jax.random.normal(ks[2], (2, 40, spec.d_model))
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    out, (c_kv, _) = A.mla_forward(p, x, pos, spec)
    want = _mla_reference(p, x, spec)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)
    # the cache holds the normed latent
    assert float(jnp.sqrt(jnp.mean(c_kv[0, 0] ** 2 / (1 + p["kv_norm"][
        "scale"]) ** 2))) == pytest.approx(1.0, rel=1e-3)


def test_mla_decode_after_prefill_matches_forward():
    """An MLA model (dense MLP, bfloat16): prefill a prompt, decode the
    rest one token at a time through the absorbed latent cache, and
    compare each step's logits with the full forward's."""
    from repro.models import build_model, transformer
    spec = _mla_spec(family="dense", num_experts=0, first_dense_layers=0,
                     d_ff=128)
    model = build_model(spec)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 14), 0,
                                spec.vocab_size)
    full, _, _ = transformer.forward(params, tokens, spec)
    _, cache = model.prefill(params, {"tokens": tokens[:, :8]}, 14)
    for t in range(8, 14):
        logits, cache = model.decode_step(params, cache,
                                          tokens[:, t:t + 1])
        want = np.asarray(full[:, t], np.float32)
        err = np.max(np.abs(np.asarray(logits, np.float32) - want)) \
            / np.max(np.abs(want))
        assert err < 0.05, (t, err)
