"""Pallas kernel validation: interpret-mode execution vs pure-jnp
oracles, swept over shapes and dtypes (the mandated per-kernel allclose)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


@pytest.mark.parametrize("k", [2, 5, 16])
@pytest.mark.parametrize("n", [128, 2048, 4999])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_reduce(k, n, dtype):
    x = jax.random.normal(jax.random.PRNGKey(k * n), (k, n), dtype)
    got = ops.fused_reduce(x, use_pallas=True)
    want = ref.fused_reduce_ref(x)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert got.dtype == x.dtype and got.shape == (n,)


def test_fused_reduce_fp32_accumulation():
    """The kernel's raison d'être: bf16 inputs accumulate in fp32 —
    sequential bf16 addition of 512 near-cancelling terms would drift."""
    k, n = 512, 256
    base = jnp.ones((k, n), jnp.bfloat16) * 0.001
    got = ops.fused_reduce(base, use_pallas=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), 0.512, rtol=2e-3)


def test_fused_reduce_bf16_provably_loses_bits_sequentially():
    """A case where sequential bf16 rounding PROVABLY loses every
    small addend: at magnitude 1024 the bf16 ulp is 8, so 1024 + 1
    rounds back to 1024 — a running bf16 sum of [1024, 1, 1, ..., 1]
    stays 1024 forever, while the exact sum is 1024 + 255.  The kernel's
    fp32 accumulator must return the exact value."""
    k, n = 256, 192
    x = jnp.concatenate([jnp.full((1, n), 1024.0, jnp.bfloat16),
                         jnp.ones((k - 1, n), jnp.bfloat16)])
    # the provable-loss oracle: running sum in bf16 never moves
    seq = x[0]
    for i in range(1, k):
        seq = (seq + x[i]).astype(jnp.bfloat16)
    assert (np.asarray(seq, np.float32) == 1024.0).all()
    got = ops.fused_reduce(x, use_pallas=True, out_dtype=jnp.float32)
    assert (np.asarray(got) == 1024.0 + (k - 1)).all()


def test_fused_reduce_padded_tail_exact():
    """n % block_n != 0: the zero-padded tail tile must not perturb the
    output — integer-valued inputs make exactness checkable bitwise."""
    from repro.kernels.fused_reduce import fused_reduce as pallas_reduce
    k, block_n = 7, 2048
    for n in (block_n + 37, 3 * block_n - 1):
        x = (jnp.arange(k * n, dtype=jnp.float32).reshape(k, n) % 513.0)
        got = pallas_reduce(x, block_n=block_n, interpret=True)
        want = np.asarray(x, np.float64).sum(0)
        assert got.shape == (n,)
        assert (np.asarray(got, np.float64) == want).all()
        # the tail region specifically (past the last full tile)
        tail = (n // block_n) * block_n
        assert (np.asarray(got)[tail:] ==
                want.astype(np.float32)[tail:]).all()


@pytest.mark.parametrize("n", [512, 4096, 10001])
@pytest.mark.parametrize("count", [1, 100])
def test_fused_adamw(n, count):
    key = jax.random.PRNGKey(n)
    p = jax.random.normal(key, (n,))
    g = jax.random.normal(jax.random.PRNGKey(1), (n,))
    m = jax.random.normal(jax.random.PRNGKey(2), (n,)) * 0.1
    v = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (n,))) * 0.01
    got = ops.adamw_update(p, g, m, v, 1e-3, count, use_pallas=True)
    want = ref.adamw_update_ref(p, g, m, v, lr=1e-3, count=count)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("s,h,dh", [(256, 2, 64), (128, 1, 128),
                                    (384, 3, 32)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100),
                                           (False, 0)])
def test_flash_attention(s, h, dh, causal, window):
    key = jax.random.PRNGKey(s + h)
    q = jax.random.normal(key, (2, s, h, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, s, h, dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, s, h, dh),
                          jnp.float32)
    got = fa.flash_attention(q, k, v, causal=causal, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 256, 2, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 64),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, 64),
                          jnp.bfloat16)
    got = fa.flash_attention(q, k, v)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100),
                                           (False, 0)])
def test_flash_attention_backward(causal, window):
    """The FA-2 backward kernels (dq pass, dk/dv pass) through the
    kernel's custom VJP vs autodiff of the naive oracle."""
    key = jax.random.PRNGKey(0)
    B, S, H, DH = 1, 256, 2, 64
    q = jax.random.normal(key, (B, S, H, DH), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, DH),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, DH),
                          jnp.float32)
    do = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, DH),
                           jnp.float32)
    blocks = ((128, 128, 128),) * 3
    _, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, window=window, blocks=blocks), q, k, v)
    dq, dk, dv = vjp(do)

    def f(q, k, v):
        return (ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window) * do).sum()

    gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in [(dq, gq, "dq"), (dk, gk, "dk"), (dv, gv, "dv")]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3, err_msg=name)


@pytest.mark.parametrize("shape", [(8, 128), (3, 37, 128), (500, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_rmsnorm(shape, dtype):
    from repro.kernels.fused_rmsnorm import fused_rmsnorm
    from repro.models.common import rmsnorm
    key = jax.random.PRNGKey(shape[-1])
    x = jax.random.normal(key, shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],),
                          jnp.float32) * 0.1
    got = fused_rmsnorm(x, s, block_rows=64)
    want = rmsnorm(x, s)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
