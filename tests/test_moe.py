"""The held-experts MoE layer against a plain per-token reference: shares
that sum to the uncut layer, no token dropped, ``norm_topk_prob`` both
ways, both aux-loss forms, the step counters, and ``gate_grad`` off."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_spec
from repro.models import moe as moe_lib
from repro.models.mlp import mlp_forward
from repro.telemetry.metrics import MetricsRegistry, record_moe_step


def _spec(**kw):
    """deepseek's layer, small: 16 routed experts, top-3, 2 shared, f32."""
    base = get_spec("deepseek-v2-lite-16b").reduced()
    kw = {"dtype": "float32", **kw}
    return dataclasses.replace(base, d_model=32, num_experts=16, top_k=3,
                               moe_d_ff=16, **kw)


def _params(spec, seed=0):
    return moe_lib.moe_params(jax.random.PRNGKey(seed), spec)


def _x(spec, b=2, s=24, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, spec.d_model))


def _reference(params, x, spec):
    """Per token: softmax over every routed expert in float64, greedy
    top-k, each chosen expert that is held applied to the token alone
    and weighted (renormalised where the spec says), plus the shared
    experts."""
    first, held = spec.expert_first, spec.held_experts
    xt = np.asarray(x, np.float64).reshape(-1, spec.d_model)
    logits = xt @ np.asarray(params["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    w1, wg, w2 = (np.asarray(params[n], np.float64)
                  for n in ("w1", "w_gate", "w2"))
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-p[t], kind="stable")[:spec.top_k]
        w = p[t, top]
        if spec.norm_topk_prob:
            w = w / w.sum()
        for e, we in zip(top, w):
            j = e - first
            if 0 <= j < held:
                g = xt[t] @ wg[j]
                h = g / (1 + np.exp(-g)) * (xt[t] @ w1[j])
                out[t] += we * (h @ w2[j])
    shared = np.asarray(mlp_forward(params["shared"], jnp.asarray(
        xt, jnp.float32), spec.mlp_type), np.float64)
    return (out + shared).reshape(x.shape)


@pytest.mark.parametrize("norm", [False, True])
def test_layer_matches_per_token_reference(norm):
    spec = _spec(norm_topk_prob=norm)
    params, x = _params(spec), _x(spec)
    y, stats = moe_lib.moe_forward(params, x, spec)
    np.testing.assert_allclose(np.asarray(y), _reference(params, x, spec),
                               rtol=1e-4, atol=1e-5)
    assert float(stats["held_pairs"]) == x.shape[0] * x.shape[1] * 3


def test_renormalising_changes_the_weights():
    """deepseek keeps the top-k probabilities as they are; granite-moe
    renormalises them to sum to one."""
    assert not get_spec("deepseek-v2-lite-16b").norm_topk_prob
    assert get_spec("granite-moe-1b-a400m").norm_topk_prob
    a, b = _spec(norm_topk_prob=False), _spec(norm_topk_prob=True)
    params, x = _params(a), _x(a)
    _, w_a, i_a = moe_lib.route(x.reshape(-1, a.d_model), params["router"],
                                a)
    _, w_b, i_b = moe_lib.route(x.reshape(-1, b.d_model), params["router"],
                                b)
    assert (np.asarray(i_a) == np.asarray(i_b)).all()
    np.testing.assert_allclose(np.asarray(w_b).sum(-1), 1.0, rtol=1e-6)
    assert float(np.max(np.asarray(w_a).sum(-1))) < 0.99


def test_held_shares_sum_to_the_uncut_layer():
    """Eight chips of two experts each: the eight shares' outputs, with
    the shared experts (computed alike on every chip) counted once, add
    up to the layer that holds all sixteen."""
    uncut = _spec()
    params, x = _params(uncut), _x(uncut)
    whole, _ = moe_lib.moe_forward(params, x, uncut)
    shared = mlp_forward(params["shared"], x, uncut.mlp_type)
    total = jnp.zeros_like(whole)
    pairs = 0.0
    for rank in range(8):
        spec = dataclasses.replace(uncut, expert_first=2 * rank,
                                   experts_held=2)
        share = dict(params, **{n: params[n][2 * rank:2 * rank + 2]
                                for n in ("w1", "w_gate", "w2")})
        y, stats = moe_lib.moe_forward(share, x, spec)
        np.testing.assert_allclose(
            np.asarray(y), _reference(share, x, spec), rtol=1e-4, atol=1e-5)
        total = total + y - shared
        pairs += float(stats["held_pairs"])
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)
    assert pairs == x.shape[0] * x.shape[1] * uncut.top_k


def test_no_token_dropped_when_every_token_routes_to_one_expert():
    """Routing forced onto expert 0 (and, the rest tied at zero, expert
    1): all T·k pairs land on two experts, every one is computed, and
    the output is the per-token reference's."""
    spec = _spec(expert_first=0, experts_held=4)
    params = dict(_params(spec))
    router = np.zeros((spec.d_model, spec.num_experts), np.float32)
    router[:, 0] = 1.0
    params["router"] = jnp.asarray(router)
    x = jnp.abs(_x(spec, b=2, s=64))           # positive: expert 0 wins
    y, stats = moe_lib.moe_forward(params, x, spec)
    t = x.shape[0] * x.shape[1]
    assert float(stats["held_pairs"]) == t * spec.top_k
    # loads 64·2 = 128 pairs each on experts 0, 1 and 2; expert 3 none
    assert float(stats["load_ratio"]) == pytest.approx(4 / 3)
    np.testing.assert_allclose(np.asarray(y), _reference(params, x, spec),
                               rtol=1e-4, atol=1e-5)


def test_pairs_on_experts_held_elsewhere_are_not_computed():
    """Experts 4-7 held: the counter is the pairs routed to them, and the
    output has no part of the other experts."""
    spec = _spec(expert_first=4, experts_held=4)
    full = _spec()
    params_full, x = _params(full), _x(full)
    params = dict(params_full, **{n: params_full[n][4:8]
                                  for n in ("w1", "w_gate", "w2")})
    y, stats = moe_lib.moe_forward(params, x, spec)
    _, _, top_i = moe_lib.route(x.reshape(-1, spec.d_model),
                                params["router"], spec)
    top_i = np.asarray(top_i)
    assert float(stats["held_pairs"]) == ((top_i >= 4) & (top_i < 8)).sum()
    np.testing.assert_allclose(np.asarray(y), _reference(params, x, spec),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["seq", "switch"])
def test_aux_loss_against_its_formula(kind):
    """seq (DeepSeek): per sequence f_i = E/(k·S)·count_i, P_i the mean
    probability over its positions; the mean over sequences of
    Σ f_i·P_i.  switch: E · Σ frac_i·P_i over the whole batch."""
    spec = _spec(router_aux=kind)
    params, x = _params(spec), _x(spec, b=3, s=20)
    b, s = x.shape[:2]
    e, k = spec.num_experts, spec.top_k
    _, stats = moe_lib.moe_forward(params, x, spec)
    probs, _, top_i = moe_lib.route(x.reshape(-1, spec.d_model),
                                    params["router"], spec)
    probs = np.asarray(probs, np.float64).reshape(b, s, e)
    top_i = np.asarray(top_i).reshape(b, s * k)
    count = np.stack([np.bincount(r, minlength=e) for r in top_i])
    if kind == "seq":
        f = count * e / (k * s)
        want = np.mean(np.sum(f * probs.mean(1), -1))
    else:
        frac = count.sum(0) / (b * s * k)
        want = e * np.sum(frac * probs.reshape(-1, e).mean(0))
    assert float(stats["aux"]) == pytest.approx(want, rel=1e-5)
    assert get_spec("deepseek-v2-lite-16b").router_aux == "seq"
    assert get_spec("granite-moe-1b-a400m").router_aux == "switch"


def test_router_runs_in_float32():
    spec = _spec(dtype="bfloat16")
    params, x = _params(spec), _x(spec).astype(jnp.bfloat16)
    probs, w, _ = moe_lib.route(x.reshape(-1, spec.d_model),
                                params["router"], spec)
    assert probs.dtype == jnp.float32 and w.dtype == jnp.float32


def test_step_counters_reach_the_registry():
    reg = MetricsRegistry()
    step = {"moe_held_pairs": 96.0, "moe_held_share": 0.125,
            "moe_load_ratio": 1.5, "aux": 0.002, "ce": 3.0}
    record_moe_step(step, reg)
    record_moe_step(step, reg)
    record_moe_step({"ce": 3.0, "aux": 0.0}, reg)    # a dense model's step
    assert reg.counter("moe_held_pairs").get() == 192.0
    assert reg.gauge("moe_held_share").get() == 0.125
    assert reg.gauge("moe_load_ratio").get() == 1.5
    assert reg.gauge("aux").get() == 0.002


def _dense_layer(params, x, spec):
    """The layer written plainly in jnp (every held expert on every token,
    masked by its routing weight), for autodiff: the gradients of the
    sorted dispatch and combine's own backward passes are checked
    against it."""
    held, first = spec.held_experts, spec.expert_first
    xt = x.reshape(-1, spec.d_model)
    probs, top_w, top_i = moe_lib.route(xt, params["router"], spec)
    weight = jnp.einsum("tk,tke->te", top_w,
                        jax.nn.one_hot(top_i, spec.num_experts))
    out = mlp_forward(params["shared"], xt, spec.mlp_type)
    for j in range(held):
        e = {n: params[n][j] for n in ("w1", "w_gate", "w2")}
        out = out + weight[:, first + j, None] * mlp_forward(e, xt,
                                                             spec.mlp_type)
    return out.reshape(x.shape)


@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)])
def test_layer_gradients_match_plain_autodiff(first, held):
    spec = _spec(expert_first=first, experts_held=held)
    full = _params(_spec())
    params = dict(full, **{n: full[n][first:first + held]
                           for n in ("w1", "w_gate", "w2")})
    x = _x(spec)
    ct = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def loss(layer):
        return lambda p, x: jnp.sum(layer(p, x) * ct)
    got = jax.grad(loss(lambda p, x: moe_lib.moe_forward(p, x, spec)[0]),
                   argnums=(0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: _dense_layer(p, x, spec)),
                    argnums=(0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=str(path))


def test_gate_grad_off_leaves_the_router_to_the_aux_loss():
    """``gate_grad=False``: the same outputs and expert gradients, no
    gradient from the outputs to the router, and the aux loss's gradient
    to the router unchanged."""
    on = _spec(expert_first=4, experts_held=4)
    off = dataclasses.replace(on, gate_grad=False)
    full = _params(_spec())
    params = dict(full, **{n: full[n][4:8] for n in ("w1", "w_gate", "w2")})
    x = _x(on)
    ct = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def grads(spec, part):
        def loss(p):
            y, st = moe_lib.moe_forward(p, x, spec)
            return jnp.sum(y * ct) if part == "out" else st["aux"]
        return jax.value_and_grad(loss)(params)

    (y_on, g_on), (y_off, g_off) = grads(on, "out"), grads(off, "out")
    np.testing.assert_allclose(float(y_off), float(y_on), rtol=1e-6)
    assert float(jnp.abs(g_on["router"]).max()) > 0
    assert float(jnp.abs(g_off["router"]).max()) == 0
    for n in ("w1", "w_gate", "w2"):
        np.testing.assert_allclose(np.asarray(g_off[n]), np.asarray(g_on[n]),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(grads(off, "aux")[1]["router"]),
                               np.asarray(grads(on, "aux")[1]["router"]),
                               rtol=1e-6, atol=1e-9)
