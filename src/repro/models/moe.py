"""Mixture-of-Experts layer: a float32 softmax router over every routed
expert, and the experts this chip holds computed in one grouped matmul.

The spec says which contiguous range of the ``num_experts`` routed
experts this chip holds (``expert_first``, ``experts_held``; all of them
by default).  Every token is routed over all experts (greedy top-k); the
(token, expert) pairs are sorted by expert, and each pair whose expert is
held goes through ``jax.lax.ragged_dot`` with the held experts' group
sizes: gate, up, SiLU, down.  The static row count is T·k, so no routing
can overflow and no token is dropped.  Pairs on experts held elsewhere
are not computed; their weight is zero in the combine.  Shared experts
are computed alike on every chip.

Tokens move between token order and expert order by gathers alone: the
rows of the (token, expert) pairs are taken in expert order, and their
results back in pair order by the inverse permutation, each of whose
gradients is again a gather (``_dispatch``, ``_combine``); the k
results of a token are summed in float32.  No scatter-add, whose conflicting
rows a TPU serialises.

Router aux loss: the switch load-balance loss over the whole batch, or
DeepSeek's per-sequence form (``router_aux="seq"``); both read 1 when
the routing is balanced.  Where ``spec.gate_grad`` is off, the top-k
weights pass no gradient to the router, which then learns from the aux
loss alone: on a chip that holds some experts and exchanges no tokens,
the loss's gradient through the weights would come from the held
experts only and pull tokens toward them, step after step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ModelSpec, dense_init
from .mlp import mlp_forward, mlp_params


def moe_params(key, spec: ModelSpec):
    d, e, f = spec.d_model, spec.held_experts, spec.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, spec.num_experts)),
        "w1": dense_init(ks[1], (e, d, f), in_axis=1),
        "w_gate": dense_init(ks[2], (e, d, f), in_axis=1),
        "w2": dense_init(ks[3], (e, f, d), in_axis=1),
    }
    if spec.num_shared_experts:
        p["shared"] = mlp_params(ks[4], d,
                                 spec.moe_d_ff * spec.num_shared_experts,
                                 spec.mlp_type)
    return p


def route(x, router, spec: ModelSpec):
    """(probs (T, E), top-k weights (T, k), top-k experts (T, k)) of
    tokens x (T, d): softmax over every routed expert in float32, greedy
    top-k, renormalised only where the spec says so."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, spec.top_k)
    if spec.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return probs, top_w, top_i


def aux_loss(probs, top_i, batch: int, spec: ModelSpec):
    """Load-balance loss over all ``num_experts``.  ``switch``: E · Σ_i
    f_i·P_i over the whole batch, f_i the share of the T·k pairs on
    expert i and P_i its mean probability.  ``seq``: for each sequence,
    f_i = E/(k·S) · count_i and P_i the mean over its positions; the mean
    over sequences of Σ_i f_i·P_i."""
    e, k = spec.num_experts, spec.top_k
    t = probs.shape[0]
    s = t // batch
    onehot = jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum(1)  # (T, E)
    if spec.router_aux == "switch":
        frac = onehot.sum(0) / (t * k)
        return e * jnp.sum(frac * probs.mean(0))
    if spec.router_aux != "seq":
        raise ValueError(f"unknown router_aux {spec.router_aux!r}")
    f = onehot.reshape(batch, s, e).sum(1) * (e / (k * s))
    p = probs.reshape(batch, s, e).mean(1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, order, back, k):
    """Rows of the (token, expert) pairs in expert order: xt[order // k].
    Its gradient takes the rows back to pair order by the inverse
    permutation ``back`` and sums each token's k of them."""
    return xt[order // k]


def _dispatch_fwd(xt, order, back, k):
    return xt[order // k], (order, back)


def _dispatch_bwd(k, res, g):
    _, back = res
    g = g[back]
    return (g.reshape(-1, k, g.shape[-1]).astype(jnp.float32).sum(1)
            .astype(g.dtype), None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(ys, w, order, back, k):
    """Σ over each token's k pairs of w·ys, summed in float32: ys (T·k,
    d) and w (T·k,) in expert order, w zero on pairs not computed (whose
    rows of ys are undefined and are selected away)."""
    part = jnp.where(w[:, None] > 0, ys * w[:, None].astype(ys.dtype), 0)
    part = part[back]
    return part.reshape(-1, k, part.shape[-1]).astype(jnp.float32).sum(1)


def _combine_fwd(ys, w, order, back, k):
    return _combine(ys, w, order, back, k), (ys, w, order)


def _combine_bwd(k, res, g):
    ys, w, order = res
    held = w[:, None] > 0
    gp = g[order // k]                                     # expert order
    d_ys = jnp.where(held, gp * w[:, None], 0).astype(ys.dtype)
    d_w = jnp.where(held[:, 0], jnp.sum(
        gp * jnp.where(held, ys, 0).astype(jnp.float32), -1), 0)
    return d_ys, d_w.astype(w.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.named_scope("moe")
def moe_forward(params, x, spec: ModelSpec):
    """x: (B, S, d) -> (out, stats): stats holds the aux loss, the pairs
    computed on held experts and the largest held expert's load over the
    mean held load."""
    b, s, d = x.shape
    cd = x.dtype
    k, held = spec.top_k, spec.held_experts
    xt = x.reshape(b * s, d)
    t = xt.shape[0]

    with jax.named_scope("router"):
        probs, top_w, top_i = route(xt, params["router"], spec)
        aux = aux_loss(probs, top_i, b, spec)

    with jax.named_scope("dispatch"):
        local = top_i.reshape(-1) - spec.expert_first           # (T·k,)
        is_held = (local >= 0) & (local < held)
        group = jnp.where(is_held, local, held)   # not held: sorted last
        order = jnp.argsort(group, stable=True)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
        sizes = jnp.sum(group[None, :] == jnp.arange(held)[:, None], axis=1,
                        dtype=jnp.int32)
        # Rows past the held groups belong to no expert: ragged_dot leaves
        # them undefined (on a TPU, whatever the buffer held), so they are
        # selected away on the way in (their gradient) and out.
        xs = jnp.where(is_held[order][:, None],
                       _dispatch(xt, order, back, k), 0)        # (T·k, d)

    with jax.named_scope("experts"):
        def gmm(a, w):
            return jax.lax.ragged_dot(a, w.astype(cd), sizes)
        h = jax.nn.silu(gmm(xs, params["w_gate"])) * gmm(xs, params["w1"])
        ys = gmm(h, params["w2"])                               # (T·k, d)

    with jax.named_scope("combine"):
        if not spec.gate_grad:
            top_w = jax.lax.stop_gradient(top_w)
        w = jnp.where(is_held, top_w.reshape(-1), 0.0)[order]
        y = _combine(ys, w, order, back, k).astype(cd)

    if spec.num_shared_experts:
        y = y + mlp_forward(params["shared"], xt, spec.mlp_type)

    loads = sizes.astype(jnp.float32)
    stats = {"aux": aux, "held_pairs": loads.sum(),
             "load_ratio": loads.max() / jnp.maximum(loads.mean(), 1e-9)}
    return y.reshape(b, s, d), stats
