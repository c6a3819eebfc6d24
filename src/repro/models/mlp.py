"""Feed-forward layers: SwiGLU / GeGLU / GELU."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ModelSpec, dense_init


def mlp_params(key, d_model: int, d_ff: int, mlp_type: str):
    ks = jax.random.split(key, 3)
    p = {"w2": dense_init(ks[2], (d_ff, d_model))}
    if mlp_type in ("swiglu", "geglu"):
        p["w1"] = dense_init(ks[0], (d_model, d_ff))
        p["w_gate"] = dense_init(ks[1], (d_model, d_ff))
    else:
        p["w1"] = dense_init(ks[0], (d_model, d_ff))
    return p


@jax.named_scope("mlp")
def mlp_forward(params, x, mlp_type: str):
    cd = x.dtype
    h = x @ params["w1"].astype(cd)
    if mlp_type == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"].astype(cd)) * h
    elif mlp_type == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"].astype(cd), approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    return h @ params["w2"].astype(cd)
