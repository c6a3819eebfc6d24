"""DeepSeek-V2 decoder (MLA with a normed latent and YaRN RoPE; a dense
first layer; then MoE layers of a float32 softmax router over every
routed expert, greedy top-k, shared experts): how the benchmark builds
the program's model from a configuration file, makes its weights,
counts its FLOPs, and computes the plain float32 reference of its loss
and gradient.

The configuration holds one chip's share of an expert-parallel
deployment: ``n_routed_experts`` experts held (from ``expert_first``) of
``router_outputs`` routed ones, and a slice of the vocabulary.  The
router scores every routed expert; pairs routed to experts held
elsewhere add nothing here, in the program and the reference alike.
Where ``router_gate_grad`` is false, the top-k weights pass no gradient
to the router, which learns from the aux loss alone.

Nothing here imports the model code under test (the reference is
written from the published description: DeepSeek-V2's modeling code
and config.json, departures in the configuration's ``assumed``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from families import dense_lm

# Query rows per block in the reference's attention: one block's scores
# for 16 heads over 8192 keys are 256 MiB.
REF_Q_BLOCK = 512

make_mm = dense_lm.make_mm
nest = dense_lm.nest


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
            "dense_ff": cfg["intermediate_size"],
            "ff": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "held": cfg["n_routed_experts"],
            "first": cfg["expert_first"],
            "experts": cfg["router_outputs"],
            "k": cfg["num_experts_per_tok"],
            "dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"]}


def _yarn(cfg: dict) -> dict:
    y = cfg["rope_scaling"]
    if y.get("type") != "yarn":
        raise ValueError(f"rope_scaling {y!r}: the family runs yarn")
    return y


def program_spec(cfg: dict):
    """The program's ModelSpec for this configuration."""
    from repro.models.common import ModelSpec, YarnScaling

    m = dims(cfg)
    y = _yarn(cfg)
    prog = cfg["program"]
    if cfg["q_lora_rank"] is not None or cfg["topk_method"] != "greedy" \
            or cfg["scoring_func"] != "softmax" or not cfg["seq_aux"] \
            or cfg["routed_scaling_factor"] != 1 or cfg["moe_layer_freq"] \
            != 1:
        raise ValueError(f"{cfg['name']}: the program runs no q_lora, a "
                         "greedy softmax router with the per-sequence aux "
                         "loss, unscaled, on every layer after the dense")
    spec = ModelSpec(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=m["d"],
        num_heads=m["h"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=m["ff"], vocab_size=m["vocab"], mlp_type="swiglu",
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YarnScaling(
            factor=float(y["factor"]),
            original_max_position=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        attention_type="mla", kv_lora_rank=m["r"], qk_rope_dim=m["rope"],
        qk_nope_dim=m["nope"], v_head_dim=m["v"],
        num_experts=m["experts"], num_shared_experts=m["shared"],
        top_k=m["k"], moe_d_ff=m["ff"], first_dense_layers=m["dense"],
        dense_d_ff=m["dense_ff"], expert_first=m["first"],
        experts_held=m["held"], norm_topk_prob=bool(cfg["norm_topk_prob"]),
        gate_grad=bool(cfg["router_gate_grad"]),
        router_aux="seq", router_aux_weight=float(cfg["aux_loss_alpha"]),
        remat=bool(prog["remat"]), dtype=prog["dtype"],
        param_dtype=prog["param_dtype"])
    if spec.padded_vocab != m["vocab"]:
        raise ValueError(f"{cfg['name']}: the program pads the vocabulary "
                         f"to {spec.padded_vocab} rows")
    return spec


# ---------------------------------------------------------------------------
# weights: one leaf per key, so a leaf can be made again on its own
# ---------------------------------------------------------------------------

def _layer_shapes(m: dict, n: int, prefix: str, moe: bool) -> dict:
    d, h = m["d"], m["h"]
    q, kv, o = h * (m["nope"] + m["rope"]), m["r"] + m["rope"], h * m["v"]
    out = {
        f"{prefix}/ln1/scale": ((n, d), 0.0),
        f"{prefix}/ln2/scale": ((n, d), 0.0),
        f"{prefix}/attn/wq": ((n, d, q), 1 / math.sqrt(d)),
        f"{prefix}/attn/wdkv": ((n, d, kv), 1 / math.sqrt(d)),
        f"{prefix}/attn/kv_norm/scale": ((n, m["r"]), 0.0),
        f"{prefix}/attn/wuk": ((n, m["r"], h * m["nope"]),
                               1 / math.sqrt(m["r"])),
        f"{prefix}/attn/wuv": ((n, m["r"], o), 1 / math.sqrt(m["r"])),
        f"{prefix}/attn/wo": ((n, o, d), 1 / math.sqrt(o)),
    }

    def mlp(path, ff, lead=()):
        return {f"{path}/w1": ((n,) + lead + (d, ff), 1 / math.sqrt(d)),
                f"{path}/w_gate": ((n,) + lead + (d, ff), 1 / math.sqrt(d)),
                f"{path}/w2": ((n,) + lead + (ff, d), 1 / math.sqrt(ff))}

    if moe:
        out[f"{prefix}/moe/router"] = ((n, d, m["experts"]),
                                       1 / math.sqrt(d))
        out.update(mlp(f"{prefix}/moe", m["ff"], (m["held"],)))
        out.update(mlp(f"{prefix}/moe/shared", m["ff"] * m["shared"]))
    else:
        out.update(mlp(f"{prefix}/mlp", m["dense_ff"]))
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, std), in the program's tree: the dense layers
    stacked under ``prefix``, the MoE layers under ``body``."""
    m = dims(cfg)
    return {
        "embed": ((m["vocab"], m["d"]), 0.02),
        "lm_head": ((m["d"], m["vocab"]), 0.02),
        "ln_f/scale": ((m["d"],), 0.0),
        **_layer_shapes(m, m["dense"], "prefix", False),
        **_layer_shapes(m, m["moe"], "body", True),
    }


def make_leaf(key, cfg: dict, name: str):
    """One float32 leaf: normal(0, std) from its own key (zeros for the
    norm scales, which are applied as ``1 + scale``)."""
    shapes = leaf_shapes(cfg)
    shape, std = shapes[name]
    if std == 0.0:
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, sorted(shapes).index(name))
    return jax.random.normal(k, shape, jnp.float32) * std


def init_params(key, cfg: dict) -> dict:
    return nest({n: make_leaf(key, cfg, n) for n in leaf_shapes(cfg)})


# ---------------------------------------------------------------------------
# FLOPs the training step requires (no recomputation counted)
# ---------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Weights a token meets in a matmul: every projection, the dense
    MLP, the router, the shared experts, the held routed experts at
    their balanced share (k · held / E experts a token) and the LM head
    (the embedding lookup is a gather, not a matmul)."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    attn = d * h * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"]) \
        + m["r"] * h * (m["nope"] + m["v"]) + h * m["v"] * d
    expert = 3 * d * m["ff"]
    share = m["k"] * m["held"]
    if share * expert % m["experts"]:
        raise ValueError("the experts' balanced share is not whole")
    moe = d * m["experts"] + m["shared"] * expert \
        + share * expert // m["experts"]
    return (m["dense"] + m["moe"]) * attn + m["dense"] * 3 * d \
        * m["dense_ff"] + m["moe"] * moe + m["vocab"] * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6·N, plus causal MLA's score (nope + rope) and value (v) matmuls
    at half of full: 6·L·h·(nope + rope + v)/2·S per token."""
    m = dims(cfg)
    layers = m["dense"] + m["moe"]
    return 6.0 * matmul_params(cfg) + 6.0 * layers * m["h"] \
        * (m["nope"] + m["rope"] + m["v"]) / 2 * seq


# ---------------------------------------------------------------------------
# the plain reference: float32 throughout, matmuls at HIGHEST precision
# ---------------------------------------------------------------------------

def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies of the rope
    dims: original below the beta_fast correction dim, divided by the
    factor above the beta_slow one, a linear ramp between."""
    y = _yarn(cfg)
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y["factor"]
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return inter * ramp + extra * (1 - ramp)


def softmax_scale(cfg: dict) -> float:
    y = _yarn(cfg)
    m = _mscale(y["factor"], y["mscale_all_dim"])
    return m * m / math.sqrt(cfg["qk_nope_head_dim"]
                             + cfg["qk_rope_head_dim"])


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, cfg):
    """x (B, S, H, rope): rotate the two halves (YaRN frequencies, cos
    and sin times mscale / mscale_all_dim)."""
    y = _yarn(cfg)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] \
        * yarn_inv_freq(cfg)[None, :]
    ms = _mscale(y["factor"], y["mscale"]) \
        / _mscale(y["factor"], y["mscale_all_dim"])
    cos = jnp.asarray(np.cos(ang) * ms, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * ms, jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, scale, mm):
    """Causal softmax attention, exact, one block of query rows at a
    time.  q, k (B, S, H, nope + rope); v (B, S, H, v)."""
    b, s, h, dq = q.shape
    blk = min(REF_Q_BLOCK, s)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    @jax.checkpoint
    def block(qb, i):
        rows = i * blk + jnp.arange(blk)
        sc = mm("bhqd,bhkd->bhqk", qb, kt) * scale
        sc = jnp.where(rows[:, None] >= jnp.arange(s)[None, :], sc,
                       -jnp.inf)
        return mm("bhqk,bhkd->bhqd", jax.nn.softmax(sc, axis=-1), vt)

    qt = jnp.swapaxes(q, 1, 2).reshape(b, h, s // blk, blk, dq)
    out = jax.lax.map(lambda a: block(*a),
                      (jnp.moveaxis(qt, 2, 0), jnp.arange(s // blk)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, s, v.shape[-1])
    return jnp.swapaxes(out, 1, 2)


def _mla(a, p, cfg, mm):
    m = dims(cfg)
    b, s, _ = a.shape
    h, nd, rd, r = m["h"], m["nope"], m["rope"], m["r"]
    eps = float(cfg["rms_norm_eps"])
    q = mm("bsd,de->bse", a, p["wq"]).reshape(b, s, h, nd + rd)
    dkv = mm("bsd,de->bse", a, p["wdkv"])
    c = _rmsnorm(dkv[..., :r], p["kv_norm"]["scale"], eps)
    k_rope = _rope(dkv[..., None, r:], cfg)
    k_nope = mm("bsr,re->bse", c, p["wuk"]).reshape(b, s, h, nd)
    v = mm("bsr,re->bse", c, p["wuv"]).reshape(b, s, h, m["v"])
    q = jnp.concatenate([q[..., :nd], _rope(q[..., nd:], cfg)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, rd))],
                        -1)
    o = _attention(q, k, v, softmax_scale(cfg), mm)
    return mm("bse,ed->bsd", o.reshape(b, s, h * m["v"]), p["wo"])


def _swiglu(f, p, mm):
    g = jax.nn.silu(mm("bsd,df->bsf", f, p["w_gate"]))
    return mm("bsf,fd->bsd", g * mm("bsd,df->bsf", f, p["w1"]), p["w2"])


def _moe(f, p, cfg, mm):
    """(output, per-sequence aux loss) of the held experts: the router's
    softmax over every routed expert, greedy top-k, the k weights as
    they are (no renormalisation; no gradient to the router through
    them where ``router_gate_grad`` is false); each held expert runs on
    every token as a dense matmul, masked by its routing weight (zero
    where the token did not choose it); the shared experts on every
    token."""
    m = dims(cfg)
    b, s, _ = f.shape
    probs = jax.nn.softmax(mm("bsd,de->bse", f, p["router"]), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m["k"])
    if not cfg["router_gate_grad"]:
        top_w = jax.lax.stop_gradient(top_w)
    chosen = jax.nn.one_hot(top_i, m["experts"], dtype=jnp.float32)
    weight = jnp.einsum("bsk,bske->bse", top_w, chosen)    # (B, S, E)
    out = _swiglu(f, p["shared"], mm)
    for j in range(m["held"]):
        e = {n: p[n][j] for n in ("w1", "w_gate", "w2")}
        out = out + weight[..., m["first"] + j, None] * _swiglu(f, e, mm)
    count = chosen.sum((1, 2))                             # (B, E)
    frac = count * m["experts"] / (m["k"] * s)
    aux = jnp.mean(jnp.sum(frac * probs.mean(1), axis=-1))
    return out, aux


def loss(params, tokens, labels, cfg: dict, mm=None):
    """Token-mean cross-entropy of the rows ``tokens`` (B, S) over the
    vocabulary slice, plus ``aux_loss_alpha`` times the MoE layers'
    per-sequence aux losses (mean over the rows), as the program adds
    them."""
    mm = mm or make_mm()
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"][tokens]

    def block(moe):
        @jax.checkpoint
        def layer(x, lp):
            x = x + _mla(_rmsnorm(x, lp["ln1"]["scale"], eps), lp["attn"],
                         cfg, mm)
            f = _rmsnorm(x, lp["ln2"]["scale"], eps)
            if moe:
                y, aux = _moe(f, lp["moe"], cfg, mm)
            else:
                y, aux = _swiglu(f, lp["mlp"], mm), jnp.zeros(())
            return x + y, aux
        return layer

    x, _ = jax.lax.scan(block(False), x, params["prefix"])
    x, aux = jax.lax.scan(block(True), x, params["body"])
    x = _rmsnorm(x, params["ln_f"]["scale"], eps)
    logits = mm("bsd,dv->bsv", x, params["lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll) + float(cfg["aux_loss_alpha"]) * jnp.sum(aux)


# ---------------------------------------------------------------------------
# operations of the new kernels, for their roofline shares
# ---------------------------------------------------------------------------

def _held_pairs(cfg: dict, batch: int, seq: int) -> float:
    m = dims(cfg)
    return batch * seq * m["k"] * m["held"] / m["experts"]


def expert_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """The held experts' grouped matmuls in one step on one chip, remat
    on: at the balanced share, T·k·held/E (token, expert) pairs a MoE
    layer, each 2·3·d·f_e in the forward, twice that in the backward and
    the forward again in the recompute: 24·d·f_e a pair."""
    m = dims(cfg)
    return m["moe"] * _held_pairs(cfg, batch, seq) * 24.0 * m["d"] \
        * m["ff"]


def expert_bytes_per_step(cfg: dict, batch: int, seq: int) -> float:
    """HBM bytes those matmuls must move at least: each of gate, up and
    down (rows P at the balanced share, weights of every held expert)
    reads its rows and weights and writes its output, in bfloat16, in
    the forward and the recompute; in the backward each reads its output
    gradient, weights and input and writes the input gradient (bfloat16)
    and the weights' gradient (float32)."""
    m = dims(cfg)
    p, d, f, g = _held_pairs(cfg, batch, seq), m["d"], m["ff"], m["held"]
    total = 0.0
    for rows_in, rows_out in ((d, f), (d, f), (f, d)):
        w = g * rows_in * rows_out
        fwd = 2.0 * (p * rows_in + w + p * rows_out)
        bwd = 2.0 * (2 * p * rows_out + w + p * rows_in + p * rows_in) \
            + 4.0 * w
        total += 2 * fwd + bwd
    return m["moe"] * total


def _attn_sizes(cfg: dict, batch: int, seq: int):
    m = dims(cfg)
    return m["dense"] + m["moe"], batch * m["h"], m["nope"] + m["rope"], \
        m["v"]


def attn_kernel_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """MLA's causal attention kernels in one step on one chip, remat on,
    over the S·(S+1)/2 visible (query, key) pairs of each head: forward
    q·kᵀ at nope + rope and p·v at v; backward dP, dV, dQ and dK and the
    scores once more (2·(v + v + qk + qk + qk) a pair); the recompute's
    forward again."""
    layers, heads, qk, v = _attn_sizes(cfg, batch, seq)
    pairs = heads * seq * (seq + 1) / 2
    fwd = 2.0 * (qk + v)
    bwd = 2.0 * (2 * v + 3 * qk)
    return layers * pairs * (2 * fwd + bwd)


def attn_kernel_bytes_per_step(cfg: dict, batch: int, seq: int) -> float:
    """HBM bytes the kernels must move at least, each of q, k, v, o, dO
    and their gradients once per pass (bfloat16) and the f32 row
    statistics: the forward (and the recompute's) reads q, k, v and
    writes o and lse; the backward reads q, k, v, dO, lse and delta and
    writes dq, dk, dv."""
    layers, heads, qk, v = _attn_sizes(cfg, batch, seq)
    rows = heads * seq
    fwd = rows * (2.0 * (2 * qk + 2 * v) + 4)
    bwd = rows * (2.0 * (2 * qk + 2 * v) + 8 + 2.0 * (2 * qk + v))
    return layers * (2 * fwd + bwd)
