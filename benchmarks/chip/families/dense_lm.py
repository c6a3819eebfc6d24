"""Dense decoder-only LM (llama block: RMSNorm, RoPE, GQA, SwiGLU, tied
embeddings): how the benchmark builds the program's model from a
configuration file, makes its weights, counts its FLOPs, and computes
the plain float32 reference of its loss and gradient.

Nothing here imports the model code under test.  The reference follows
the configuration file as it is run (its ``reduced`` keys are the
program's departures from the published model, e.g. the RMSNorm epsilon
the program hard-codes).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# Query rows per block in the reference's attention: the scores of one
# block for all heads stay under a few hundred MB at 4096 positions.
REF_Q_BLOCK = 512


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"],
            "rows": cfg["embedding_rows"]}


def program_spec(cfg: dict):
    """The program's ModelSpec for this configuration."""
    from repro.models.common import ModelSpec

    prog = cfg["program"]
    spec = ModelSpec(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mlp_type="swiglu", rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        remat=bool(prog["remat"]), dtype=prog["dtype"],
        param_dtype=prog["param_dtype"])
    if spec.padded_vocab != cfg["embedding_rows"]:
        raise ValueError(f"{cfg['name']}: the program holds "
                         f"{spec.padded_vocab} embedding rows, the "
                         f"configuration says {cfg['embedding_rows']}")
    return spec


# ---------------------------------------------------------------------------
# weights: one leaf per key, so a leaf can be made again on its own
# ---------------------------------------------------------------------------

def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, std, fan-in rule), in the program's tree."""
    m = dims(cfg)
    d, L, q, kv, ff = m["d"], m["layers"], m["h"] * m["hd"], \
        m["kv"] * m["hd"], m["ff"]
    return {
        "embed": ((m["rows"], d), 0.02),
        "ln_f/scale": ((d,), 0.0),
        "body/ln1/scale": ((L, d), 0.0),
        "body/ln2/scale": ((L, d), 0.0),
        "body/attn/wq": ((L, d, q), 1 / math.sqrt(d)),
        "body/attn/wk": ((L, d, kv), 1 / math.sqrt(d)),
        "body/attn/wv": ((L, d, kv), 1 / math.sqrt(d)),
        "body/attn/wo": ((L, q, d), 1 / math.sqrt(q)),
        "body/mlp/w1": ((L, d, ff), 1 / math.sqrt(d)),
        "body/mlp/w_gate": ((L, d, ff), 1 / math.sqrt(d)),
        "body/mlp/w2": ((L, ff, d), 1 / math.sqrt(ff)),
    }


def make_leaf(key, cfg: dict, name: str):
    """One float32 leaf: normal(0, std) from its own key (zeros for the
    norm scales, which the block applies as ``1 + scale``)."""
    shapes = leaf_shapes(cfg)
    shape, std = shapes[name]
    if std == 0.0:
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, sorted(shapes).index(name))
    return jax.random.normal(k, shape, jnp.float32) * std


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def init_params(key, cfg: dict) -> dict:
    return nest({n: make_leaf(key, cfg, n) for n in leaf_shapes(cfg)})


# ---------------------------------------------------------------------------
# FLOPs the training step requires (no recomputation counted)
# ---------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul: every projection of every
    layer and the LM head over the published vocabulary (the embedding
    lookup is a gather, not a matmul)."""
    m = dims(cfg)
    q, kv = m["h"] * m["hd"], m["kv"] * m["hd"]
    per_layer = m["d"] * q + 2 * m["d"] * kv + q * m["d"] \
        + 3 * m["d"] * m["ff"]
    return m["layers"] * per_layer + m["vocab"] * m["d"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """6·N for the weights' forward and backward, plus causal attention's
    score and value matmuls at half of full: 6·L·(h·hd)·S per token."""
    m = dims(cfg)
    return 6.0 * matmul_params(cfg) \
        + 6.0 * m["layers"] * m["h"] * m["hd"] * seq


# ---------------------------------------------------------------------------
# the plain reference: float32 throughout, matmuls at HIGHEST precision
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, theta):
    """x (B, S, H, hd): rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, mm):
    """Causal softmax attention, exact, one block of query rows at a
    time.  q (B,S,H,hd); k, v (B,S,H,hd) with the kv heads repeated."""
    b, s, h, hd = q.shape
    blk = min(REF_Q_BLOCK, s)
    kt = jnp.swapaxes(k, 1, 2)                     # (B,H,S,hd)
    vt = jnp.swapaxes(v, 1, 2)

    @jax.checkpoint
    def block(qb, i):
        rows = i * blk + jnp.arange(blk)
        sc = mm("bhqd,bhkd->bhqk", qb, kt) / math.sqrt(hd)
        sc = jnp.where(rows[:, None] >= jnp.arange(s)[None, :], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("bhqk,bhkd->bhqd", p, vt)

    qt = jnp.swapaxes(q, 1, 2).reshape(b, h, s // blk, blk, hd)
    out = jax.lax.map(lambda a: block(*a),
                      (jnp.moveaxis(qt, 2, 0), jnp.arange(s // blk)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, s, hd)
    return jnp.swapaxes(out, 1, 2)


def make_mm(round_operand=None):
    """einsum at HIGHEST precision; ``round_operand`` rounds both
    operands first (the control's lower precision)."""
    def mm(eq, a, b):
        if round_operand is not None:
            a, b = round_operand(a), round_operand(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


def loss(params, tokens, labels, cfg: dict, mm=None):
    """Token-mean cross-entropy of the block of rows ``tokens`` (B, S),
    over every row of the embedding table, as the program runs it."""
    mm = mm or make_mm()
    m = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    b, s = tokens.shape
    rep = m["h"] // m["kv"]
    emb = params["embed"]
    x = emb[tokens]

    @jax.checkpoint
    def layer(x, lp):
        a = _rmsnorm(x, lp["ln1"]["scale"], eps)
        q = mm("bsd,de->bse", a, lp["attn"]["wq"]).reshape(
            b, s, m["h"], m["hd"])
        k = mm("bsd,de->bse", a, lp["attn"]["wk"]).reshape(
            b, s, m["kv"], m["hd"])
        v = mm("bsd,de->bse", a, lp["attn"]["wv"]).reshape(
            b, s, m["kv"], m["hd"])
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        o = _attention(q, k, v, mm).reshape(b, s, m["h"] * m["hd"])
        x = x + mm("bse,ed->bsd", o, lp["attn"]["wo"])
        f = _rmsnorm(x, lp["ln2"]["scale"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", f, lp["mlp"]["w_gate"]))
        u = mm("bsd,df->bsf", f, lp["mlp"]["w1"])
        return x + mm("bsf,fd->bsd", g * u, lp["mlp"]["w2"]), None

    x, _ = jax.lax.scan(layer, x, params["body"])
    x = _rmsnorm(x, params["ln_f"]["scale"], eps)
    logits = mm("bsd,vd->bsv", x, emb)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
