"""Config exactness vs the assignment table + input_specs shapes."""
import jax.numpy as jnp
import pytest

from repro.configs import (SHAPES, get_spec, input_specs, list_archs,
                           long500k_policy, shape_supported)

# (layers, d_model, heads, kv, d_ff, vocab) straight from the assignment
ASSIGNED = {
    "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
    "gemma-7b": (28, 3072, 16, 16, 24576, 256000),
    "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
    "deepseek-v2-lite-16b": (27, 2048, 16, 16, 1408, 102400),
    "smollm-360m": (32, 960, 15, 5, 2560, 49152),
    "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064),
    "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
    "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
    "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
}


@pytest.mark.parametrize("arch", list_archs())
def test_assigned_numbers(arch):
    spec = get_spec(arch)
    L, d, h, kv, ff, v = ASSIGNED[arch]
    assert spec.num_layers == L
    assert spec.d_model == d
    assert spec.num_heads == h
    assert spec.num_kv_heads == kv
    assert spec.d_ff == ff
    assert spec.vocab_size == v


def test_special_fields():
    ds = get_spec("deepseek-v2-lite-16b")
    assert ds.attention_type == "mla" and ds.kv_lora_rank == 512
    assert ds.num_experts == 64 and ds.top_k == 6
    assert ds.num_shared_experts == 2
    z = get_spec("zamba2-1.2b")
    assert z.ssm_state == 64 and z.family == "hybrid"
    g = get_spec("gemma-7b")
    assert g.head_dim == 256 and g.mlp_type == "geglu"
    # DeepSeek-V2-Lite's config.json: softmax router, top-6 weights as
    # they are, per-sequence aux loss at 0.001, YaRN x40 over 4096
    # positions (mscale = mscale_all_dim = 0.707), RMSNorm on the latent
    assert not ds.norm_topk_prob and ds.router_aux == "seq"
    assert ds.router_aux_weight == 0.001
    y = ds.rope_scaling
    assert (y.factor, y.original_max_position, y.beta_fast,
            y.beta_slow) == (40.0, 4096, 32.0, 1.0)
    assert y.mscale == y.mscale_all_dim == 0.707
    assert ds.held_experts == 64 and ds.expert_first == 0
    from repro.models.attention import mla_params
    import jax
    shapes = jax.eval_shape(lambda k: mla_params(k, ds),
                            jax.random.PRNGKey(0))
    assert shapes["kv_norm"]["scale"].shape == (512,)
    gm = get_spec("granite-moe-1b-a400m")
    assert gm.num_experts == 32 and gm.top_k == 8
    # granite-moe keeps its renormalised top-k and the switch loss
    assert gm.norm_topk_prob and gm.router_aux == "switch"
    assert gm.rope_scaling is None
    w = get_spec("whisper-tiny")
    assert w.encoder_layers == 4 and w.encoder_seq == 1500
    x = get_spec("xlstm-350m")
    assert x.slstm_every == 8


def test_shapes_table():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1


@pytest.mark.parametrize("arch", list_archs())
def test_input_specs_train(arch):
    spec = get_spec(arch)
    s = input_specs(spec, "train_4k")
    assert s["tokens"].shape == (256, 4096)
    assert s["labels"].shape == (256, 4096)
    assert s["tokens"].dtype == jnp.int32
    if spec.family == "audio":
        assert s["frames"].shape == (256, 1500, 384)
    if spec.family == "vlm":
        assert s["patches"].shape == (256, 576, 3072)


def test_long500k_policy():
    assert long500k_policy(get_spec("xlstm-350m")) == "native"
    assert long500k_policy(get_spec("zamba2-1.2b")) == "native"
    assert long500k_policy(get_spec("deepseek-v2-lite-16b")) == "native"
    assert long500k_policy(get_spec("gemma-7b")) == "window"
    for a in ("granite-3-2b", "smollm-360m", "phi-3-vision-4.2b",
              "whisper-tiny", "deepseek-7b"):
        ok, why = shape_supported(get_spec(a), "long_500k")
        assert not ok and "full-attention" in why


def test_decode_input_specs_are_structs():
    import jax
    spec = get_spec("granite-3-2b")
    s = input_specs(spec, "decode_32k")
    assert s["tokens"].shape == (128, 1)
    leaves = jax.tree_util.tree_leaves(s["cache"])
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    # body kv cache: (layers, batch, seq, kv, head_dim)
    assert s["cache"]["body"]["k"].shape == (40, 128, 32768, 8, 64)


def test_padded_vocab():
    assert get_spec("granite-3-2b").padded_vocab == 49408
    assert get_spec("gemma-7b").padded_vocab == 256000
