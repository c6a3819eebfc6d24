"""A cell at a size the CPU runs in seconds: smollm's block and mix with
every size cut, for the harness's tests."""
import json
import os

import bench

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CHIP = os.path.join(ROOT, "benchmarks", "chip")


def cell(chips: int = 1, limits: dict | None = None,
         mix_name: str | None = None) -> bench.Cell:
    with open(os.path.join(CHIP, "configs", "smollm-360m.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               vocab_size=512, embedding_rows=512)
    mix_name = mix_name or ("dp4-b4s1024" if chips == 4
                            else "train-b6s2048")
    with open(os.path.join(CHIP, "mixes", mix_name + ".json")) as f:
        mix = json.load(f)
    mix.update(seq_len=32, pool_batches=4)
    family = bench._load_module(
        os.path.join(CHIP, "families", "dense_lm.py"), "family_dense_lm")
    e2e = [{"name": "tokens_per_s", "unit": "tokens/s"},
           {"name": "setup_s", "unit": "s"}]
    return bench.Cell(f"tiny-{chips}", chips, cfg, mix, family,
                      limits or {}, e2e, [])

