"""BENCHMARK.json names only files that exist, and its entries keep to
the benchmark's contract: names, units, keys, and one reader per
metric, one mix per traffic, limits for every cell."""
import json
import os
import re

import bench
import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert all(m["moves"] in e2e for m in b["per_layer"])


def test_every_entry_has_its_files():
    b = _bench()
    for c in b["configs"]:
        with open(os.path.join(tiny.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            tiny.CHIP, "families", cfg["family"] + ".py"))
    for w in b["workloads"]:
        cell = bench.load_cell(w["name"], tiny.ROOT)
        assert cell.limits
        assert set(cell.limits) <= set(bench.CHECKS)
        for lim in cell.limits.values():
            assert lim["lower"] < lim["limit"] < lim["upper"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(os.path.join(tiny.CHIP, "metrics",
                                           m["name"] + ".py"))
