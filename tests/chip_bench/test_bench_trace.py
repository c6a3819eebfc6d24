"""The reduction from a profiler trace to device intervals."""
import gzip
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr

TESTDATA = os.path.join(os.path.dirname(tr.__file__), "testdata")


def test_union_length_subtract():
    u = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert u == [(0, 3), (5, 9)]
    assert tr.length(u) == 7
    assert tr.subtract(u, [(1, 2), (4, 6), (8, 20)]) == \
        [(0, 1), (2, 3), (6, 8)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_op_name():
    assert tr.op_name("%fusion.12 = bf16[4,8]{1,0} fusion(...)") == \
        "fusion.12"
    assert tr.op_name("collective-permute-start.3") == \
        "collective-permute-start.3"


# Convolutions as XLA writes them: plain, a matmul whose heads are a
# dilated spatial dimension (as on a TPU), padded and reversed (as in a
# backward pass), and strided with both dilations.
CONVS = [((2, 8, 15), (4, 8, 3), (1,), [(0, 0)], (1,), (1,)),
         ((2, 8, 15), (4, 8, 15), (14,), [(0, 0)], (15,), (1,)),
         ((3, 6, 5), (5, 6, 5), (1,), [(4, 4)], (1,), (1,)),
         ((2, 4, 9, 7), (6, 4, 3, 2), (2, 1), [(1, 2), (0, 1)], (2, 1),
          (1, 2))]


@pytest.mark.parametrize("lhs,rhs,strides,pad,ld,rd", CONVS)
def test_conv_flops_agree_with_xla(lhs, rhs, strides, pad, ld, rd):
    """Only the kernel taps that land on the input count, as XLA's own
    cost analysis counts them."""
    def f(a, b):
        return jax.lax.conv_general_dilated(a, b, strides, pad,
                                            lhs_dilation=ld,
                                            rhs_dilation=rd)
    c = jax.jit(f).lower(jnp.ones(lhs), jnp.ones(rhs)).compile()
    (flops,) = tr.read_hlo(c.as_text()).flops.values()
    assert flops == c.cost_analysis()["flops"]


def test_dot_flops():
    def f(a, b):
        return jnp.einsum("bhqd,bhkd->bhqk", a, b)
    a = jnp.ones((2, 3, 8, 16))
    text = jax.jit(f).lower(a, a).compile().as_text()
    assert list(tr.read_hlo(text).flops.values()) == [2 * 2 * 3 * 8 * 8 * 16]


def test_kinds_from_compiled_hlo():
    """A dot inside a loop body is a matmul, the loop a container, and
    an elementwise op neither."""
    w = jnp.ones((64, 64))

    def f(x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=3)[0].sum()

    text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    hlo = tr.read_hlo(text)
    kinds = hlo.kinds
    assert "matmul" in kinds.values()
    assert [n for n, k in kinds.items() if k == "container"]
    assert all(n.startswith("while") for n, k in kinds.items()
               if k == "container")
    # one execution of the body's matmul; the trace has one per step
    assert sorted(hlo.flops.values()) == [2.0 * 64 ** 3]


def test_device_reduction_on_synthetic_events():
    """Busy counts leaf ops and async collectives start to done; a loop
    op's span is not busy of itself; exposed is the collective time no
    compute covers; matmul FLOPs add up per execution."""
    dev = tr.DeviceTrace(
        "/device:TPU:0", modules=[(0.0, 100.0)],
        ops=[("while.1", 0.0, 60.0), ("fusion.1", 0.0, 20.0),
             ("convolution.2", 30.0, 40.0), ("convolution.2", 40.0, 50.0),
             ("collective-permute-start.1", 45.0, 46.0),
             ("fusion.3", 70.0, 80.0), ("cp-done.9", 89.0, 90.0),
             ("copy-start.2", 0.0, 1.0), ("copy-done.2", 99.0, 100.0)])
    kinds = {"while.1": "container", "fusion.1": "matmul",
             "convolution.2": "matmul", "fusion.3": "other",
             "collective-permute-start.1": "collective",
             "cp-done.9": "collective", "copy-start.2": "other",
             "copy-done.2": "other"}
    hlo = tr.Hlo(kinds, {"fusion.1": 5.0, "convolution.2": 3.0}, {},
                 {"collective-permute-start.1": "cp-done.9",
                  "copy-start.2": "copy-done.2"})
    r = tr.reduce_device(dev, hlo)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx(81 * ns)   # 0-20, 30-90, 99-100
    assert r["matmul_s"] == pytest.approx(40 * ns)   # fusion.1 + conv.2
    assert r["matmul_flops"] == 5.0 + 2 * 3.0
    assert r["collective_s"] == pytest.approx(45 * ns)
    assert r["exposed_s"] == pytest.approx(30 * ns)  # 50-70, 80-90
    assert r["idle"] == [(20.0, 30.0), (90.0, 99.0)]
    # an op the HLO does not name is an error, not a guess
    dev.ops.append(("fusion.77", 55.0, 56.0))
    with pytest.raises(KeyError):
        tr.reduce_device(dev, hlo)


# Traces recorded on v5e chips by record_trace.py: two steps of a
# two-layer model at smollm's widths, data parallel over the chips present
# (dp4_tiny: four chips, so the aggregation's collectives are in it;
# one_tiny: one chip).
RECORDED = sorted(f[:-len(".xplane.pb.gz")] for f in os.listdir(TESTDATA)
                  if f.endswith(".xplane.pb.gz"))


@pytest.fixture(scope="module", params=RECORDED)
def recorded(request, tmp_path_factory):
    name = request.param
    path = tmp_path_factory.mktemp("trace") / (name + ".xplane.pb")
    with gzip.open(os.path.join(TESTDATA, name + ".xplane.pb.gz"),
                   "rb") as f, open(path, "wb") as out:
        shutil.copyfileobj(f, out)
    with gzip.open(os.path.join(TESTDATA, name + ".hlo.txt.gz"),
                   "rt") as f:
        hlo = f.read()
    return str(path), hlo


def test_recorded_trace_devices_and_lines(recorded):
    path, _ = recorded
    devices, host = tr.load(path)
    n = len(devices)
    assert n in (1, 4)
    assert [d.name for d in devices] == \
        [f"/device:TPU:{i}" for i in range(n)]
    for d in devices:
        assert len(d.modules) == 2          # two traced steps
        assert d.ops
    names = {h.name for h in host}
    assert {"step", "dispatch", "sync"} <= names


# The recorded step per chip: one row of 128 tokens through two layers
# at smollm's widths, remat on.  Its matmuls need 6·N·T plus causal
# attention, 6·L·(h·hd)·S·T (N = 2 x 9,830,400 + 49152 x 960); with every
# layer's forward done again and attention's masked half counted, at
# most 2·N_layers·T and 16·L·S²·(h·hd) more.
RECORDED_FLOPS = (6 * 66_846_720 * 128 + 6 * 2 * 960 * 128 * 128,
                  6 * 66_846_720 * 128 + 2 * 2 * 9_830_400 * 128
                  + 2 * 16 * 128 * 128 * 960)


def test_recorded_trace_reduction(recorded):
    path, text = recorded
    hlo = tr.read_hlo(text)
    kinds = hlo.kinds
    devices, _ = tr.load(path)
    # every op of every device is in the HLO of the step that ran
    missing = {n for d in devices for n, _, _ in d.ops if n not in kinds}
    assert not missing, sorted(missing)[:5]
    many = len(devices) > 1
    seen = {kinds[n] for d in devices for n, _, _ in d.ops}
    assert {"matmul", "container", "other"} <= seen
    assert ("collective" in seen) == many
    starts = {n for n, k in kinds.items()
              if k == "collective" and "-start" in n}
    assert {n for n in hlo.pairs if kinds[n] == "collective"} == starts
    assert bool(starts) == many
    r = tr.reduce_trace(path, hlo, ("step", "data", "dispatch", "sync"))
    assert r["devices"] == len(devices)
    lo, hi = RECORDED_FLOPS
    for d, p in zip(devices, r["per_device"]):
        assert 0 < p["matmul_s"] < p["busy_s"] <= p["window_s"]
        assert lo < p["matmul_flops"] / len(d.modules) <= hi
        assert 0 < p["matmul_flops"] / (197e12 * p["matmul_s"]) < 1
        if many:
            assert 0 < p["exposed_s"] <= p["collective_s"] < p["busy_s"]
            # every collective is in flight from start to done on every
            # device, not for the two ops' own durations alone
            ops = sum(e - s for n, s, e in d.ops
                      if kinds[n] == "collective") * 1e-9
            assert p["collective_s"] > ops
        else:
            assert p["collective_s"] == p["exposed_s"] == 0
        idle = tr.length(p["idle"]) * 1e-9
        assert idle == pytest.approx(p["window_s"] - p["busy_s"])
    bd = r["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    times = [v for _, v in bd["device_ops"]]
    assert times == sorted(times, reverse=True)
