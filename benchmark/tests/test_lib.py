"""The yardstick's own arithmetic: FLOP and byte counts against hand counts,
traffic and weights from the seed, the trace reduction on a small recorded
trace, the comparison's leaf rule."""

import json
import os

import numpy as np
import pytest

from lib import checks, flops, peaks, traffic, weights
from lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,published", [("gpt2-medium", 354_823_168)])
def test_param_count_matches_the_published_model(name, published):
    c = cfg(name)
    assert flops.param_count(c, tied_head=True) == published
    h, v = c["n_embd"], c["vocab_size"]
    assert flops.param_count(c) == published + h * v + v
    # the benchmark's weight generator makes exactly those leaves
    shapes = weights.gpt2_leaf_shapes(c)
    assert sum(int(np.prod(s)) for s in shapes.values()) == flops.param_count(c)


@pytest.mark.parametrize("name,h,layers", [("gpt2-medium", 1024, 24)])
def test_flop_counts_against_hand_counts(name, h, layers):
    c = cfg(name)
    ctx = 512.0
    hand = (2 * layers * (4 * h * h + 2 * h * 4 * h)      # projections + FFN
            + 4 * layers * h * ctx                        # QK^T and PV
            + 2 * h * 50257)                              # LM head
    assert flops.forward_flops_token(c, ctx) == pytest.approx(hand)
    assert flops.train_flops_token(c, 1024) == pytest.approx(
        3 * flops.forward_flops_token(c, 512.5))
    # causal flash: 7 matmuls of 2*B*S*S*h FLOPs, halved by the mask
    assert flops.flash_attention_flops(c, 8, 1024) == pytest.approx(
        7 * 2 * 8 * 1024 * 1024 * h / 2)
    # forward reads Q, K, V and writes O; backward reads 5 and writes 3; bf16
    assert flops.flash_attention_bytes(c, 8, 1024) == pytest.approx(12 * 8 * 1024 * h * 2)


def test_gpt2_medium_train_flops_value():
    assert flops.train_flops_token(cfg("gpt2-medium"), 1024) == pytest.approx(2.272e9, rel=2e-3)


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def test_train_batches_rows_all_differ():
    it = traffic.TrainFeed({"seq_len": 16}, 5, 100, 4)
    a, b = next(it)["input_ids"], next(it)["input_ids"]
    it.rewind(0)
    assert np.array_equal(next(it)["input_ids"], a)
    assert a.shape == (4, 16) and a.dtype == np.int32
    assert len({tuple(r) for r in np.concatenate([a, b])}) == 8


def test_weights_are_a_function_of_seed_and_name():
    import jax

    shapes = {"layer_3/mlp_in/kernel": (8, 16), "layer_3/ln_mlp/scale": (8,),
              "layer_3/mlp_in/bias": (16,)}
    a = jax.jit(lambda k: weights.make_leaves(k, shapes))(weights.seed_key(2 ** 31 + 5))
    b = jax.jit(lambda k: weights.make_leaves(k, shapes))(weights.seed_key(2 ** 31 + 5))
    c = jax.jit(lambda k: weights.make_leaves(k, shapes))(weights.seed_key(2 ** 31 + 6))
    for n in shapes:
        assert np.array_equal(a[n], b[n]) and not np.array_equal(a[n], c[n])
    assert abs(float(a["layer_3/ln_mlp/scale"].mean()) - 1.0) < 0.05
    # one leaf made alone (as the reference makes a layer) is the same leaf
    key = weights.seed_key(2 ** 31 + 5)
    alone = jax.jit(lambda key, tag: weights.make_leaf(key, "mlp_in/kernel", tag, (8, 16)))(
        key, weights.name_tag("layer_3/mlp_in/kernel"))
    assert np.array_equal(alone, a["layer_3/mlp_in/kernel"])
    assert weights.flatten(weights.nest(a)).keys() == a.keys()


def test_trace_arithmetic_on_made_up_events():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5)]
    assert T.union_seconds(ev) == pytest.approx(20e-9)
    assert T.total_seconds(ev) == pytest.approx(25e-9)
    assert T.matching(ev, ("b", "c")) == ev[1:]
    assert T.inside(ev, [("m", 4, 8)]) == [ev[1]]
    tr = {"devices": [{"name": "/device:TPU:0", "modules": [("jit_f(1)", 0, 40)], "ops": ev}]}
    assert T.busy_seconds(tr) == pytest.approx(20e-9)
    assert T.span_seconds(tr) == pytest.approx(40e-9)
    assert T.top_ops(tr, 2)[0][0] in ("a", "b")
    assert T.idle_gaps(tr)[0] == ["before:jit_f(1)", pytest.approx(15e-9)]


def test_device_idle_train_is_read_on_the_device_clock():
    """Idle is what lies between the first train step's start and the last
    one's end; what comes before and after (the profiler) is not in it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "device_idle_train", os.path.join(BENCH, "metrics", "device_idle.train.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    dev = {"name": "/device:TPU:0",
           "modules": [("jit_other(1)", 0, 50), ("jit_train_step(2)", 1000, 100),
                       ("jit_train_step(2)", 1120, 100)],
           "ops": [("x", 0, 50), ("a", 1000, 100), ("b", 1120, 80), ("c", 1200, 20)]}
    assert mod.read({"trace": {"devices": [dev]}}) == pytest.approx(100 * 20 / 220)
    dev["modules"] = dev["modules"][:2]
    assert mod.read({"trace": {"devices": [dev]}}) is None     # one step: no span
    assert mod.read({}) is None


@pytest.mark.parametrize("name", ["train"])
def test_trace_reduction_on_a_recorded_trace(name):
    """A small extract of a real TPU v5e trace of the cell (PR 24)."""
    path = os.path.join(HERE, "data", f"trace_{name}.json")
    tr = T.load_extract(path)
    dev = tr["devices"][0]
    assert dev["name"].startswith("/device:TPU:")
    with open(os.path.join(HERE, "data", f"trace_{name}.expected.json")) as f:
        want = json.load(f)
    assert T.busy_seconds(tr) == pytest.approx(want["busy_s"])
    assert T.span_seconds(tr) == pytest.approx(want["span_s"])
    mods = T.matching(dev["modules"], want["program"])
    assert len(mods) == want["program_runs"]
    kernels = T.inside(T.matching(dev["ops"], want["kernel"]), mods)
    assert len(kernels) == want["kernel_calls"]
    assert T.total_seconds(kernels) == pytest.approx(want["kernel_s"])
    assert 0 < T.busy_seconds(tr) <= T.span_seconds(tr)


def test_worst_leaf_gap_uses_the_median_leaf_for_tiny_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    assert checks.worst_leaf_gap(prog, ref) == pytest.approx(0.1)
    assert checks.moving_leaves({"a": 1.0, "b": 2.0, "c": 1e-9}) == ["a", "b"]
    rows = checks.compare({"x": 0.5, "y": float("nan")}, {"x": 1.0, "y": 1.0})
    assert [r["ok"] for r in rows] == [True, False] and not checks.verdict(rows)
    with pytest.raises(KeyError):
        checks.compare({"z": 0.0}, {})
