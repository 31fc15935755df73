"""``lib/spans.py`` and the nine readers that use it: on made-up events, and
on a recorded extract of a real v5e traced run of the cell with its host
lines and its ring (``data/trace_train_host.json``, ``data/ring_train.json``;
PR 25, ``tools/describe_spans.py --extract``)."""

import importlib.util
import json
import os

import pytest

from lib import spans as S
from lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
STEP = ("jit_train_step",)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# one loop thread: a fit of three steps; times in ns. The device runs step 0
# at 100-200, step 1 at 260-360 (the loop synced after step 0 and handed step
# 1 over late), step 2 at 366-466 (queued in time: the gap is the device's).
LOOP = [
    ("train.fit", 0, 1000), ("train.epoch", 10, 900),
    ("train.input_wait", 20, 10), ("train.step_dispatch", 30, 40),
    ("train.first_step_sync", 70, 140),           # returns 10 ns into the gap
    ("train.metrics_accumulate", 212, 6),
    ("train.input_wait", 220, 10), ("train.step_dispatch", 230, 28),
    ("train.input_wait", 270, 4), ("train.step_dispatch", 274, 30),
    ("train.epoch_sync", 320, 500),
]
OTHER = [("train.input_wait", 0, 5000)]           # a feeder thread's own events
HOST = {"threads": [{"name": "python3", "events": OTHER},
                    {"name": "python3", "events": LOOP}]}
DEV = {"name": "/device:TPU:0",
       "modules": [("jit_train_step(7)", 100, 100), ("jit_train_step(7)", 258, 102),
                   ("jit_add(1)", 362, 2), ("jit_train_step(7)", 364, 102)],
       "ops": [("%fusion.1", 100, 60),
               ("%attention._causal_attend.flash_fwd.1 = custom-call() tpu_custom_call", 160, 40),
               ("%fusion.1", 260, 50),
               ("%attention._causal_attend.flash_fwd.1 = custom-call() tpu_custom_call", 310, 50),
               ("%add.1", 362, 2), ("%fusion.1", 366, 40),
               ("%attention._causal_attend.flash_fwd.1 = custom-call() tpu_custom_call", 406, 60)]}
TRACE = {"devices": [DEV]}


def test_loop_thread_is_found_by_what_it_holds():
    assert S.loop_thread(HOST, "train.step_dispatch") == LOOP
    assert S.loop_thread(HOST, "engine.schedule") == []
    assert S.loop_thread(None, "train.step_dispatch") == []


def test_attribute_nested_annotations_and_a_gap_outside_every_one():
    gaps = S.device_gaps(DEV, 100, 466)
    assert gaps == [[200, 60], [360, 2], [364, 2]]
    first, second, third = S.attribute(gaps, LOOP)
    # the innermost annotation wins over the epoch and the fit around it
    assert first["at_start"] == "train.first_step_sync"
    assert first["by_label"] == {
        "train.first_step_sync": 10, "train.epoch": 6, "train.metrics_accumulate": 6,
        "train.input_wait": 10, "train.step_dispatch": 28}
    assert first["label"] == "train.step_dispatch" and first["dur_ns"] == 60
    assert second["label"] == third["label"] == "train.epoch_sync"
    # a gap that starts outside every annotation
    (lone,) = S.attribute([[2000, 50]], LOOP)
    assert lone["at_start"] == S.NONE and lone["by_label"] == {S.NONE: 50}
    # one that starts outside and runs into one
    (half,) = S.attribute([[990, 30]], [("train.fit", 1000, 100)])
    assert half["at_start"] == S.NONE and half["label"] == "train.fit"
    assert half["by_label"] == {S.NONE: 10, "train.fit": 20}


def test_host_idle_pairs_steps_with_their_dispatch_by_ordinal():
    # gap 200-260 ends at step 1, whose dispatch returns at 258: 58 of 60 are
    # the host's; step 2 was handed over at 304, before its gap began: the
    # device's own; the gap at 360 ends at another program: nobody's
    idle, host = S.host_idle_ns(DEV, STEP, LOOP, "train.step_dispatch")
    assert (idle, host) == (64, 58)
    assert reader("idle_host_share.train")({"trace": TRACE, "host": HOST}) == pytest.approx(
        100 * 58 / 64)
    # annotations that do not pair with the executions: nothing is read
    assert S.host_idle_ns(DEV, STEP, LOOP[:-4], "train.step_dispatch") is None
    assert reader("idle_host_share.train")({"trace": TRACE, "host": None}) is None


def test_dispatch_lags_are_the_clock_check():
    assert S.dispatch_lags(DEV, STEP, LOOP, "train.step_dispatch") == [70, 28, 90]


@pytest.mark.parametrize("name,want", [
    ("input_wait_ms_p50.train", 10e-6), ("dispatch_ms_p50.train", 30e-6)])
def test_annotation_medians_are_the_loop_threads(name, want):
    assert reader(name)({"host": HOST}) == pytest.approx(want)
    assert reader(name)({"host": None}) is None
    assert reader(name)({"host": {"threads": [{"name": "x", "events": OTHER}]}}) is None


@pytest.mark.parametrize("kernel,want", [("fwd", 150e-6 / 3), ("dq", None), ("dkv", None)])
def test_kernel_time_per_step_by_the_kernels_own_name(kernel, want):
    got = reader(f"flash_{kernel}_ms.train")({"trace": TRACE})
    assert got == (pytest.approx(want) if want else None)
    assert reader(f"flash_{kernel}_ms.train")({}) is None


RING = [
    {"trace_id": "a", "spans": [
        {"name": "train.init_state", "span_id": "i", "parent_id": None,
         "start": 0.0, "end": 10.0, "attrs": {}},
        {"name": "jax.trace", "span_id": "t1", "parent_id": "i",
         "start": 1.0, "end": 4.0, "attrs": {"fun": "create"}},
        {"name": "jax.lower", "span_id": "l1", "parent_id": "i",
         "start": 4.0, "end": 5.0, "attrs": {"fun": "create"}},
        {"name": "jax.compile", "span_id": "c1", "parent_id": "i",
         "start": 5.0, "end": 9.0, "attrs": {"fun": "create"}}]},
    {"trace_id": "b", "spans": [
        {"name": "train.fit", "span_id": "f", "parent_id": None,
         "start": 20.0, "end": 40.0, "attrs": {}},
        {"name": "train.epoch", "span_id": "e1", "parent_id": "f",
         "start": 21.0, "end": 30.0, "attrs": {}},
        {"name": "train.epoch", "span_id": "e2", "parent_id": "f",
         "start": 30.5, "end": 39.0, "attrs": {}},
        {"name": "jax.trace", "span_id": "t2", "parent_id": "e1",
         "start": 22.0, "end": 25.0, "attrs": {"fun": "train_step"}},
        {"name": "train.checkpoint", "span_id": "k", "parent_id": "e1",
         "start": 24.0, "end": 28.0, "attrs": {}},
        {"name": "jax.trace", "span_id": "t3", "parent_id": "k",        # overlaps t2
         "start": 24.0, "end": 26.0, "attrs": {"fun": "norms"}},
        {"name": "jax.compile", "span_id": "c2", "parent_id": "k",
         "start": 26.0, "end": 27.5, "attrs": {"fun": "norms"}}]},
    {"trace_id": "c", "spans": [                      # another plane's trace
        {"name": "serve.request", "span_id": "r", "parent_id": None,
         "start": 50.0, "end": 60.0, "attrs": {}},
        {"name": "jax.compile", "span_id": "c3", "parent_id": "r",
         "start": 51.0, "end": 59.0, "attrs": {"fun": "decode"}}]},
]


def test_ring_readers_count_nesting_once_and_only_under_train_spans():
    ctx = {"ring": RING}
    # trace: 1-4, 22-25 and 24-26 (a union: 22-26), lower 4-5
    assert reader("setup_trace_lower_s.train")(ctx) == pytest.approx(3 + 4 + 1)
    assert reader("setup_compile_load_s.train")(ctx) == pytest.approx(4 + 1.5)
    # the newest train.fit less what its epochs cover
    assert reader("fit_self_ms.train")(ctx) == pytest.approx((20 - 9 - 8.5) * 1e3)
    fit_trace = RING[1]
    epoch = fit_trace["spans"][1]
    assert S.self_seconds(fit_trace, epoch) == pytest.approx(9 - 3 - 4 + 1)   # kids overlap
    for name in ("setup_trace_lower_s.train", "setup_compile_load_s.train",
                 "fit_self_ms.train"):
        assert reader(name)({"ring": None}) is None
        assert reader(name)({"ring": [RING[2]]}) is None


def test_without_a_trace_or_a_tracer_every_loader_gives_none(tmp_path):
    assert S.load_host(str(tmp_path)) is None


# -- the recorded extract -------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    host, trace = S.load_extract(os.path.join(HERE, "data", "trace_train_host.json"))
    with open(os.path.join(HERE, "data", "ring_train.json")) as f:
        ring = json.load(f)
    with open(os.path.join(HERE, "data", "trace_train_host.expected.json")) as f:
        want = json.load(f)
    return host, trace, ring, want


def test_recorded_extract_host_lines_lie_on_the_devices_clock(recorded):
    host, trace, _, want = recorded
    dev = trace["devices"][0]
    assert dev["name"].startswith("/device:TPU:")
    loop = S.loop_thread(host, "train.step_dispatch")
    steps = T.matching(dev["modules"], STEP)
    assert len(steps) == want["steps"]
    lags = S.dispatch_lags(dev, STEP, loop, "train.step_dispatch")
    assert len(lags) == want["steps"] and min(lags) == want["min_lag_ns"] > 0
    # every step carries 24 events of each flash kernel's own name, still
    # under the method's name that the accepted roofline reader matches
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        found = T.inside(T.matching(dev["ops"], ((kernel, "tpu_custom_call"),)), steps)
        assert len(found) == 24 * want["steps"]
        assert all("_causal_attend" in e[0] for e in found)


def test_recorded_extract_gaps_and_readers(recorded):
    host, trace, ring, want = recorded
    dev = trace["devices"][0]
    loop = S.loop_thread(host, "train.step_dispatch")
    lo, hi, _ = S.program_span(dev, STEP)
    gaps = [g for g in S.attribute(S.device_gaps(dev, lo, hi), loop)
            if g["dur_ns"] >= 20_000]
    assert [[g["dur_ns"], g["label"], g["at_start"]] for g in gaps] == want["gaps"]
    assert all(g["label"] != S.NONE for g in gaps)
    # the extract pairs only its own steps: cut the annotations to them
    n = want["steps"]
    calls = S.named(loop, "train.step_dispatch")[:n]
    cut = [e for e in loop if e[0] != "train.step_dispatch"] + calls
    assert list(S.host_idle_ns(dev, STEP, cut, "train.step_dispatch")) == want["host_idle_ns"]
    ctx = {"trace": trace, "host": host, "ring": ring}
    for name, value in want["metrics"].items():
        assert reader(name)(ctx) == pytest.approx(value), name
    three = sum(want["metrics"][f"flash_{k}_ms.train"] for k in ("fwd", "dq", "dkv"))
    all_flash = T.inside(T.matching(dev["ops"], (("_causal_attend", "tpu_custom_call"),)),
                         T.matching(dev["modules"], STEP))
    assert three == pytest.approx(T.total_seconds(all_flash) * 1e3 / n)
