"""``lib/scopes.py`` and the six part readers on a recorded extract of one
``jit_train_step`` execution of ``trinity-mini.train.seq8192`` on a v5e
(``data/parts_trinity_train.json``, PR 37: the execution's loops, kernel
launches and operations of 50 us or longer with their parts, the rest summed
by part; ``.expected.json`` the readers' values on the whole execution and its
busy time, as the chip run recorded them)."""

import importlib.util
import json
import os

import pytest

from lib import scopes
from lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
EXTRACT = os.path.join(HERE, "data", "parts_trinity_train.json")
# the parts the program names (``ops/pallas/scope.py::STEP_PARTS``)
STEP_PARTS = ("embed", "mixer", "ffn", "experts_walk", "head_loss", "optimizer")
PARTS_READERS = ("experts_walk_ms.train", "ffn_ms.train", "mixer_xla_ms.train",
                 "vocab_ms.train", "optimizer_ms.train", "unscoped_ms.train",
                 "shared_ms.train")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def recorded():
    with open(EXTRACT.replace(".json", ".expected.json")) as f:
        return scopes.load_extract(EXTRACT), json.load(f)


@pytest.mark.parametrize("name", PARTS_READERS)
def test_readers_give_the_recorded_values(recorded, name):
    found, expected = recorded
    assert reader(name).read({"ops": found}) == pytest.approx(expected[name], rel=1e-9)


def kernels_ms(found):
    """The flash readers' launches in the extract, ms."""
    ops = [o for o in found["ops"] if scopes.is_kernel(
        o[0], (("flash_", "tpu_custom_call"),))]
    return sum(o[2] for o in ops) / 1e6


def test_parts_and_unscoped_are_the_executions_busy_time(recorded):
    found, expected = recorded
    by_part = scopes.ms_by_part(found)
    assert set(by_part) <= {*STEP_PARTS, scopes.SHARED, scopes.UNSCOPED}
    # every operation is in one part: the parts add up to the execution's
    # operations, which run one at a time
    assert sum(by_part.values()) == pytest.approx(expected["busy_ms"], rel=0.01)
    # the readers split the same time: the mixer's XLA work and the kernels
    # with their own readers, the FFN with the walk, the vocabulary, Adam, the
    # fusions that join parts, the rest
    readers = sum(reader(n).read({"ops": found}) for n in PARTS_READERS
                  if n != "experts_walk_ms.train")
    assert readers + kernels_ms(found) == pytest.approx(sum(by_part.values()), rel=1e-9)


def test_loops_are_not_counted_twice(recorded):
    found, expected = recorded
    loops = [o for o in found["ops"] if scopes.is_container(o[0])]
    assert loops, "the extract keeps the walk's loops"
    leaves = [o for o in found["ops"] if not scopes.is_container(o[0])]
    inside = T.inside(leaves, [tuple(o[:3]) for o in loops])
    assert inside, "operations run inside the loops"
    # a loop's own event spans what runs in it: counted, the sum would pass
    # the execution's busy time by the loops' length
    with_loops = sum(o[2] for o in found["ops"]) / 1e6 + sum(found["rest_ns"].values()) / 1e6
    assert with_loops > expected["busy_ms"] * 1.05
    assert sum(scopes.ms_by_part(found).values()) == pytest.approx(expected["busy_ms"], rel=0.01)


def test_the_walk_is_what_runs_in_the_expert_layers_loops(recorded):
    """The reader and PERF.md section 5's scratch sum (every operation inside a
    ``%while`` that holds a ``ragged-dot`` launch) agree within 5%."""
    found, expected = recorded
    leaves = [tuple(o[:3]) for o in found["ops"] if not scopes.is_container(o[0])]
    ragged = [o for o in leaves if "ragged-dot" in o[0]]
    loops = [tuple(o[:3]) for o in found["ops"] if scopes.is_container(o[0])
             and T.inside(ragged, [tuple(o[:3])])]
    kept = sum(o[2] for o in T.inside(leaves, loops)) / 1e6
    walk = reader("experts_walk_ms.train").read({"ops": found})
    # the extract sums the walk's short operations by part, not by loop
    short = found["rest_ns"].get("experts_walk", 0) / 1e6
    assert walk == pytest.approx(kept + short, rel=0.05)
    assert [o for o in found["ops"] if "ragged-dot" in o[0]
            and o[3] != "experts_walk"] == [], "every ragged-dot launch is the walk's"


def test_a_fusion_that_joins_parts_is_read_as_shared(recorded):
    """XLA fuses each weight's Adam update into its gradient's matmul: such a
    fusion is neither the layer's nor the optimizer's alone, and no part's
    reader takes it."""
    found, expected = recorded
    joined = {o[3] for o in found["ops"] if "+" in o[3]}
    assert {"mixer+optimizer", "ffn+optimizer", "ffn+mixer"} <= joined
    ms = sum(o[2] for o in found["ops"] if "+" in o[3]) / 1e6
    ms += sum(v for k, v in found["rest_ns"].items() if "+" in k) / 1e6
    assert reader("shared_ms.train").read({"ops": found}) == pytest.approx(ms, rel=1e-9)
    alone = {o[3] for o in found["ops"] if o[3] in STEP_PARTS}
    assert "optimizer" in alone, "Adam's launches of its own read as the optimizer's"


def test_a_program_without_the_scopes_reads_none():
    ops = [["%fusion.1", 0, 10, scopes.UNSCOPED], ["%while.2", 10, 5, scopes.UNSCOPED]]
    found = {"executions": 1, "modules": [["jit_train_step(1)", 0, 20]], "ops": ops,
             "rest_ns": {}}
    for name in PARTS_READERS:
        assert reader(name).read({"ops": found}) is None
    assert scopes.load_ops(os.path.join(HERE, "no_such_trace")) is None


def test_describe_parts_lists_each_parts_longest_operations(recorded):
    spec = importlib.util.spec_from_file_location(
        "describe_parts", os.path.join(BENCH, "tools", "describe_parts.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    found, _ = recorded
    top = tool.top_ops(found, 3)
    by_part = scopes.ms_by_part(found)
    for part, ops in top.items():
        assert len(ops) <= 3 and [o[1] for o in ops] == sorted((o[1] for o in ops), reverse=True)
        assert sum(o[1] for o in ops) <= by_part[scopes.bucket(part)] + 1e-9
    assert top["mixer"][0][0].startswith("%attention._causal_attend.flash_dkv")
