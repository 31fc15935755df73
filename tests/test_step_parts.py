"""The parts of the train step (``ops/pallas/scope.py::part_scope``) in the
compiled step of each cell family, at the toy sizes of
``tests/test_benchmark_contract.py``: every instruction that has an
``op_name`` carries a ``part.`` component, and the scopes change nothing but
that metadata. Also ``benchmark/lib/scopes.py``'s joins, on a CPU trace and on
made-up events (the readers against a recorded chip trace are
``benchmark/tests/test_scopes.py``'s)."""

import contextlib
import functools
import glob
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_benchmark_contract import DATA, MODELS, TOY, _json

from lib import scopes  # noqa: E402  (``benchmark/`` is on the path: the contract test)
from pyspark_tf_gke_tpu.ops.pallas.scope import STEP_PARTS, part_scope
from pyspark_tf_gke_tpu.utils.compile_cache import key_on_names

# instructions whose ``op_name`` is not a name stack: JAX names an argument
# after its path in the state (``state.params['wte']...``), also where XLA
# bitcasts it
BOOKKEEPING = ("parameter", "bitcast")
# the modules that enter the scopes
SITES = ("pyspark_tf_gke_tpu.models.hybrid_lm", "pyspark_tf_gke_tpu.models.causal_lm",
         "pyspark_tf_gke_tpu.models.moe", "pyspark_tf_gke_tpu.train.trainer")
FAMILIES = sorted(TOY)


def compiled_step(runner):
    """The toy step of ``runner``'s family, compiled: its HLO text."""
    from pyspark_tf_gke_tpu.models import hybrid_lm
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.harness import make_optimizer
    from pyspark_tf_gke_tpu.train.trainer import Trainer, causal_lm_task

    toy, traffic = TOY[runner]
    cfg = _json(DATA, "configs", toy + ".json")
    tr = _json(DATA, "cells", f"{toy}.{traffic}.json")["train"]
    seq = int(_json(DATA, "traffic", traffic + ".json")["seq_len"])
    batch = {"input_ids": np.zeros((int(tr["rows_per_chip"]), seq), np.int32)}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hybrid_lm, "kda", functools.partial(hybrid_lm.kda, interpret=True))
        patch.setattr(hybrid_lm, "ssd", functools.partial(hybrid_lm.ssd, interpret=True))
        mesh = make_mesh(tr["mesh"], devices=jax.devices()[:1])
        trainer = Trainer(MODELS[runner](cfg, tr, mesh),
                          causal_lm_task(vocab_chunks=tr["vocab_chunks"] or None), mesh,
                          tx=make_optimizer(tr["optimizer"]["learning_rate"],
                                            optimizer=tr["optimizer"]["name"]))
        state = trainer.init_state(jax.random.PRNGKey(0), batch)
        trainer._build_steps()
        return trainer._train_step.lower(state, batch).compile().as_text()


@pytest.fixture(scope="module")
def steps():
    """``steps(runner, scoped)``: the compiled toy step, with the part scopes
    or with every site's ``part_scope`` a null context; made once."""
    made = {}

    def get(runner, scoped=True):
        if (runner, scoped) not in made:
            with pytest.MonkeyPatch.context() as patch:
                if not scoped:
                    for site in SITES:
                        patch.setattr(importlib.import_module(site), "part_scope",
                                      lambda name: contextlib.nullcontext())
                made[(runner, scoped)] = compiled_step(runner)
        return made[(runner, scoped)]

    return get


def computations(text):
    """``{name: [instruction lines]}`` and the names of those that run as
    control flow (the entry, loop bodies and conditions, branches, calls):
    the computations whose instructions are operations of the trace."""
    comps, entry, cur = {}, None, None
    for line in text.split("\n"):
        head = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif cur and line.startswith("  "):
            comps[cur].append(line)
    run, todo = {entry}, [entry]
    while todo:
        for line in comps[todo.pop()]:
            refs = re.findall(r"(?:body|condition|true_computation|false_computation)=%([\w.\-]+)",
                              line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            refs += re.findall(r"%([\w.\-]+)", branches.group(1)) if branches else []
            refs += re.findall(r"to_apply=%([\w.\-]+)", line) if " call(" in line else []
            for r in refs:
                if r in comps and r not in run:
                    run.add(r)
                    todo.append(r)
    return comps, run


def named(line):
    """``(instruction, opcode, op_name or None)`` of an instruction line."""
    inst, rest = re.match(r"\s+(?:ROOT )?%(\S+) = (.*)", line).groups()
    opcode = re.search(r"\s([a-z][\w\-]*)\(", rest)
    op_name = re.search(r'op_name="((?:[^"\\]|\\.)*)"', line)
    return inst, opcode.group(1) if opcode else "", op_name.group(1) if op_name else None


def strip(text):
    """The HLO text without what only the metadata holds: each instruction's
    ``metadata={...}`` and the module's tables of source frames."""
    out, frames = [], False
    for line in text.split("\n"):
        frames = (frames or line == "FileNames") and not line.startswith(("%", "ENTRY"))
        if not frames:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


@pytest.mark.parametrize("runner", FAMILIES)
def test_every_named_instruction_of_the_step_is_in_a_part(steps, runner):
    comps, run = computations(steps(runner))
    parts, outside = {}, {}
    for comp in run:
        for line in comps[comp]:
            inst, opcode, op_name = named(line)
            if op_name is None:
                continue
            part = scopes.part_of(op_name)
            parts[part] = parts.get(part, 0) + 1
            if part is None:
                outside.setdefault(opcode, []).append(op_name)
    print(f"{runner}: instructions by part {parts}; allowed outside every part: "
          f"{ {k: len(v) for k, v in outside.items() if k in BOOKKEEPING} }")
    stray = {k: v[:3] for k, v in outside.items() if k not in BOOKKEEPING}
    assert not stray, f"{runner}: instructions with an op_name and no part: {stray}"
    assert set(parts) - {None} <= set(STEP_PARTS)
    want = {"embed", "mixer", "ffn", "head_loss", "optimizer"}
    assert want <= set(parts), f"{runner}: no instruction of {want - set(parts)}"


@pytest.mark.parametrize("runner", ["train_kimi_linear", "train_nemotron_h", "train_afmoe"])
def test_the_walks_loops_are_the_experts_walk(steps, runner):
    """The held experts' loops, forward, rerun forward and backward (each a
    ``custom_vjp`` rule traced apart from its call), are ``experts_walk`` to
    the last instruction of their bodies."""
    comps, run = computations(steps(runner))
    loops = []
    for comp in run:
        for line in comps[comp]:
            inst, opcode, op_name = named(line)
            if opcode == "while" and scopes.part_of(op_name) == "experts_walk":
                loops.append(re.search(r"body=%([\w.\-]+)", line).group(1))
    assert loops, f"{runner}: no loop of the walk in the compiled step"
    for body in loops:
        for line in comps[body]:
            inst, opcode, op_name = named(line)
            if op_name is not None and opcode not in BOOKKEEPING:
                assert scopes.part_of(op_name) == "experts_walk", (body, inst, op_name)


@pytest.mark.parametrize("runner,rule", [("train_kimi_linear", "kda_bwd"),
                                         ("train_nemotron_h", "ssd_bwd")])
def test_the_recurrences_backward_rules_are_the_mixer(steps, runner, rule):
    comps, run = computations(steps(runner))
    seen = 0
    for comp in run:
        for line in comps[comp]:
            inst, opcode, op_name = named(line)
            if op_name and rule in op_name:
                seen += 1
                assert scopes.part_of(op_name) == "mixer", (inst, op_name)
    assert seen, f"no instruction of {rule}'s rule in the compiled step"


@pytest.mark.parametrize("runner", FAMILIES)
def test_the_scopes_change_nothing_but_metadata(steps, runner):
    scoped, plain = steps(runner), steps(runner, scoped=False)
    assert "part." in scoped and "part." not in plain
    assert strip(scoped) == strip(plain)


def test_part_scope_knows_the_steps_parts_only():
    for name in STEP_PARTS:
        with part_scope(name):
            pass
    with pytest.raises(ValueError, match="unknown step part"):
        part_scope("attention")


def test_part_of_takes_the_last_part_in_the_name_stack():
    assert scopes.part_of("jit(train_step)/jvp(HybridLM)/layer_1/part.ffn/mlp/"
                          "part.experts_walk/while/body/add") == "experts_walk"
    assert scopes.part_of("jit(train_step)/transpose(jvp(part.head_loss))/while") == "head_loss"
    assert scopes.part_of("ragged-dot-none") is None
    assert scopes.part_of(None) is None


def test_an_operation_without_a_part_takes_its_loops():
    ops = [["%fusion.1", 0, 10], ["%while.2", 10, 50], ["%ragged-dot-none.3", 12, 5],
           ["%fusion.4", 20, 5], ["%copy.5", 70, 5]]
    parts = ["mixer", "experts_walk", None, "optimizer", None]
    got = scopes.resolve(ops, parts)
    assert [op[3] for op in got] == ["mixer", "experts_walk", "experts_walk", "optimizer",
                                     scopes.UNSCOPED]
    by_part = scopes.ms_by_part({"executions": 1, "ops": got})
    # the loop's own 50 ns are not counted: only what ran in it
    assert by_part == {"mixer": 1e-5, "experts_walk": 5e-6, "optimizer": 5e-6,
                       scopes.UNSCOPED: 5e-6}
    assert scopes.ms_by_part({"executions": 1, "ops": scopes.resolve(ops, [None] * 5)}) is None


def fused_text(dot, update, xla):
    """A fusion of a matmul and an update, their parts ``dot`` and
    ``update`` (``None``: no metadata), XLA naming the fusion ``xla``."""
    def meta(part, op):
        return "" if part is None else f', metadata={{op_name="j/part.{part}/{op}"}}'

    return "\n".join([
        "%fused_computation.1 (p: f32[4], q: f32[4]) -> (f32[4], f32[4]) {",
        "  %p = f32[4]{0} parameter(0)",
        "  %q = f32[4]{0} parameter(1)",
        "  %dot.1 = f32[4]{0} multiply(%p, %q)" + meta(dot, "dot_general"),
        "  %mul.1 = f32[4]{0} multiply(%dot.1, %q)" + meta(update, "mul"),
        "  %convert.1 = f32[4]{0} convert(%mul.1)",
        "  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%convert.1, %mul.1)",
        "}",
        "ENTRY %main.2 (x: f32[4]) -> (f32[4], f32[4]) {",
        "  %x = f32[4]{0} parameter(0)",
        "  %copy.3 = f32[4]{0} copy(%x)",
        "  ROOT %fusion.2 = (f32[4]{0}, f32[4]{0}) fusion(%copy.3, %x), kind=kOutput, "
        "calls=%fused_computation.1" + meta(xla, "dot_general"),
        "}"])


@pytest.mark.parametrize("dot,update,xla,part", [
    # Adam's update fused into its gradient's matmul, which XLA names the
    # fusion after: the fusion joins two parts and is read as shared
    ("mixer", "optimizer", "mixer", "mixer+optimizer"),
    # what the fused instructions carry, not XLA's name for the fusion
    ("optimizer", "optimizer", "ffn", "optimizer"),
    # XLA's name only where no fused instruction carries one
    (None, None, "head_loss", "head_loss"),
])
def test_a_fusion_is_in_the_parts_its_instructions_carry(dot, update, xla, part):
    parts = scopes.hlo_parts(fused_text(dot, update, xla))
    assert parts["fusion.2"] == part
    assert "copy.3" not in parts            # XLA's copy: unscoped
    got = scopes.resolve([["%fusion.2", 0, 10]], [parts["fusion.2"]])
    assert scopes.ms_by_part({"executions": 1, "ops": got}) == {scopes.bucket(part): 1e-5}


def test_the_compile_cache_keys_on_the_parts():
    """JAX's persistent cache key leaves metadata out unless told: two steps
    that differ only in their part scopes would share an executable, and the
    one loaded second would read the other's names. The trainer keys on
    them (``utils/compile_cache.py::key_on_names``)."""
    import hashlib

    from jax._src import cache_key, config

    def digest(part):
        @jax.jit
        def f(x):
            with part_scope(part):
                return jnp.sin(x) * 2

        h = hashlib.sha256()
        cache_key._hash_computation(h, f.lower(jnp.ones(4)).compiler_ir(),
                                    cache_key.IgnoreCallbacks.NO)
        return h.hexdigest()

    was = config.compilation_cache_include_metadata_in_key.value
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
        assert digest("mixer") == digest("ffn")
        key_on_names()
        assert digest("mixer") != digest("ffn")
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", was)


def test_a_cpu_trace_keeps_the_programs_hlo_with_its_parts(tmp_path):
    """Source (b) of ``lib/scopes.py`` on a CPU trace: the program's HLO from
    the plane ``/host:metadata``, read by the wire format, joined to the
    operations' names. (A CPU trace has no device plane, so ``load_ops`` gives
    ``None``: the chip's is the one it reads.)"""
    @jax.jit
    def step(x):
        with part_scope("mixer"):
            y = jnp.sin(x) @ x
        with part_scope("optimizer"):
            return jnp.cos(y).sum()

    x = jnp.ones((128, 128))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
    tables = scopes.program_parts(path, ("jit_step",))
    assert len(tables) == 1
    (table,) = tables.values()
    assert {p for v in table.values() for p in v.split("+")} == {"mixer", "optimizer"}
    assert scopes.load_ops(str(tmp_path)) is None
