"""What the benchmark's per-layer readers key on is what the program writes
today: one case for each (per-layer metric, cell) pair of ``BENCHMARK.json``.

A reader (``benchmark/metrics/<name>.py``) finds its events by names the
program chooses: the jitted step's name, the Pallas kernels' names behind the
scope of the method that launches them, the trainer's annotations and ring
spans, JAX's compile spans under them, the step's counters and the parts of
the step that ``ops/pallas/scope.py::part_scope`` names. ``benchmark/tests``
checks the readers against recorded traces and is not in tier-1; nothing there
sees a rename in the program, which would read ``null`` on the chip. Here the
reader's side is its module constants, loaded from its file, and the program's
side is a trainer of the cell's family at the toy size of
``benchmark/tests/data``, built as the cell's runner builds it.
``tests/test_step_parts.py`` compiles the same toy steps and holds every
operation of them to a part.

Not asserted: ``tpu_custom_call``, the second half of every ``KERNEL`` pair.
It is the chip's name for a Mosaic call (``tests/test_tpu_compile.py`` has it
for a described v5e); off the TPU the kernels run in the Pallas interpreter.
"""

import dataclasses
import functools
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)                    # the readers import ``lib``

from lib import spans as S  # noqa: E402
from lib import trace as T  # noqa: E402
from pyspark_tf_gke_tpu.ops.pallas.scope import STEP_PARTS  # noqa: E402

STEPS = 2                                    # of the traced ``fit``
# a cell's runner -> its family's toy configuration and the toy traffic it runs under
# (the state-space scan's chunk is 128 rows: its family's toy rows hold two)
TOY = {"train": ("tiny-gpt2", "train.tiny-seq128"),
       "train_kimi_linear": ("tiny-kimi", "train.tiny-seq128"),
       "train_nemotron_h": ("tiny-nemotron", "train.tiny-seq256"),
       "train_afmoe": ("tiny-afmoe", "train.tiny-seq256")}
# Readers without module constants: the ``ctx`` keys they read. The runner
# fills each from ``Trainer.fit``'s history: a counter is the window's mean
# under its own name, ``steps`` is the window sized by ``step_time_ms``.
CTX_KEYS = {
    "mfu.train": ("steps",),
    "mfu.train.kimi-linear": ("steps", "moe_held_assignments"),
    "moe_held_tokens_per_expert.train": ("moe_held_assignments",),
    "moe_held_load_max_over_mean.train": ("moe_held_assignments", "moe_held_load_max"),
    "mfu.train.nemotron-h": ("steps", "moe_held_assignments"),
    "moe_held_tokens_per_expert.train.nemotron-h": ("moe_held_assignments",),
    "moe_held_load_max_over_mean.train.nemotron-h": ("moe_held_assignments",
                                                     "moe_held_load_max"),
    "mfu.train.afmoe": ("steps", "moe_held_assignments"),
    "moe_held_tokens_per_expert.train.afmoe": ("moe_held_assignments",),
    "moe_held_load_max_over_mean.train.afmoe": ("moe_held_assignments", "moe_held_load_max"),
}
FROM_HISTORY = {"steps": "step_time_ms"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = _json(ROOT, "BENCHMARK.json")
CASES = [(m["name"], w) for m in BENCHMARK["per_layer"]
         for w in m.get("workloads") or [c["name"] for c in BENCHMARK["workloads"]]]


def reader(metric):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "contract_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(path) as f:
        return mod, f.read()


def _gpt2_model(cfg, tr, mesh):
    from pyspark_tf_gke_tpu.models.causal_lm import CausalLM, CausalLMConfig

    return CausalLM(CausalLMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
        max_seq_len=cfg["n_positions"], layer_norm_eps=cfg["layer_norm_epsilon"],
        dtype=jnp.bfloat16, remat=bool(tr["remat"]), use_flash=True), mesh=mesh)


def _hybrid_model(cfg, tr, mesh):
    """Any family of ``models/hybrid_lm.py``, by the file's ``model_type``."""
    from pyspark_tf_gke_tpu.models.hybrid_lm import HybridLM, config_from_file

    mcfg = config_from_file(cfg, dtype=jnp.bfloat16, remat=bool(tr["remat"]))
    return HybridLM(dataclasses.replace(mcfg, use_flash=True), mesh=mesh)


MODELS = {"train": _gpt2_model, "train_kimi_linear": _hybrid_model,
          "train_nemotron_h": _hybrid_model, "train_afmoe": _hybrid_model}


def written(runner, trace_dir):
    """What a trainer of ``runner``'s family writes, by the runner's own lines
    (``benchmark/runners/<runner>.py::build``) at the toy size. Off the TPU
    no decoder takes its kernels unasked, so the configuration says
    ``use_flash`` (flash then interprets by itself) and ``kda`` and ``ssd``
    are told to interpret through the names ``models/hybrid_lm.py`` imports."""
    from pyspark_tf_gke_tpu.models import hybrid_lm
    from pyspark_tf_gke_tpu.obs.trace import TraceRecorder
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.harness import make_optimizer
    from pyspark_tf_gke_tpu.train.trainer import Trainer, causal_lm_task

    toy, traffic = TOY[runner]
    cfg = _json(DATA, "configs", toy + ".json")
    cell = _json(DATA, "cells", f"{toy}.{traffic}.json")
    mix = _json(DATA, "traffic", traffic + ".json")
    tr = cell["train"]
    rows, seq = int(tr["rows_per_chip"]), int(mix["seq_len"])

    def batches():
        rng = np.random.default_rng(0)
        while True:
            yield {"input_ids": rng.integers(0, cfg["vocab_size"], (rows, seq), dtype=np.int32)}

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hybrid_lm, "kda", functools.partial(hybrid_lm.kda, interpret=True))
        patch.setattr(hybrid_lm, "ssd", functools.partial(hybrid_lm.ssd, interpret=True))
        mesh = make_mesh(tr["mesh"], devices=jax.devices()[:1])
        tracer = TraceRecorder()
        trainer = Trainer(MODELS[runner](cfg, tr, mesh),
                          causal_lm_task(vocab_chunks=tr["vocab_chunks"] or None), mesh,
                          tx=make_optimizer(tr["optimizer"]["learning_rate"],
                                            optimizer=tr["optimizer"]["name"]),
                          tracer=tracer)
        feed = batches()
        state = trainer.init_state(jax.random.PRNGKey(0), next(feed))
        # The per-step annotations go to the profiler's trace alone
        # (``obs.trace.annotate``). A profiler session of the host, started
        # as the runner starts its own (``lib/trace.py::start``), sees them
        # on a CPU, and the readers' own loader reads them back.
        T.start(trace_dir)
        try:
            state, history = trainer.fit(state, feed, epochs=1, steps_per_epoch=STEPS,
                                         prefetch=int(mix["prefetch"]))
            jax.block_until_ready(state.params)
        finally:
            jax.profiler.stop_trace()
        lowered = trainer._train_step.lower(state, next(feed)).as_text(debug_info=True)
    return {
        # as the trace's ``XLA Modules`` line names an execution
        "programs": [(name, 0, 0) for name in re.findall(r"module @(\S+)", lowered)],
        "parts": set(re.findall(r"part\.(\w+)", lowered)),
        "kernels": [(name, 0, 0) for name in launches(lowered)],
        "host": S.load_host(trace_dir),
        "ring": tracer.traces(limit=1 << 20),
        "history": history,
    }


def launches(lowered):
    """Names of the kernel launches in a lowered step. XLA names a Mosaic call
    after the innermost scope at the launch: the component before
    ``pallas_call`` in the launch's location (found by hand: a regular
    expression with a free start is quadratic in a text of megabytes)."""
    names, end = set(), lowered.find('/pallas_call"')
    while end >= 0:
        start = max(lowered.rfind('"', 0, end), lowered.rfind("/", 0, end)) + 1
        names.add(lowered[start:end])
        end = lowered.find('/pallas_call"', end + 1)
    return sorted(names)


@pytest.fixture(scope="module")
def family(tmp_path_factory):
    """``family(runner)``: what that runner's trainer writes, made once."""
    made = {}

    def get(runner):
        if runner not in TOY:
            pytest.fail(f"no toy configuration for runner {runner!r}: add one to TOY and MODELS")
        if runner not in made:
            made[runner] = written(runner, str(tmp_path_factory.mktemp(runner)))
        return made[runner]

    return get


@pytest.mark.parametrize("metric,cell", CASES, ids=[f"{m}-{c}" for m, c in CASES])
def test_reader_keys_on_what_the_program_writes(metric, cell, family):
    mod, source = reader(metric)
    wrote = family(_json(BENCH, "cells", cell + ".json")["runner"])
    checked = 0
    if hasattr(mod, "PROGRAM"):
        checked += 1
        assert T.matching(wrote["programs"], mod.PROGRAM), (
            f"{metric}: no program named like {mod.PROGRAM} among {wrote['programs']}")
    for name, _ in getattr(mod, "KERNEL", ()):
        checked += 1
        assert T.matching(wrote["kernels"], (name,)), (
            f"{metric}: no kernel launch named like {name!r} among "
            f"{[k[0] for k in wrote['kernels']]}")
    for const in ("ANNOTATION", "DISPATCH"):
        if hasattr(mod, const):
            checked += 1
            name = getattr(mod, const)
            loop = S.loop_thread(wrote["host"], getattr(mod, "LOOP", name))
            assert len(S.named(loop, name)) == STEPS, (
                f"{metric}: {STEPS} steps left {len(S.named(loop, name))} {name!r} "
                f"annotations on the loop thread, which holds {sorted({e[0] for e in loop})}")
    if hasattr(mod, "ROOT"):
        checked += 1
        assert S.last_root(wrote["ring"], mod.ROOT), (
            f"{metric}: no root span {mod.ROOT!r} in the trainer's ring")
    if hasattr(mod, "EPOCH"):
        checked += 1
        trace, root = S.last_root(wrote["ring"], mod.ROOT)
        epoch = [s for s in S.children(trace, root) if s["name"] == mod.EPOCH][-1]
        assert {mod.REAL, mod.WALKED} <= set(epoch["attrs"]), (
            f"{metric}: the last {mod.EPOCH!r} span holds {sorted(epoch['attrs'])}")
    parts = set(getattr(mod, "PARTS", ()))
    if parts:
        # each part is the program's, each family has one of them at least
        # (Nemotron's and GPT-2's FFN has no walk), and some family every one
        checked += 1
        assert parts <= set(STEP_PARTS), f"{metric}: {parts} are not all of {STEP_PARTS}"
        assert parts & wrote["parts"], (
            f"{metric}: none of {parts} in the lowered step, which has {sorted(wrote['parts'])}")
        cells = next(m.get("workloads") or [c["name"] for c in BENCHMARK["workloads"]]
                     for m in BENCHMARK["per_layer"] if m["name"] == metric)
        seen = set().union(*(family(_json(BENCH, "cells", c + ".json")["runner"])["parts"]
                             for c in cells))
        assert parts <= seen, f"{metric}: no family of its cells has part.{parts - seen}"
    for name in getattr(mod, "SPANS", ()):
        checked += 1
        assert S.union_under(wrote["ring"], (name,), mod.UNDER) is not None, (
            f"{metric}: no {name!r} span under a {mod.UNDER!r} span in the trainer's ring")
    for key in CTX_KEYS.get(metric, ()):
        checked += 1
        wanted = FROM_HISTORY.get(key, key)
        assert f'"{key}"' in source, f"{metric} no longer reads {key!r}: correct CTX_KEYS"
        assert len(wrote["history"].get(wanted, ())) == 1, (
            f"{metric}: fit's history has no {wanted!r} for ctx[{key!r}]: "
            f"{sorted(wrote['history'])}")
    assert checked, (f"{metric} has no module constant this test knows and no entry "
                     f"in CTX_KEYS: nothing holds the program to what it reads")
