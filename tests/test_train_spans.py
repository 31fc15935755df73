"""The program's span primitive (``obs/trace.py``: ``span``, ``annotate``,
``record_span``, the process-default tracer), its callers (the trainer's
``train.*`` spans, JAX's compile listener in ``obs/compiles.py``,
``StepRecord.phase``) and the Pallas kernels' own names
(``ops/pallas/scope.py``)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.obs import compiles
from pyspark_tf_gke_tpu.obs.events import EventLog
from pyspark_tf_gke_tpu.obs.metrics import MetricsRegistry, get_registry
from pyspark_tf_gke_tpu.obs.stepstats import PHASES, StepStatsRing
from pyspark_tf_gke_tpu.obs.trace import (
    TraceRecorder,
    annotate,
    current_span,
    get_tracer,
    set_tracer,
    span,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the primitive ---------------------------------------------------------------


def spans_of(tracer):
    return [s for t in tracer.traces(limit=1 << 20) for s in t["spans"]]


def test_span_writes_a_child_under_the_current_span():
    tracer = TraceRecorder()
    with span("root", tracer=tracer, attrs={"k": 1}) as root:
        assert current_span() is root
        with span("kid") as kid:                 # no tracer: the parent's ring
            assert kid.parent_id == root.span_id and current_span() is kid
        assert current_span() is root
    assert current_span() is None
    got = {s["name"]: s for s in spans_of(tracer)}
    assert got["kid"]["parent_id"] == got["root"]["span_id"]
    assert got["root"]["attrs"] == {"k": 1} and got["root"]["parent_id"] is None


def test_span_without_parent_or_tracer_writes_nothing():
    before = len(spans_of(get_tracer()))
    with span("nobody") as sp:
        assert sp is None and current_span() is None
    assert len(spans_of(get_tracer())) == before


def test_span_marks_an_error_and_still_closes():
    tracer = TraceRecorder()
    with pytest.raises(ValueError):
        with span("boom", tracer=tracer):
            raise ValueError("x")
    (got,) = spans_of(tracer)
    assert got["attrs"]["status"] == "error:ValueError" and got["end"] >= got["start"]


def test_annotate_only_call_sites_leave_the_ring_unchanged_over_1000_steps():
    tracer = TraceRecorder()
    with span("fit", tracer=tracer):
        live = tracer._live[current_span().trace_id]
        for _ in range(1000):
            with annotate("train.input_wait"):
                pass
            with annotate("train.step_dispatch"):
                pass
        assert live["spans"] == [] and live["open"] == 1
    assert [s["name"] for s in spans_of(tracer)] == ["fit"]


def test_record_span_takes_a_finished_span_with_its_own_clock():
    tracer = TraceRecorder()
    with span("root", tracer=tracer) as root:
        tracer.record_span("late", 100.0, 102.5, root, {"fun": "f"})
    late = next(s for s in spans_of(tracer) if s["name"] == "late")
    assert (late["start"], late["end"], late["duration_ms"]) == (100.0, 102.5, 2500.0)
    assert late["parent_id"] == root.span_id and late["attrs"] == {"fun": "f"}


def test_default_tracer_is_one_bounded_ring_per_process():
    old = get_tracer()
    try:
        set_tracer(None)
        fresh = get_tracer()
        assert fresh is get_tracer() and fresh is not old
        assert fresh.enabled and fresh.max_traces == 256
    finally:
        set_tracer(old)


def test_obs_trace_imports_and_annotates_with_jax_absent():
    """The router's stance: the module is loaded from its file (the package
    root imports jax for its own reasons) and never pulls jax in."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('obs_trace', sys.argv[1])\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['obs_trace'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "tracer = mod.TraceRecorder()\n"
        "with mod.span('root', tracer=tracer):\n"
        "    with mod.annotate('x'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print(len(tracer.traces()))\n")
    path = os.path.join(ROOT, "pyspark_tf_gke_tpu", "obs", "trace.py")
    out = subprocess.run([sys.executable, "-c", code, path], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1"


# -- the trainer -----------------------------------------------------------------


@pytest.fixture()
def lm(devices, tmp_path):
    """A tiny causal LM trainer with a tracer, registry and trail of its own."""
    from pyspark_tf_gke_tpu.models.causal_lm import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.trainer import Trainer, causal_lm_task

    mesh = make_mesh({"dp": 1}, devices=devices[:1])
    cfg = CausalLMConfig(vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
                         intermediate_size=64, max_seq_len=16, dtype=jnp.float32)
    tracer, registry = TraceRecorder(), MetricsRegistry()
    events = EventLog(str(tmp_path / "events.jsonl"))
    trainer = Trainer(CausalLM(cfg, mesh=mesh), causal_lm_task(), mesh,
                      metrics_registry=registry, event_log=events, tracer=tracer)

    def feed(rows=4, seq=16):
        rng = np.random.default_rng(0)
        while True:
            yield {"input_ids": rng.integers(0, 64, (rows, seq), dtype=np.int32)}

    return trainer, tracer, registry, events, feed


def test_fit_leaves_fit_over_epochs_with_counts_and_self_time(lm):
    trainer, tracer, registry, _, feed = lm
    it = feed()
    state = trainer.init_state(jax.random.PRNGKey(0), next(it))
    saves = []

    class Saver:
        def maybe_save(self, state, history):
            saves.append(len(history["loss"]))

    trainer.fit(state, it, epochs=2, steps_per_epoch=3, checkpoint_manager=Saver(),
                val_batches=lambda: [next(it)])
    trace = tracer.traces()[-1]
    by_name = {}
    for s in trace["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    (fit,) = by_name["train.fit"]
    epochs = sorted(by_name["train.epoch"], key=lambda s: s["start"])
    assert fit["parent_id"] is None and len(epochs) == 2 and saves == [1, 2]
    for ep in epochs:
        assert ep["parent_id"] == fit["span_id"]
        assert {"steps", "rows", "input_wait_ms", "dispatch_ms", "sync_ms"} <= set(ep["attrs"])
        assert ep["attrs"]["steps"] == 3 and ep["attrs"]["rows"] == 12
        phases = sum(ep["attrs"][k] for k in ("input_wait_ms", "dispatch_ms", "sync_ms"))
        assert 0 < phases <= ep["duration_ms"] + 1e-6
    for name in ("train.validate", "train.checkpoint"):
        assert sorted(s["parent_id"] for s in by_name[name]) == sorted(
            e["span_id"] for e in epochs)
    # what lies outside the epochs is the fit's own: sum(epochs) + self = fit
    covered = sum(e["end"] - e["start"] for e in epochs)
    self_s = (fit["end"] - fit["start"]) - covered
    assert epochs[0]["start"] >= fit["start"] and epochs[-1]["end"] <= fit["end"]
    assert epochs[0]["end"] <= epochs[1]["start"] and self_s > 0
    # the compile of the step hangs under the first epoch, with its name
    compiled = [s for s in by_name["jax.compile"] if s["attrs"]["fun"] == "train_step"]
    assert [s["parent_id"] for s in compiled] == [epochs[0]["span_id"]]


def test_input_wait_histogram_counts_one_observation_per_step(lm):
    trainer, _, registry, _, feed = lm
    it = feed()
    state = trainer.init_state(jax.random.PRNGKey(0), next(it))
    trainer.fit(state, it, epochs=2, steps_per_epoch=4)
    assert registry.get("train_input_wait_ms").count == 8
    state = trainer.init_state(jax.random.PRNGKey(0), next(it))
    trainer.fit(state, it, epochs=1, steps_per_epoch=3, grad_accum=2)
    assert registry.get("train_input_wait_ms").count == 11


def test_compile_under_init_state_is_its_descendant_and_a_bare_one_is_not(lm):
    trainer, tracer, _, _, feed = lm
    trainer.init_state(jax.random.PRNGKey(0), next(feed()))
    (trace,) = [t for t in tracer.traces() if any(
        s["name"] == "train.init_state" for s in t["spans"])]
    root = next(s for s in trace["spans"] if s["name"] == "train.init_state")
    kids = [s for s in trace["spans"] if s["parent_id"] == root["span_id"]]
    assert {"jax.trace", "jax.lower", "jax.compile"} <= {s["name"] for s in kids}
    assert all(s["attrs"]["fun"] and "(" not in s["attrs"]["fun"] for s in kids)
    # no program span around it: counted, but no span anywhere
    n_spans = len(spans_of(tracer)) + len(spans_of(get_tracer()))
    counter = get_registry().get(compiles.COUNTER)

    def bare_program(x):
        return x * 3 + 1

    before = counter.labels("bare_program").value
    jax.jit(bare_program)(jnp.ones((3,)))
    assert counter.labels("bare_program").value == before + 1
    assert len(spans_of(tracer)) + len(spans_of(get_tracer())) == n_spans


def test_only_the_outermost_trace_leaves_a_span():
    compiles.install_compile_listener()
    tracer = TraceRecorder()

    def outer_program(x):
        return jax.jit(lambda y: y + 1)(x) * jnp.sin(x)      # jits traced inside

    x = jnp.ones((5,))             # made out here: eager ops trace programs too
    with span("root", tracer=tracer):
        jax.jit(outer_program)(x)
    traced = [s for s in spans_of(tracer) if s["name"] == "jax.trace"]
    assert [s["attrs"]["fun"] for s in traced] == ["outer_program"]


def test_a_second_compile_of_the_step_inside_an_epoch_emits_train_recompile(lm):
    trainer, _, _, events, feed = lm

    def two_shapes():
        for rows in (4, 4, 8, 8):                # the third step meets a new shape
            yield next(feed(rows=rows))

    it = two_shapes()
    state = trainer.init_state(jax.random.PRNGKey(0), next(feed()))
    trainer.fit(state, it, epochs=1, steps_per_epoch=4, prefetch=0)
    got = [e for e in events.tail(100) if e["kind"] == "train_recompile"]
    assert len(got) == 1
    assert got[0]["fun"] == "train_step" and got[0]["global_step"] == 2
    assert got[0]["seconds"] > 0


@pytest.mark.parametrize("label,want", [
    ("jit(train_step)", "train_step"), ("train_step", "train_step"),
    ("pmap(step)", "step"), ("jit(<lambda>)", "<lambda>"), (None, "unknown")])
def test_fun_label_strips_the_wrapper(label, want):
    assert compiles.fun_label(label) == want


# -- the engine's phases ------------------------------------------------------------


class _StubClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_step_record_phase_keeps_exclusive_time_with_the_annotation():
    clock = _StubClock()
    ring = StepStatsRing(capacity=4, clock=clock)
    rec = ring.begin()
    with rec.phase("schedule"):
        clock.advance(0.002)
        with rec.phase("dispatch"):
            clock.advance(0.003)
            with rec.phase("device_wait"):
                clock.advance(0.050)
        clock.advance(0.001)
    with pytest.raises(RuntimeError):
        with rec.phase("collect"):
            clock.advance(0.004)
            raise RuntimeError("the phase still closes")
    assert ring.close(rec)
    assert rec.phases == pytest.approx(
        {"schedule": 3.0, "dispatch": 3.0, "device_wait": 50.0, "collect": 4.0})
    assert sum(rec.phases.values()) == pytest.approx(rec.wall_ms)
    assert set(rec.phases) <= set(PHASES)


# -- kernel names ---------------------------------------------------------------------


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _flash(grad):
    from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.ones((1, 32, 2, 8), jnp.float32)

    def fwd(q):
        return flash_attention(q, q, q, causal=True, block_q=16, block_k=16,
                               interpret=True).sum()

    return _lowered(jax.grad(fwd) if grad else fwd, q)


def _layernorm():
    from pyspark_tf_gke_tpu.ops.pallas.layernorm import fused_layernorm

    return _lowered(lambda x: fused_layernorm(x, jnp.ones((16,)), jnp.zeros((16,)),
                                              interpret=True), jnp.ones((8, 16)))


def _paged():
    from pyspark_tf_gke_tpu.ops.pallas.paged_attention import paged_attention

    kp = jnp.ones((4, 4, 1, 8), jnp.float32)
    return _lowered(lambda q: paged_attention(
        q, kp, kp, jnp.zeros((2, 2), jnp.int32), jnp.array([3, 0], jnp.int32),
        interpret=True), jnp.ones((2, 2, 8)))


def _matmul():
    from pyspark_tf_gke_tpu.ops.pallas.fused_matmul import norm_relu_matmul

    a = jnp.ones((16,))
    return _lowered(jax.value_and_grad(                 # the value keeps the forward alive
        lambda x, w: (norm_relu_matmul(x, w, a, a, interpret=True) ** 2).sum(),
        argnums=(0, 1)), jnp.ones((8, 16)), jnp.ones((16, 8)))


def _conv3():
    from pyspark_tf_gke_tpu.ops.pallas.fused_conv3 import conv3_norm_stats

    a = jnp.ones((4,))
    return _lowered(jax.value_and_grad(
        lambda x, w: (conv3_norm_stats(x, w, a, a, interpret=True) ** 2).sum(),
        argnums=(0, 1)), jnp.ones((1, 6, 6, 4)), jnp.ones((3, 3, 4, 4)))


@pytest.mark.parametrize("lower,names", [
    (lambda: _flash(False), ["flash_fwd"]),
    (lambda: _flash(True), ["flash_fwd", "flash_dq", "flash_dkv"]),
    (_layernorm, ["layernorm_fwd"]),
    (_paged, ["paged_attention_decode"]),
    (_matmul, ["fused_matmul_fwd", "fused_matmul_dx", "fused_matmul_dw"]),
    (_conv3, ["fused_conv3_fwd", "fused_conv3_dx", "fused_conv3_dw"]),
], ids=["flash_fwd", "flash_bwd", "layernorm", "paged", "fused_matmul", "fused_conv3"])
def test_lowered_text_holds_each_kernels_name(lower, names):
    text = lower()
    for name in names:
        assert name in text, name


def test_kernel_scope_joins_the_callers_scope():
    """XLA names a Mosaic call after the innermost scope alone, so the
    kernel's name rides behind the method that calls it (what the accepted
    readers match in ``jit_train_step``) and does not replace it."""
    from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention
    from pyspark_tf_gke_tpu.ops.pallas.scope import kernel_scope

    def scoped(x):
        with jax.named_scope("attention._causal_attend"):
            with kernel_scope("flash_fwd"):
                return x * 2

    assert ("attention._causal_attend/attention._causal_attend.flash_fwd"
            in _lowered(scoped, jnp.ones((4,))))

    def step(q):                          # as the train step meets it: under a gradient
        with jax.named_scope("attention._causal_attend"):
            return (flash_attention(q, q, q, causal=True, block_q=16, block_k=16,
                                    interpret=True) ** 2).sum()

    text = _lowered(jax.value_and_grad(step), jnp.ones((1, 32, 2, 8), jnp.float32))
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"attention._causal_attend.{kernel}/" in text, kernel
