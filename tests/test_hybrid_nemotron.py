"""``models/hybrid_lm.py::HybridLM`` built from a ``nemotron_h`` file (one mixer
a layer: Mamba-2, GQA or relu2 experts) against the benchmark's plain
reference (``benchmark/reference/nemotron_h.py``) on seeded weights at a small
size, and through ``Trainer`` / ``causal_lm_task`` / ``lm_pretrain`` as the
other decoders go; and the Kimi-Linear family's tree and traced step, which
the new kinds must leave as the parent commit had them."""

import dataclasses
import hashlib
import json
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import weights as W  # noqa: E402
from lib import weights_nemotron_h as N  # noqa: E402
from reference import nemotron_h as R  # noqa: E402

from pyspark_tf_gke_tpu.models import hybrid_lm, moe  # noqa: E402
from pyspark_tf_gke_tpu.models.hybrid_lm import (HybridLM, HybridLMConfig,  # noqa: E402
                                                 Mamba2Mixer, config_from_file)
from pyspark_tf_gke_tpu.models.moe import HeldExpertsLayer  # noqa: E402

REAL = os.path.join(ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b.json")
DATA = os.path.join(ROOT, "benchmark", "tests", "data", "configs")
TINY, TINY_KIMI = os.path.join(DATA, "tiny-nemotron.json"), os.path.join(DATA, "tiny-kimi.json")


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    return load(TINY)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(0), (2, 256), 0, 256)


def program_sum_ce(model, params, ids):
    logits, sown = model.apply({"params": params}, ids, mutable=["counters"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], -1)), sown["counters"]


def abstract_tree(model, seq=128):
    return nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)))["params"])


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradients_against_the_reference(tiny, ids, remat):
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32, remat=remat))
    flat = R.weights(tiny, 5)
    (loss, sown), grads = jax.value_and_grad(
        lambda p: program_sum_ce(model, p, ids), has_aux=True)(W.nest(flat))
    want, ref_grads = jax.value_and_grad(lambda w: R.sum_ce(w, ids, tiny))(flat)
    assert float(abs(loss - want)) < 1e-5 * float(want)
    got = W.flatten(grads)
    assert set(got) == set(ref_grads)
    for name, r in ref_grads.items():
        scale = max(float(jnp.max(jnp.abs(r))), 1e-7)
        assert float(jnp.max(jnp.abs(got[name] - r))) <= 2e-4 * scale, name
    counters = HybridLM.step_counters(sown)
    assert set(counters) == {"moe_held_assignments", "moe_held_load_max",
                             "moe_held_rows_walked"}
    assert 0 < float(counters["moe_held_load_max"]) <= float(counters["moe_held_assignments"])
    assert float(counters["moe_held_assignments"]) <= float(counters["moe_held_rows_walked"])


def test_through_the_kernels_it_is_the_scan_form(tiny, ids, monkeypatch):
    """``ssd`` told to take its Pallas kernels (in the interpreter, off the
    TPU) gives the decoder the logits the ``lax.scan`` form gives it."""
    import functools

    model = HybridLM(config_from_file(tiny, dtype=jnp.float32))
    params = W.nest(R.weights(tiny, 7))
    want = model.apply({"params": params}, ids, mutable=["counters"])[0]
    monkeypatch.setattr(hybrid_lm, "ssd", functools.partial(hybrid_lm.ssd, interpret=True))
    got = model.apply({"params": params}, ids, mutable=["counters"])[0]
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


def test_a_scan_that_loses_its_state_between_chunks_is_the_references_fault(tiny, ids,
                                                                           monkeypatch):
    """The fault the benchmark plants in its reference (``ssd_state_zeroed``)
    is what a program whose scan restarts at every chunk computes, and past
    the first chunk its logits are far from the sound ones."""
    whole = hybrid_lm.ssd

    def forgetful(x, dt, a, b, c, d, **kw):
        return jnp.concatenate(
            [whole(x[:, i:i + 128], dt[:, i:i + 128], a, b[:, i:i + 128], c[:, i:i + 128], d,
                   **kw) for i in range(0, x.shape[1], 128)], axis=1)

    # the mixers' input projections at the real width's scale (0.02 x 2688^1/2
    # a column, not 0.02 x 64^1/2), so that the state's part of y is of the skip's
    flat = {n: v * 6.5 if n.endswith("in_proj/kernel") else v
            for n, v in R.weights(tiny, 9).items()}
    sound, faulty = R.logits(flat, ids, tiny), R.logits(flat, ids, tiny, fault="ssd_state_zeroed")
    monkeypatch.setattr(hybrid_lm, "ssd", forgetful)
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32))
    got = model.apply({"params": W.nest(flat)}, ids, mutable=["counters"])[0]
    top = float(jnp.max(jnp.abs(sound)))
    assert float(jnp.max(jnp.abs(got - faulty))) < 1e-5 * top
    assert float(jnp.max(jnp.abs(faulty[:, :128] - sound[:, :128]))) == 0.0   # the first chunk
    assert float(jnp.max(jnp.abs(faulty - sound))) > 2e-2 * top


def test_the_tree_is_the_one_the_benchmark_makes_weights_for(tiny):
    tree = abstract_tree(HybridLM(config_from_file(tiny, dtype=jnp.float32)))
    assert {n: v.shape for n, v in W.flatten(tree).items()} == {
        n: tuple(s) for n, s in N.leaf_shapes(tiny).items()}


def test_the_cut_configuration_is_nine_layers_and_667_million_parameters():
    real = load(REAL)
    cfg = config_from_file(REAL)
    assert cfg.attention == ("mamba2", "none", "mamba2", "none", "mamba2", "gqa", "none",
                             "mamba2", "none")
    assert cfg.ffn == ("none", "experts", "none", "experts", "none", "none", "experts", "none",
                       "experts")
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token) == (128, (0, 8), 6)
    assert (cfg.expert_activation, cfg.expert_intermediate_size,
            cfg.shared_intermediate_size, cfg.route_scale) == ("relu2", 1856, 3712, 2.5)
    assert (cfg.hidden_size, cfg.vocab_size) == (2688, 16384)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_chunk,
            cfg.conv_size) == (64, 64, 128, 8, 128, 4)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    # paths, shapes and dtypes at the real widths are the benchmark's leaf table
    tree = W.flatten(abstract_tree(HybridLM(cfg), seq=128))
    assert {n: (v.shape, v.dtype) for n, v in tree.items()} == {
        n: (tuple(s), jnp.float32) for n, s in N.leaf_shapes(real).items()}
    count = sum(int(np.prod(v.shape)) for v in tree.values())
    assert count == N.param_count(real) == 666_963_456       # 10.67 GB at 16 B a parameter
    mamba = {n.split("attention/")[1]: v.shape for n, v in tree.items()
             if n.startswith("layer_0/attention/")}
    assert mamba == {"in_proj/kernel": (2688, 10304), "conv/kernel": (4, 6144),
                     "conv/bias": (6144,), "A_log": (64,), "dt_bias": (64,), "D": (64,),
                     "norm/scale": (4096,), "out_proj/kernel": (4096, 2688)}
    assert "layer_0/ln_mlp/scale" not in tree and "layer_1/ln_attn/scale" not in tree


def test_mamba2_mixer_per_shard_over_rows_and_heads(tiny):
    """Under a ``dp=2, tp=2`` mesh of the CPU's devices each shard runs ``ssd``
    on its rows and on its heads' and groups' columns; ``A``, ``D`` and the
    rest are summed over the shards of rows: outputs and gradients are the
    one-device layer's."""
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

    cfg = config_from_file(tiny, dtype=jnp.float32)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.hidden_size))
    plain = Mamba2Mixer(cfg)
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(3), x.shape),
        nn.unbox(plain.init(jax.random.PRNGKey(0), hidden)))
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    sharded = Mamba2Mixer(cfg, mesh=mesh)
    loss = lambda layer: lambda p, h: jnp.sum(jnp.square(layer.apply(p, h)))
    with mesh:
        got = jax.jit(jax.value_and_grad(loss(sharded)))(params, hidden)
    want = jax.value_and_grad(loss(plain))(params, hidden)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * float(jnp.max(jnp.abs(w)))


@pytest.mark.parametrize("kw,match", [(dict(decode=True), "Reach 3 and 4"),
                                      (dict(prefill=True), "Reach 3 and 4"),
                                      (dict(slot_decode=True), "state-space scan"),
                                      (dict(segment_ids=jnp.zeros((2, 256), jnp.int32)),
                                       "segment_ids.*state-space scan")])
def test_what_is_not_built_yet_raises(tiny, ids, kw, match):
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32))
    with pytest.raises(NotImplementedError, match=match):
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, **kw))


@pytest.mark.parametrize("change,match", [
    (dict(hybrid_override_pattern="MEMEM*EM"), "does not name 9 layers"),
    (dict(hybrid_override_pattern="MEMEM-EME"), "by M, \\* and E"),
    (dict(mlp_hidden_act="silu"), "relu2")])
def test_config_refuses_what_it_cannot_build(tiny, change, match):
    with pytest.raises(ValueError, match=match):
        config_from_file(dict(tiny, **change))


def test_a_layer_needs_a_kind():
    with pytest.raises(ValueError, match="neither an attention nor an FFN kind"):
        HybridLMConfig(vocab_size=8, hidden_size=8, attention=("gqa", "none"),
                       ffn=("none", "none"))
    with pytest.raises(ValueError, match="unknown layer kind"):
        HybridLMConfig(vocab_size=8, hidden_size=8, attention=("mamba",), ffn=("none",))
    with pytest.raises(ValueError, match="unknown expert activation"):
        HeldExpertsLayer(num_experts=4, held=(0, 2), top_k=2, hidden_size=8, intermediate_size=8,
                         activation="gelu").init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


@pytest.mark.parametrize("vocab_chunks", [None, 2], ids=["dense_loss", "chunked_loss"])
def test_trainer_takes_it_and_its_counters_reach_metrics_and_registry(tiny, ids, vocab_chunks):
    from pyspark_tf_gke_tpu.obs.metrics import MetricsRegistry
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.trainer import Trainer, causal_lm_task

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32, remat=True), mesh=mesh)
    registry = MetricsRegistry()
    trainer = Trainer(model, causal_lm_task(vocab_chunks=vocab_chunks), mesh,
                      learning_rate=1e-3, metrics_registry=registry)
    batch = {"input_ids": np.asarray(ids[:, :128])}
    state = trainer.init_state(jax.random.PRNGKey(0), batch)
    state, history = trainer.fit(state, iter([batch] * 4), epochs=2, steps_per_epoch=2,
                                 prefetch=0)
    assert history["loss"][1] < history["loss"][0]
    assert history["moe_held_assignments"][0] > 0
    assert history["moe_held_load_max"][0] <= history["moe_held_assignments"][0]
    assert history["moe_held_assignments"][0] <= history["moe_held_rows_walked"][0]
    text = registry.exposition()
    for name, key in (("train_moe_held_assignments", "moe_held_assignments"),
                      ("train_moe_held_load_max", "moe_held_load_max"),
                      ("train_moe_held_rows_walked", "moe_held_rows_walked")):
        line = next(l for l in text.splitlines() if l.startswith(name + " "))
        assert float(line.split()[-1]) == pytest.approx(history[key][-1])


@pytest.mark.parametrize("arch,config", [("nemotron-h", TINY), ("kimi-linear", TINY_KIMI)])
def test_lm_pretrain_arch_and_model_config_go_together(arch, config):
    from pyspark_tf_gke_tpu.train import lm_pretrain

    for argv in (["--data-pattern", "x", "--arch", arch],
                 ["--data-pattern", "x", "--model-config", config]):
        with pytest.raises(SystemExit, match="kimi-linear / nemotron-h / afmoe and --model-config go"):
            lm_pretrain.main(argv)
    both = ["--data-pattern", "x", "--arch", arch, "--model-config", config]
    with pytest.raises(SystemExit, match=f"--arch {arch} trains only.*state-space scan"):
        lm_pretrain.main(both + ["--export-bundle", "/tmp/nowhere"])
    with pytest.raises(SystemExit, match=f"--arch {arch} trains only"):
        lm_pretrain.main(both + ["--doc-masking"])


def test_lm_pretrain_refuses_a_file_of_the_other_family(tmp_path):
    from pyspark_tf_gke_tpu.train import lm_pretrain

    common = ["--data-pattern", "x", "--output-dir", str(tmp_path)]
    with pytest.raises(SystemExit, match="model_type 'nemotron_h'.*states 'kimi_linear'"):
        lm_pretrain.main(common + ["--arch", "nemotron-h", "--model-config", TINY_KIMI])
    with pytest.raises(SystemExit, match="model_type 'kimi_linear'.*states 'nemotron_h'"):
        lm_pretrain.main(common + ["--arch", "kimi-linear", "--model-config", TINY])
    assert lm_pretrain.HYBRID_ARCHS == {"kimi-linear": "kimi_linear",
                                        "nemotron-h": "nemotron_h", "afmoe": "afmoe"}


def test_a_fresh_mamba2_mixer_carries_its_state_past_a_chunk(tiny):
    """``init`` draws ``A_log``, ``dt_bias`` and the convolution as published
    (the file's ``time_step_*``), so a good share of a fresh mixer's heads keep
    a state past a chunk: tokens of the first half chunk move the output more
    than a chunk later. With ``A_log`` and ``dt_bias`` at nought, the draw this
    replaces, nothing does."""
    cfg = dataclasses.replace(config_from_file(tiny, dtype=jnp.float32),
                              mamba_heads=64, ssm_groups=8)
    assert (cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor) == (1e-3, 0.1, 1e-4)
    mixer = Mamba2Mixer(cfg)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (1, 256, cfg.hidden_size))
    params = mixer.init(jax.random.PRNGKey(2), hidden)["params"]
    a, dt = jnp.exp(params["A_log"]), jax.nn.softplus(params["dt_bias"])
    assert 1.0 <= float(jnp.min(a)) and float(jnp.max(a)) <= 16.0
    assert 1e-3 * 0.999 <= float(jnp.min(dt)) and float(jnp.max(dt)) <= 0.1 * 1.001
    taps = params["conv"]["kernel"]
    assert float(jnp.max(jnp.abs(taps))) <= 0.5 and 0.25 < float(jnp.std(taps)) < 0.32
    assert float(jnp.max(jnp.abs(params["conv"]["bias"]))) <= 0.5
    assert bool(jnp.all(params["D"] == 1.0))
    # what a chunk of 128 tokens leaves of a state at the bias's own step
    kept = jnp.exp(-128.0 * dt * a)
    assert int(jnp.sum(kept > 0.05)) >= 8                     # about a quarter of 64 expected

    def late_change(p):
        moved = hidden.at[:, :64].add(1.0)
        out, out_moved = (mixer.apply({"params": p}, h) for h in (hidden, moved))
        return float(jnp.max(jnp.abs(out_moved[:, 192:] - out[:, 192:]))
                     / jnp.max(jnp.abs(out[:, 192:])))

    assert late_change(params) > 1e-3
    inert = dict(params, A_log=jnp.zeros_like(a), dt_bias=jnp.zeros_like(dt))
    assert late_change(inert) < 1e-6


# -- the families' trees and traced steps, pinned: a change that moves one says so here ----

def _digest(text):
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


def _paths(tree, dtypes=True):
    return str(sorted((jax.tree_util.keystr(k), v.shape) + ((str(v.dtype),) if dtypes else ())
                      for k, v in jax.tree_util.tree_leaves_with_path(tree)))


# sha256 of the parameter tree's (path, shape, dtype) list and of the jaxpr of
# the loss's value and gradient (bf16, remat, matmul precision "highest" as
# tests/conftest.py sets it), made by this test's own lines. The two trees on
# an unpacked ``git archive`` of PR 32's PARENT commit (cb2cd98, PR 31) and
# unmoved since: checkpoints and the benchmark's weights hang on them. The two
# programs ("step", "layer") on PR 36's tree (the parent 23e942e, PR 35), whose
# expert layer walks its held assignments in fine steps with float32 carries,
# under the ``parents_loop`` trip count below (540a2eb4e1075bfc and
# 500b56275c2b57ea before, PR 33's KDA and PR 32's one slab a pass)
# The step is now the one whose remat keeps the mixer kernels' named forward
# results (c9fc73cbb082abba before it).
PARENT = {"tree": "c53b307284d821a5", "step": "a17cbe057da8602c",
          "layer_tree": "e6935d7c7d3bcc3c", "layer": "5232367fe8b46bec"}


@pytest.fixture(params=["parents_loop", "first_slab_always"])
def slabs(request, monkeypatch):
    """The expert layer's loop as it is, and with PR 31's least trip count
    (``ceil(load / rows)``, nought at a load of nought) in its place."""
    if request.param == "parents_loop":
        monkeypatch.setattr(moe, "_slabs_walked", lambda total, rows: -(-total // rows))
    return request.param


def test_the_toy_kimi_decoder_keeps_the_parents_tree_and_traced_step(slabs):
    """A layer that may lack its attention or its FFN, two more mixers and a
    second family of keys in ``config_from_file`` change nothing a
    ``kimi_linear`` file builds: the leaves PR 31 had, and the program pinned
    above but for the expert layers' first step, which is walked whatever the
    load."""
    model = HybridLM(config_from_file(load(TINY_KIMI), dtype=jnp.bfloat16, remat=True))
    ids = jnp.zeros((2, 128), jnp.int32)
    tree = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    assert _digest(_paths(tree)) == PARENT["tree"]

    def loss(p, ids):
        logits, sown = model.apply({"params": p}, ids, mutable=["counters"])
        return jnp.sum(logits), sown

    with jax.default_matmul_precision("highest"):
        step = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(tree, ids)
    assert (_digest(str(step)) == PARENT["step"]) == (slabs == "parents_loop")


# sha256 of the same two things for the toy Nemotron-H decoder, made by the test's
# own lines: the tree on an unpacked ``git archive`` of PR 35's PARENT commit
# (1883bd5, PR 33), the step on PR 36's tree (666d53fc8302499e before, with the
# expert layers' one slab a pass)
# The step is now the one whose remat keeps the mixer kernels' named forward
# results (1979d6cac49af06d before it).
PARENT_NEMOTRON = {"tree": "67e4567cd2412ebd", "step": "11b36227f7760bb0"}


def test_the_toy_nemotron_decoder_keeps_the_parents_tree_and_traced_step():
    """Two more attention kinds, the sandwich norms, the embedding's scale and
    a third family of keys in ``config_from_file`` (PR 35) change nothing a
    ``nemotron_h`` file builds: the leaves PR 33 had, and the program pinned
    above."""
    model = HybridLM(config_from_file(load(TINY), dtype=jnp.bfloat16, remat=True))
    ids = jnp.zeros((2, 128), jnp.int32)
    tree = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    assert _digest(_paths(tree)) == PARENT_NEMOTRON["tree"]

    def loss(p, ids):
        logits, sown = model.apply({"params": p}, ids, mutable=["counters"])
        return jnp.sum(logits), sown

    with jax.default_matmul_precision("highest"):
        step = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(tree, ids)
    assert _digest(str(step)) == PARENT_NEMOTRON["step"]


def test_swiglu_held_experts_keep_the_parents_tree_and_traced_layer(slabs):
    layer = HeldExpertsLayer(num_experts=16, held=(4, 4), top_k=4, hidden_size=32,
                             intermediate_size=24, route_scale=2.446, shared=1)
    x = jnp.zeros((2, 24, 32), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))["params"]
    assert _digest(_paths(params, dtypes=False)) == PARENT["layer_tree"]
    with jax.default_matmul_precision("highest"):
        traced = jax.make_jaxpr(jax.value_and_grad(lambda p, x: jnp.sum(
            layer.apply({"params": p}, x)[0].astype(jnp.float32))))(params, x)
    assert (_digest(str(traced)) == PARENT["layer"]) == (slabs == "parents_loop")
