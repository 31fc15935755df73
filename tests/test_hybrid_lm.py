"""``models/hybrid_lm.py::HybridLM`` against the benchmark's plain reference
(``benchmark/reference/kimi_linear.py``) on seeded weights at a small size,
and through ``Trainer`` / ``causal_lm_task`` / ``lm_pretrain`` as the other
decoder goes."""

import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import weights as W  # noqa: E402
from lib import weights_kimi_linear as K  # noqa: E402
from reference import kimi_linear as R  # noqa: E402

from pyspark_tf_gke_tpu.models.hybrid_lm import (HybridLM, HybridLMConfig,  # noqa: E402
                                                 KDAAttention, config_from_file)

REAL = os.path.join(ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json")
TINY = os.path.join(ROOT, "benchmark", "tests", "data", "configs", "tiny-kimi.json")


@pytest.fixture(scope="module")
def tiny():
    with open(TINY) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0, 256)


def program_sum_ce(model, params, ids):
    logits, sown = model.apply({"params": params}, ids, mutable=["counters"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], -1)), sown["counters"]


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


def test_the_tree_is_the_one_the_benchmark_makes_weights_for(tiny, ids):
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32))
    tree = nn.unbox(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    assert {n: v.shape for n, v in W.flatten(tree).items()} == {
        n: tuple(s) for n, s in K.leaf_shapes(tiny).items()}


def test_the_cut_configuration_is_five_layers_and_602_million_parameters():
    cfg = config_from_file(REAL)
    assert cfg.attention == ("kda", "kda", "kda", "mla", "kda")
    assert cfg.ffn == ("dense", "experts", "experts", "experts", "experts")
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token) == (256, (0, 8), 8)
    assert (cfg.hidden_size, cfg.vocab_size, cfg.kda_head_dim) == (2304, 20480, 128)
    model = HybridLM(cfg)
    tree = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"])
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))
    with open(REAL) as f:
        assert count == K.param_count(json.load(f)) == 602_434_432


def test_the_cut_configurations_tree_is_the_benchmarks_leaf_table():
    """Paths, shapes and dtypes at the real widths: the benchmark makes its
    weights by these names (``A_log (32,)``, ``dt_bias (4096,)``,
    ``o_norm/scale (128,)``, ``q_conv/kernel (4, 4096)``), all float32."""
    with open(REAL) as f:
        real = json.load(f)
    model = HybridLM(config_from_file(real))
    tree = W.flatten(nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]))
    assert {n: (v.shape, v.dtype) for n, v in tree.items()} == {
        n: (tuple(s), jnp.float32) for n, s in K.leaf_shapes(real).items()}
    kda = {n.split("attention/")[1]: v.shape for n, v in tree.items()
           if n.startswith("layer_0/attention/")}
    assert (kda["A_log"], kda["dt_bias"], kda["o_norm/scale"], kda["q_conv/kernel"]) == (
        (32,), (4096,), (128,), (4, 4096))


def _equations(jaxpr, skipped):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, but for what a
    ``custom_vjp`` call holds (counted in ``skipped``)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("custom_vjp_call"):
            skipped.append(eqn)
            continue
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, skipped)


def test_kda_attention_never_leaves_the_flat_layout(tiny):
    """Outside ``kda``'s own ``custom_vjp`` call (whose ``lax.scan`` walk off
    the TPU blocks its operands as it likes) the layer holds no ``[B, S, H,
    D]`` view, sums no decay and convolves nothing: on the chip each such
    view or float32 pass of ``[2, 8192, 4096]`` is a round trip of 268 MB.
    q, k and v reach the call as their projections' matmuls write them, in
    ``cfg.dtype``, with the taps beside them."""
    cfg = config_from_file(tiny, dtype=jnp.bfloat16)
    layer = KDAAttention(cfg)
    hidden = jnp.zeros((1, 128, cfg.hidden_size), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), hidden)
    skipped = []
    eqns = list(_equations(jax.make_jaxpr(layer.apply)(params, hidden).jaxpr, skipped))
    assert len(skipped) == 1 and len(eqns) > 20
    # five arrays in, the sequence on axis 1, nothing of rank 4; then the taps
    operands = skipped[0].invars[-8:]
    assert [(v.aval.shape, v.aval.dtype) for v in operands] == [
        ((1, 128, 256), jnp.bfloat16)] * 3 + [((1, 128, 256), jnp.float32),
                                              ((1, 128, 2), jnp.float32)] + [
        ((tiny["linear_attn_config"]["short_conv_kernel_size"], 256), jnp.float32)] * 3
    made_by = {v: eqn for eqn in eqns for v in eqn.outvars}
    for v in operands[:3]:            # nothing between a projection's matmul and the call
        assert made_by[v].primitive.name == "dot_general", made_by[v]
    for eqn in eqns:
        name = eqn.primitive.name
        assert name not in ("pad", "conv_general_dilated", "cumsum", "cumlogsumexp", "cummax",
                            "cumprod"), eqn
        assert not name.startswith("reduce_window"), eqn
        if name in ("reshape", "broadcast_in_dim", "transpose"):
            assert all(len(v.aval.shape) < 4 for v in eqn.outvars), eqn


def test_kda_attention_per_shard_over_rows_and_heads(tiny):
    """Under a ``dp=2, tp=2`` mesh of the CPU's devices each shard runs ``kda``
    on its rows and its heads' columns of all five flat operands (``beta``'s
    last axis is its heads) and of the three taps, whose gradients are summed
    over the shards of rows: outputs and gradients are the one-device layer's."""
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

    cfg = config_from_file(tiny, dtype=jnp.float32)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.hidden_size))
    plain = KDAAttention(cfg)
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(3), x.shape),
        nn.unbox(plain.init(jax.random.PRNGKey(0), hidden)))
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    sharded = KDAAttention(cfg, mesh=mesh)
    loss = lambda layer: lambda p, h: jnp.sum(jnp.square(layer.apply(p, h)))
    with mesh:
        got = jax.jit(jax.value_and_grad(loss(sharded)))(params, hidden)
    want = jax.value_and_grad(loss(plain))(params, hidden)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * float(jnp.max(jnp.abs(w)))


@pytest.mark.parametrize("kw,match", [(dict(decode=True), "Reach 3 and 4"),
                                      (dict(prefill=True), "Reach 3 and 4"),
                                      (dict(slot_decode=True), "Reach 3 and 4"),
                                      (dict(segment_ids=jnp.zeros((2, 128), jnp.int32)),
                                       "segment_ids")])
def test_what_is_not_built_yet_raises(tiny, ids, kw, match):
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32))
    with pytest.raises(NotImplementedError, match=match):
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, **kw))


def test_config_refuses_unknown_kinds_and_ragged_lists(tiny):
    with pytest.raises(ValueError, match="unknown layer kind"):
        HybridLMConfig(vocab_size=8, hidden_size=8, attention=("swa",), ffn=("dense",))
    with pytest.raises(ValueError, match="one kind per layer"):
        HybridLMConfig(vocab_size=8, hidden_size=8, attention=("kda", "mla"), ffn=("dense",))
    bad = dict(tiny, linear_attn_config=dict(tiny["linear_attn_config"], kda_layers=[1, 2]))
    with pytest.raises(ValueError, match="not in exactly one"):
        config_from_file(bad)


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
    batch = {"input_ids": np.asarray(ids)}
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


def test_lm_pretrain_arch_and_model_config_go_together():
    from pyspark_tf_gke_tpu.train import lm_pretrain

    for argv in (["--data-pattern", "x", "--arch", "kimi-linear"],
                 ["--data-pattern", "x", "--model-config", TINY]):
        with pytest.raises(SystemExit, match="go together"):
            lm_pretrain.main(argv)
    with pytest.raises(SystemExit, match="trains only"):
        lm_pretrain.main(["--data-pattern", "x", "--arch", "kimi-linear", "--model-config",
                          TINY, "--export-bundle", "/tmp/nowhere"])
