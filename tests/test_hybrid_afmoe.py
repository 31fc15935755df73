"""``models/hybrid_lm.py::HybridLM`` built from an ``afmoe`` file (gated
grouped-query attention inside a window with rotary positions beside global
layers without, sandwich norms, dense and expert FFNs) against the benchmark's
plain reference (``benchmark/reference/afmoe.py``) on seeded weights at a small
size, and through ``Trainer`` / ``causal_lm_task`` / ``lm_pretrain`` as the
other decoders go; and the share tied to the model. That the new kinds leave
the other families' trees and traced steps as the parent commit had them is
``tests/test_hybrid_nemotron.py``'s to hold."""

import dataclasses
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
from lib import weights_afmoe as A  # noqa: E402
from reference import afmoe as R  # noqa: E402
from reference.kimi_linear import rms_norm  # noqa: E402

from pyspark_tf_gke_tpu.models import hybrid_lm  # noqa: E402
from pyspark_tf_gke_tpu.models.hybrid_lm import (GatedAttention, HybridLM,  # noqa: E402
                                                 config_from_file)

REAL = os.path.join(ROOT, "benchmark", "configs", "trinity-mini.json")
DATA = os.path.join(ROOT, "benchmark", "tests", "data", "configs")
TINY, TINY_NEMOTRON = os.path.join(DATA, "tiny-afmoe.json"), os.path.join(DATA, "tiny-nemotron.json")


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """Four layers, sliding sliding full sliding, a window of 64 in rows of
    256, one dense FFN and three expert layers holding experts 4-7 of 16."""
    return load(TINY)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(0), (2, 256), 0, 256)


def program(model, params, ids):
    """(sum of next-token cross entropy, logits, counters) of the program."""
    logits, sown = model.apply({"params": params}, ids, mutable=["counters"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], -1)), (logits, sown["counters"])


def abstract_tree(model, seq=128):
    return nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)))["params"])


# -- against the reference ---------------------------------------------------------------

# float32 against float32, both at ``highest``: the same sums in another order. A
# logit is a sum of 64 products of O(1) factors (1e-6 of the largest logit); a leaf's
# gradient sums 510 tokens' terms through four layers (2e-4 of the leaf's largest
# entry, as the other families' tests allow); the loss is a sum of 510 terms of about
# 5.5 (1e-5 relative). bf16 compute (8 bits of mantissa against 24) passes none of
# the three: ``test_bf16_compute_fails_each_tolerance`` holds that.
LOGITS, LOSS, GRADS = 1e-6, 1e-5, 2e-4


def gaps(model, flat, ids, tiny):
    (loss, (logits, sown)), grads = jax.value_and_grad(
        lambda p: program(model, p, ids), has_aux=True)(W.nest(flat))
    want, ref_grads = jax.value_and_grad(lambda w: R.sum_ce(w, ids, tiny))(flat)
    ref_logits = R.logits(flat, ids, tiny)
    got = W.flatten(grads)
    assert set(got) == set(ref_grads)
    by_leaf = {n: float(jnp.max(jnp.abs(got[n] - r))) / max(float(jnp.max(jnp.abs(r))), 1e-7)
               for n, r in ref_grads.items()}
    return {"logits": float(jnp.max(jnp.abs(logits - ref_logits)) / jnp.max(jnp.abs(ref_logits))),
            "loss": float(abs(loss - want) / want), "grads": by_leaf}, sown


@pytest.mark.parametrize("remat", [False, True])
def test_logits_loss_and_every_leafs_gradient_against_the_reference(tiny, ids, remat):
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32, remat=remat))
    got, sown = gaps(model, R.weights(tiny, 5), ids, tiny)
    assert got["logits"] < LOGITS and got["loss"] < LOSS
    assert len(got["grads"]) == len(A.leaf_shapes(tiny))
    for name, gap in got["grads"].items():
        assert gap <= GRADS, name
    counters = HybridLM.step_counters(sown)
    assert set(counters) == {"moe_held_assignments", "moe_held_load_max",
                             "moe_held_rows_walked"}
    assert 0 < float(counters["moe_held_load_max"]) <= float(counters["moe_held_assignments"])
    assert float(counters["moe_held_assignments"]) <= float(counters["moe_held_rows_walked"])


def test_bf16_compute_fails_each_tolerance(tiny, ids):
    model = HybridLM(config_from_file(tiny, dtype=jnp.bfloat16))
    got, _ = gaps(model, R.weights(tiny, 5), ids, tiny)
    assert got["logits"] > 100 * LOGITS and got["loss"] > LOSS
    assert max(got["grads"].values()) > 10 * GRADS


def test_the_tiny_size_has_both_layer_types_a_binding_window_and_three_expert_layers(tiny):
    cfg = config_from_file(tiny)
    assert cfg.attention == ("gated_sliding", "gated_sliding", "gated_full", "gated_sliding")
    assert cfg.ffn == ("dense", "experts", "experts", "experts")
    assert cfg.sliding_window == 64 < 256
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token) == (16, (4, 4), 4)
    assert cfg.sandwich_norms and cfg.scale_embedding


@pytest.mark.parametrize("fault", R.FAULTS)
def test_each_planted_fault_is_what_a_program_without_that_part_computes(tiny, ids, fault,
                                                                         monkeypatch):
    """The reference's faults (``tools/control_afmoe.py``) are the program with
    the window not handed on, the rotation skipped, the gate skipped; and each
    moves the logits far past the tolerance."""
    flat = R.weights(tiny, 9)
    sound, faulty = R.logits(flat, ids, tiny), R.logits(flat, ids, tiny, fault=fault)
    if fault == "window_ignored":
        whole = hybrid_lm._flash_or_dense
        monkeypatch.setattr(hybrid_lm, "_flash_or_dense",
                            lambda cfg, mesh, q, k, v, window=None: whole(cfg, mesh, q, k, v))
    elif fault == "rotation_off":
        monkeypatch.setattr(hybrid_lm, "apply_rope", lambda x, positions, theta: x)
    else:
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x) if x.ndim == 3 else
                            jax.lax.logistic(x))
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32))
    got = model.apply({"params": W.nest(flat)}, ids, mutable=["counters"])[0]
    top = float(jnp.max(jnp.abs(sound)))
    assert float(jnp.max(jnp.abs(got - faulty))) < 1e-5 * top
    assert float(jnp.max(jnp.abs(faulty - sound))) > 1e-2 * top
    if fault == "window_ignored":       # the window binds from its own length on
        assert float(jnp.max(jnp.abs(faulty[:, :64] - sound[:, :64]))) < 1e-6 * top


def test_through_the_flash_kernels_it_is_the_dense_form(tiny, ids):
    """``use_flash`` (the kernels in the interpreter, off the TPU): windowed
    launches on the sliding layers, causal ones on the global layer, and the
    logits the dense fallback gives."""
    cfg = config_from_file(tiny, dtype=jnp.float32)
    params = W.nest(R.weights(tiny, 7))
    want = HybridLM(cfg).apply({"params": params}, ids, mutable=["counters"])[0]
    flash = HybridLM(dataclasses.replace(cfg, use_flash=True))
    got = flash.apply({"params": params}, ids, mutable=["counters"])[0]
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
    text = jax.jit(lambda p, i: flash.apply({"params": p}, i, mutable=["counters"])[0]).lower(
        params, ids).as_text(debug_info=True)
    assert len(re.findall(r"window_flash_fwd/pallas_call", text)) > 0
    assert len(re.findall(r"(?<!window_)flash_fwd/pallas_call", text)) > 0


def test_attention_is_blind_to_position_on_a_global_layer_and_not_on_a_sliding_one(tiny):
    """Keys and values behind a row, swapped among themselves: a global layer's
    row (no position signal at all) reads the same, a sliding layer's does not."""
    cfg = config_from_file(tiny, dtype=jnp.float32)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.hidden_size))
    swapped = hidden.at[:, 3].set(hidden[:, 11]).at[:, 11].set(hidden[:, 3])
    for sliding, same in ((False, True), (True, False)):
        layer = GatedAttention(cfg, sliding=sliding)
        params = layer.init(jax.random.PRNGKey(0), hidden)
        out, out_swapped = layer.apply(params, hidden), layer.apply(params, swapped)
        moved = float(jnp.max(jnp.abs(out[:, 20:] - out_swapped[:, 20:]))) \
            / float(jnp.max(jnp.abs(out)))
        assert (moved < 1e-5) == same


# -- the share tied to the model ------------------------------------------------------------

def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer(tiny):
    """Four chips hold four experts each of the tiny layer's 16. What each
    share's expert layer gives less the shared expert (which every chip computes
    alike), summed over the shares, plus the shared expert once, is the
    reference's layer with all 16 experts held."""
    from reference.kimi_linear import expert_ffn, swiglu

    uncut = dict(tiny, num_experts=16, deployment=dict(tiny["deployment"], experts_held_first=0))
    d = A.dims(uncut)
    shapes = A.ffn_leaf_shapes(uncut, "experts")
    key = W.seed_key(3)
    w = {n: W.make_leaf(key, n, W.name_tag(n), s) for n, s in shapes.items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, d["h"]))
    prep = lambda m: m
    want = expert_ffn(x, w, d, prep)
    shared = swiglu(x, w["shared/gate/kernel"], w["shared/up/kernel"], w["shared/down/kernel"],
                    prep)
    total = shared
    for first in range(0, 16, 4):
        cfg = config_from_file(dict(tiny, deployment=dict(tiny["deployment"],
                                                          experts_held_first=first)),
                               dtype=jnp.float32)
        layer = hybrid_lm.HeldExpertsLayer(
            num_experts=cfg.num_experts, held=cfg.experts_held, top_k=cfg.experts_per_token,
            hidden_size=cfg.hidden_size, intermediate_size=cfg.expert_intermediate_size,
            route_scale=cfg.route_scale, shared=cfg.shared_experts, dtype=jnp.float32)
        held = {n: v[first:first + 4] if n.startswith("w_") else v for n, v in w.items()}
        out, _ = layer.apply({"params": W.nest(held)}, x)
        total = total + out - shared
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


# -- the configuration file and the normal path -------------------------------------------

def test_the_tree_is_the_one_the_benchmark_makes_weights_for(tiny):
    tree = abstract_tree(HybridLM(config_from_file(tiny, dtype=jnp.float32)))
    assert {n: v.shape for n, v in W.flatten(tree).items()} == {
        n: tuple(s) for n, s in A.leaf_shapes(tiny).items()}


def test_the_cut_configuration_is_five_layers_and_705_million_parameters():
    real = load(REAL)
    cfg = config_from_file(REAL)
    assert cfg.attention == ("gated_sliding", "gated_sliding", "gated_full", "gated_sliding",
                             "gated_sliding")
    assert cfg.ffn == ("dense", "experts", "experts", "experts", "experts")
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token) == (128, (0, 16), 8)
    assert (cfg.expert_activation, cfg.expert_intermediate_size, cfg.intermediate_size,
            cfg.shared_experts, cfg.route_scale) == ("swiglu", 1024, 6144, 1, 2.826)
    assert (cfg.hidden_size, cfg.vocab_size) == (2048, 25024)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim, cfg.sliding_window, cfg.rope_theta) == (
        32, 4, 128, 2048, 10000.0)
    assert cfg.sandwich_norms and cfg.scale_embedding and cfg.layer_norm_eps == 1e-5
    # paths, shapes and dtypes at the real widths are the benchmark's leaf table
    tree = W.flatten(abstract_tree(HybridLM(cfg), seq=128))
    assert {n: (v.shape, v.dtype) for n, v in tree.items()} == {
        n: (tuple(s), jnp.float32) for n, s in A.leaf_shapes(real).items()}
    count = sum(int(np.prod(v.shape)) for v in tree.values())
    assert count == A.param_count(real) == 705_474_304        # 11.29 GB at 16 B a parameter
    assert f"{count:,}" in real["why"]
    attention = {n.split("attention/")[1]: v.shape for n, v in tree.items()
                 if n.startswith("layer_2/attention/")}
    assert attention == {"q_proj/kernel": (2048, 4096), "k_proj/kernel": (2048, 512),
                         "v_proj/kernel": (2048, 512), "gate_proj/kernel": (2048, 4096),
                         "q_norm/scale": (128,), "k_norm/scale": (128,),
                         "o_proj/kernel": (4096, 2048)}
    assert sum(int(np.prod(s)) for s in attention.values()) == 27_263_232
    layer = sum(int(np.prod(v.shape)) for n, v in tree.items() if n.startswith("layer_1/"))
    assert layer == 134_488_448 and tree["layer_1/mlp/w_up"].shape == (16, 2048, 1024)
    assert tree["layer_0/mlp/up/kernel"].shape == (2048, 6144)


@pytest.mark.parametrize("kw,match", [(dict(decode=True), "Reach 2"),
                                      (dict(prefill=True), "window layers beside global"),
                                      (dict(slot_decode=True), "page allocator"),
                                      (dict(segment_ids=jnp.zeros((2, 256), jnp.int32)),
                                       "segment_ids")])
def test_what_is_not_built_yet_raises(tiny, ids, kw, match):
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32))
    with pytest.raises(NotImplementedError, match=match):
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, **kw))


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=["sliding_attention"] * 3), "does not name 4 layers"),
    (dict(layer_types=["sliding_attention", "chunked_attention"] * 2), "does not name"),
    (dict(score_func="softmax"), "sigmoid router"),
    (dict(route_norm=False), "renormalised"),
    (dict(n_group=2), "not grouped")])
def test_config_refuses_what_it_cannot_build(tiny, change, match):
    with pytest.raises(ValueError, match=match):
        config_from_file(dict(tiny, **change))


@pytest.mark.parametrize("vocab_chunks", [None, 2], ids=["dense_loss", "chunked_loss"])
def test_trainer_takes_it_and_its_counters_reach_the_history(tiny, ids, vocab_chunks):
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.trainer import Trainer, causal_lm_task

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    model = HybridLM(config_from_file(tiny, dtype=jnp.float32, remat=True), mesh=mesh)
    trainer = Trainer(model, causal_lm_task(vocab_chunks=vocab_chunks), mesh, learning_rate=1e-3)
    batch = {"input_ids": np.asarray(ids[:, :128])}
    state = trainer.init_state(jax.random.PRNGKey(0), batch)
    state, history = trainer.fit(state, iter([batch] * 4), epochs=2, steps_per_epoch=2,
                                 prefetch=0)
    assert history["loss"][1] < history["loss"][0]
    assert 0 < history["moe_held_load_max"][0] <= history["moe_held_assignments"][0]
    assert history["moe_held_assignments"][0] <= history["moe_held_rows_walked"][0]


def test_lm_pretrain_takes_the_family_by_its_arch():
    from pyspark_tf_gke_tpu.train import lm_pretrain

    for argv in (["--data-pattern", "x", "--arch", "afmoe"],
                 ["--data-pattern", "x", "--model-config", TINY]):
        with pytest.raises(SystemExit, match="afmoe and --model-config go"):
            lm_pretrain.main(argv)
    both = ["--data-pattern", "x", "--arch", "afmoe", "--model-config", TINY]
    with pytest.raises(SystemExit, match="--arch afmoe trains only"):
        lm_pretrain.main(both + ["--export-bundle", "/tmp/nowhere"])
    with pytest.raises(SystemExit, match="model_type 'afmoe'.*states 'nemotron_h'"):
        lm_pretrain.main(["--data-pattern", "x", "--arch", "afmoe",
                          "--model-config", TINY_NEMOTRON])
    with pytest.raises(SystemExit, match="model_type 'nemotron_h'.*states 'afmoe'"):
        lm_pretrain.main(["--data-pattern", "x", "--arch", "nemotron-h", "--model-config", TINY])


def test_lm_pretrain_trains_the_family_from_its_file(tiny, tmp_path):
    """``--arch afmoe --model-config``: the normal path end to end at the toy
    size, on the mesh ``lm_pretrain`` makes of the CPU's eight devices (the byte
    tokenizer has 259 ids: a copy of the file with a larger vocabulary)."""
    from pyspark_tf_gke_tpu.train import lm_pretrain

    config = tmp_path / "afmoe.json"
    config.write_text(json.dumps(dict(tiny, vocab_size=320)))
    (tmp_path / "corpus.txt").write_text("window layers beside global ones\n" * 400)
    out = lm_pretrain.main([
        "--data-pattern", str(tmp_path / "corpus.txt"), "--arch", "afmoe",
        "--model-config", str(config), "--seq-len", "128", "--batch-size", "8",
        "--epochs", "1", "--steps-per-epoch", "3", "--remat", "--vocab-chunks", "2",

        "--output-dir", str(tmp_path / "out")])
    assert np.isfinite(out["loss"][-1]) and out["moe_held_assignments"][-1] > 0
