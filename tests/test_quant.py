"""Weight-only int8 quantization (ops/quant.py) + quantized serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.ops.quant import (
    QTensor,
    dequantize_tree,
    is_quantized,
    quantization_error,
    quantize_tensor,
    quantize_tree,
    tree_bytes,
)


def test_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    qt = quantize_tensor(w)
    assert qt.q.dtype == jnp.int8
    assert qt.scale.shape == (128,)
    # per-channel symmetric: error <= scale/2 per channel
    err = quantization_error(w, qt)
    max_scale = float(qt.scale.max())
    assert err <= max_scale / 2 + 1e-6


def test_quantize_tree_selectivity():
    params = {
        "dense": {"kernel": jnp.ones((128, 64), jnp.float32),
                  "bias": jnp.ones((64,), jnp.float32)},
        "ln": {"scale": jnp.ones((64,), jnp.float32)},
        "small": {"kernel": jnp.ones((4, 4), jnp.float32)},  # < min_size
    }
    q = quantize_tree(params)
    assert isinstance(q["dense"]["kernel"], QTensor)
    assert not isinstance(q["dense"]["bias"], QTensor)
    assert not isinstance(q["small"]["kernel"], QTensor)
    assert is_quantized(q) and not is_quantized(params)
    # bytes: kernel 128*64*4 → 128*64*1 + 64*4
    assert tree_bytes(q) < tree_bytes(params)
    d = dequantize_tree(q)
    assert d["dense"]["kernel"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(d["dense"]["kernel"]),
                               np.ones((128, 64)), atol=0.01)


def test_qtensor_jit_transparent():
    """QTensor trees must flow through jit as operands."""
    w = jnp.asarray(np.random.default_rng(1).normal(size=(32, 32)),
                    jnp.float32)
    qt = quantize_tensor(w)

    @jax.jit
    def f(q):
        return dequantize_tree({"k": q})["k"].sum()

    assert np.isfinite(float(f(qt)))


def test_quantized_generate_matches_shapes_and_quality():
    """Quantized serving: generate() runs on an int8 tree; logits stay
    close to the dense model's (weight-only quant is near-lossless for a
    tiny model), and greedy tokens overwhelmingly agree."""
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig, generate
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    cfg = CausalLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_heads=2, intermediate_size=64, max_seq_len=48,
                         dtype=jnp.float32)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(0), ids)["params"])
    qparams = quantize_tree(params, min_size=64)
    assert is_quantized(qparams)

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 97, (2, 6)).astype(np.int32))

    logits_d = model.apply({"params": params}, prompt)
    logits_q = model.apply({"params": dequantize_tree(qparams)}, prompt)
    # int8 per-channel on a tiny net: logits drift stays small
    assert float(jnp.max(jnp.abs(logits_d - logits_q))) < 0.5

    out = generate(model, qparams, prompt, max_new_tokens=6)
    assert out.shape == (2, 12)
    assert ((np.asarray(out) >= 0) & (np.asarray(out) < 97)).all()


def test_dequantize_embeddings_handles_frozendict():
    """The embedding-hoist must work for plain dicts AND FrozenDict."""
    import flax.core

    from pyspark_tf_gke_tpu.ops.quant import dequantize_embeddings

    tree = {
        "wte": {"embedding": quantize_tensor(jnp.ones((64, 32), jnp.float32))},
        "l0": {"kernel": quantize_tensor(jnp.ones((64, 32), jnp.float32))},
    }
    for t in (tree, flax.core.freeze(tree)):
        out = dequantize_embeddings(t)
        assert not isinstance(out["wte"]["embedding"], QTensor)
        assert isinstance(out["l0"]["kernel"], QTensor)


def test_embedding_tables_quantized_per_row():
    """Embedding tables get one scale per ROW (gathered unit): a single
    outlier row must not coarsen every other token's embedding, which is
    exactly what per-column scales (computed over the whole vocabulary)
    would do."""
    rng = np.random.default_rng(0)
    table = rng.normal(scale=0.02, size=(64, 32)).astype(np.float32)
    table[7] *= 1000.0  # one outlier token
    params = {"wte": {"embedding": jnp.asarray(table)},
              "dense": {"kernel": jnp.asarray(
                  rng.normal(size=(64, 32)).astype(np.float32))}}
    q = quantize_tree(params, min_size=64)

    emb = q["wte"]["embedding"]
    assert isinstance(emb, QTensor)
    assert emb.scale.shape == (64, 1)               # per-row
    assert q["dense"]["kernel"].scale.shape == (32,)  # per-column (unchanged)

    deq = np.asarray(emb.dequantize())
    normal_rows = np.delete(np.arange(64), 7)
    err = np.abs(deq[normal_rows] - table[normal_rows]).max()
    # per-row: normal rows keep their own tiny scale (~0.02*k/127).
    # Per-column scales would be ~20/127 ≈ 0.16 — orders worse.
    assert err < 5e-3
    # the outlier row itself roundtrips within its own scale
    assert np.abs(deq[7] - table[7]).max() <= float(emb.scale[7, 0]) / 2 + 1e-6
