"""Beam-search decoding: parity with greedy at K=1, score optimality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from pyspark_tf_gke_tpu.models import (
    CausalLM,
    CausalLMConfig,
    beam_search,
    generate,
)
from pyspark_tf_gke_tpu.utils.seeding import make_rng

TINY = dict(vocab_size=53, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_seq_len=32, dtype=jnp.float32)


def _setup(seed=0, **over):
    cfg = CausalLMConfig(**{**TINY, **over})
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 6), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(seed), ids)["params"])
    return model, params


def _seq_logprob(model, params, seq, s_prompt):
    """Sum of next-token log-probs over the generated suffix."""
    logits = model.apply({"params": params}, seq)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    total = 0.0
    for t in range(s_prompt, seq.shape[1]):
        total += float(logp[0, t - 1, int(seq[0, t])])
    return total


def test_beam1_equals_greedy():
    model, params = _setup(seed=1)
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(rng.integers(0, 53, (2, 4)).astype(np.int32))
    greedy = generate(model, params, prompt, max_new_tokens=6)
    beams, scores = beam_search(model, params, prompt, max_new_tokens=6,
                                num_beams=1, length_penalty=0.0)
    np.testing.assert_array_equal(np.asarray(beams), np.asarray(greedy))
    assert np.isfinite(np.asarray(scores)).all()


def test_beam_finds_at_least_greedy_likelihood():
    """With no length penalty, the best of K beams must score >= the
    greedy sequence under the model (beam explores a superset)."""
    model, params = _setup(seed=2)
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(0, 53, (1, 4)).astype(np.int32))
    n_new = 5

    greedy = generate(model, params, prompt, max_new_tokens=n_new)
    beams, _ = beam_search(model, params, prompt, max_new_tokens=n_new,
                           num_beams=4, length_penalty=0.0)
    lp_greedy = _seq_logprob(model, params, greedy, 4)
    lp_beam = _seq_logprob(model, params, beams, 4)
    assert lp_beam >= lp_greedy - 1e-4


def test_beam_score_matches_rescoring():
    """The score beam_search reports must equal the sequence's actual
    log-probability under the model (length_penalty=0)."""
    model, params = _setup(seed=3)
    prompt = jnp.zeros((1, 3), jnp.int32)
    beams, scores = beam_search(model, params, prompt, max_new_tokens=4,
                                num_beams=3, length_penalty=0.0)
    lp = _seq_logprob(model, params, beams, 3)
    np.testing.assert_allclose(float(scores[0]), lp, rtol=1e-4, atol=1e-4)


def test_beam_eos_finishes_and_pads():
    """Rig eos to the model's most likely first token so at least one
    hypothesis finishes immediately — the finished pool must keep it,
    and padding after the first eos must be eos."""
    model, params = _setup(seed=4)
    prompt = jnp.zeros((2, 3), jnp.int32)
    greedy = generate(model, params, prompt, max_new_tokens=1)
    eos = int(np.asarray(greedy[0, 3]))

    beams, scores = beam_search(model, params, prompt, max_new_tokens=8,
                                num_beams=3, eos_token_id=eos,
                                length_penalty=1.0)
    toks = np.asarray(beams[:, 3:])
    assert (toks == eos).any(axis=1).all(), "no beam finished with eos"
    for row in toks:
        first = int(np.argmax(row == eos))
        assert (row[first:] == eos).all()
    assert np.isfinite(np.asarray(scores)).all()


def test_beam_short_finished_hypothesis_survives():
    """A hypothesis that ends early must stay in the finished pool even
    while longer active beams keep exploring (the GNMT pool property):
    with eos = the argmax first token, the immediate-finish hypothesis
    must be among the selectable results and win under a strong length
    penalty... or at minimum the returned score must be >= its score."""
    model, params = _setup(seed=6)
    prompt = jnp.zeros((1, 3), jnp.int32)
    greedy = generate(model, params, prompt, max_new_tokens=1)
    eos = int(np.asarray(greedy[0, 3]))

    # score of the ends-immediately hypothesis
    logits = model.apply({"params": params}, prompt)
    lp0 = float(jax.nn.log_softmax(
        logits[0, -1].astype(jnp.float32))[eos])

    _, scores = beam_search(model, params, prompt, max_new_tokens=6,
                            num_beams=2, eos_token_id=eos,
                            length_penalty=0.0)
    assert float(scores[0]) >= lp0 - 1e-5


def test_beam_num_beams_validated():
    model, params = _setup()
    with pytest.raises(ValueError, match="num_beams"):
        beam_search(model, params, jnp.zeros((1, 3), jnp.int32),
                    max_new_tokens=2, num_beams=0)


def test_beam_with_gqa_and_int8():
    from pyspark_tf_gke_tpu.ops.quant import quantize_tree

    model, params = _setup(seed=5, num_kv_heads=1)
    qparams = quantize_tree(params, min_size=64)
    prompt = jnp.zeros((1, 3), jnp.int32)
    beams, scores = beam_search(model, qparams, prompt, max_new_tokens=5,
                                num_beams=2)
    assert beams.shape == (1, 8)
    assert np.isfinite(np.asarray(scores)).all()


def test_beam_bounds_checked():
    model, params = _setup()
    with pytest.raises(ValueError, match="max_seq_len"):
        beam_search(model, params, jnp.zeros((1, 30), jnp.int32),
                    max_new_tokens=10, num_beams=2)


def test_beam_eos_id_validated():
    model, params = _setup()
    with pytest.raises(ValueError, match="eos_token_id"):
        beam_search(model, params, jnp.zeros((1, 3), jnp.int32),
                    max_new_tokens=2, num_beams=2, eos_token_id=999)


def test_reorder_beams_select_path_matches_gather():
    # The large-leaf K-way select path must be element-exact vs the
    # take_along_axis path — including NaN/inf semantics: a non-finite
    # value travels with its OWN beam only (never leaks across rows the
    # way a one-hot contraction's 0*inf would).
    import numpy as np

    from pyspark_tf_gke_tpu.models.beam_search import _reorder_beams

    b, k, f = 2, 4, 9000  # k*f*b = 72k elements > the 1<<16 threshold
    rng = np.random.default_rng(0)
    for dtype in (jnp.float32, jnp.bfloat16, jnp.int8):
        base = rng.normal(size=(b * k, f)) * 3
        leaf = jnp.asarray(base, dtype)
        if dtype != jnp.int8:
            leaf = leaf.at[1, 7].set(jnp.nan)  # beam 1 of batch row 0
            leaf = leaf.at[k + 2, 5].set(jnp.inf)
        idx = jnp.asarray([[1, 1, 3, 0], [2, 0, 0, 3]], jnp.int32)
        small = leaf.reshape(b, k, f)
        expected = jnp.take_along_axis(
            small, idx[:, :, None], axis=1).reshape(b * k, f)
        got = _reorder_beams(leaf, idx, select=True)
        assert got.shape == expected.shape and got.dtype == expected.dtype
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(expected, np.float32))
