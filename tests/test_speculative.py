"""Speculative decoding: greedy-exact draft-and-verify
(models/speculative.py). The defining property — the draft model can
NEVER change the output, only the speed — is asserted token-for-token
against plain greedy generate()."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from pyspark_tf_gke_tpu.models import (
    CausalLM,
    CausalLMConfig,
    generate,
    speculative_generate,
)
from pyspark_tf_gke_tpu.utils.seeding import make_rng

TARGET = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
              intermediate_size=64, max_seq_len=96, dtype=jnp.float32)
DRAFT = dict(vocab_size=97, hidden_size=16, num_layers=1, num_heads=2,
             intermediate_size=32, max_seq_len=96, dtype=jnp.float32)


def _make(cfg_dict, seed):
    cfg = CausalLMConfig(**cfg_dict)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(seed), ids)["params"])
    return model, params


@pytest.fixture(scope="module")
def models():
    target = _make(TARGET, seed=0)
    draft = _make(DRAFT, seed=1)
    return target, draft


def test_speculative_equals_greedy_with_unrelated_draft(models):
    """A randomly-initialized draft disagrees with the target almost
    everywhere — the output must STILL be exactly the target's greedy
    sequence (rejections cost speed, never correctness)."""
    (tm, tp), (dm, dp) = models
    rng = np.random.default_rng(0)
    for trial in range(3):
        prompt = jnp.asarray(rng.integers(0, 97, (1, 5)).astype(np.int32))
        ref = generate(tm, tp, prompt, max_new_tokens=20)
        out, stats = speculative_generate(
            tm, tp, dm, dp, prompt, max_new_tokens=20, gamma=4,
            return_stats=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert stats["rounds"] >= 1 and stats["proposed"] >= stats["accepted"]


def test_speculative_with_perfect_draft_accepts_everything(models):
    """Draft == target: every proposal verifies, so each round emits
    gamma+1 tokens and the acceptance rate is 100%."""
    (tm, tp), _ = models
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, 97, (1, 6)).astype(np.int32))
    ref = generate(tm, tp, prompt, max_new_tokens=21)
    out, stats = speculative_generate(
        tm, tp, tm, tp, prompt, max_new_tokens=21, gamma=4,
        return_stats=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert stats["accepted"] == stats["proposed"]
    # 1 free token from prefill, then gamma+1=5 per round for 20 more
    assert stats["rounds"] == 4
    assert stats["tokens_per_round"] >= 5.0


def test_speculative_eos_padding_matches_greedy(models):
    """Pick an id that actually occurs mid-sequence as 'eos': both paths
    must truncate there and pad identically."""
    (tm, tp), (dm, dp) = models
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(0, 97, (1, 5)).astype(np.int32))
    plain = np.asarray(generate(tm, tp, prompt, max_new_tokens=16))[0, 5:]
    eos = int(plain[len(plain) // 2])  # a token greedy really emits
    ref = generate(tm, tp, prompt, max_new_tokens=16, eos_token_id=eos)
    out = speculative_generate(tm, tp, dm, dp, prompt, max_new_tokens=16,
                               gamma=3, eos_token_id=eos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_speculative_validations(models):
    (tm, tp), (dm, dp) = models
    prompt2 = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="batch-1"):
        speculative_generate(tm, tp, dm, dp, prompt2, max_new_tokens=4)
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="max_new_tokens"):
        speculative_generate(tm, tp, dm, dp, prompt, max_new_tokens=0)
    with pytest.raises(ValueError, match="gamma"):
        speculative_generate(tm, tp, dm, dp, prompt, max_new_tokens=4,
                             gamma=0)
    with pytest.raises(ValueError, match="max_seq_len"):
        speculative_generate(tm, tp, dm, dp, prompt, max_new_tokens=500)
    bad_draft = CausalLM(CausalLMConfig(**{**DRAFT, "vocab_size": 50}))
    with pytest.raises(ValueError, match="vocab"):
        speculative_generate(tm, tp, bad_draft, dp, prompt, max_new_tokens=4)


def test_speculative_composes_with_gqa_and_int8_kv(models):
    """The chunk-verify forward rides the same cache machinery as plain
    decode — GQA and the int8 KV cache must not change the output."""
    _, (dm, dp) = models
    cfg = CausalLMConfig(**{**TARGET, "num_kv_heads": 1,
                            "kv_cache_quant": True})
    tm = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    tp = nn.meta.unbox(jax.jit(tm.init)(make_rng(3), ids)["params"])
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, 97, (1, 5)).astype(np.int32))
    ref = generate(tm, tp, prompt, max_new_tokens=12)
    out = speculative_generate(tm, tp, dm, dp, prompt, max_new_tokens=12,
                               gamma=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_device_loop_matches_host_loop(models):
    """The one-dispatch while_loop driver and the per-round host-sync
    driver must produce identical tokens AND consistent stats — the
    driver choice is a speed lever only (the host loop pays blocking
    accept/rollback readbacks every round)."""
    (tm, tp), (dm, dp) = models
    rng = np.random.default_rng(7)
    for mnt, gamma in ((20, 4), (7, 3), (1, 2)):
        prompt = jnp.asarray(rng.integers(0, 97, (1, 5)).astype(np.int32))
        host_out, host_stats = speculative_generate(
            tm, tp, dm, dp, prompt, max_new_tokens=mnt, gamma=gamma,
            return_stats=True, device_loop=False)
        dev_out, dev_stats = speculative_generate(
            tm, tp, dm, dp, prompt, max_new_tokens=mnt, gamma=gamma,
            return_stats=True, device_loop=True)
        np.testing.assert_array_equal(np.asarray(dev_out),
                                      np.asarray(host_out))
        assert dev_stats["accepted"] <= dev_stats["proposed"]
        if mnt > 1:
            assert dev_stats["rounds"] >= 1


def test_device_loop_eos_matches_host_loop(models):
    (tm, tp), (dm, dp) = models
    prompt = jnp.asarray(
        np.random.default_rng(8).integers(0, 97, (1, 5)).astype(np.int32))
    plain = np.asarray(generate(tm, tp, prompt, max_new_tokens=16))[0, 5:]
    eos = int(plain[len(plain) // 2])
    host_out = speculative_generate(tm, tp, dm, dp, prompt,
                                    max_new_tokens=16, gamma=3,
                                    eos_token_id=eos, device_loop=False)
    dev_out = speculative_generate(tm, tp, dm, dp, prompt,
                                   max_new_tokens=16, gamma=3,
                                   eos_token_id=eos, device_loop=True)
    np.testing.assert_array_equal(np.asarray(dev_out), np.asarray(host_out))


def test_device_loop_seq_bound(models):
    """Forcing the device loop past its stricter bound errors; auto mode
    falls back to the host loop and still matches plain greedy."""
    (tm, tp), (dm, dp) = models
    prompt = jnp.zeros((1, 80), jnp.int32)  # 80 + 16 + 4 - 1 = 99 > 96
    with pytest.raises(ValueError, match="device_loop"):
        speculative_generate(tm, tp, dm, dp, prompt, max_new_tokens=16,
                             gamma=4, device_loop=True)
    ref = generate(tm, tp, prompt, max_new_tokens=16)
    out = speculative_generate(tm, tp, dm, dp, prompt, max_new_tokens=16,
                               gamma=4)  # auto -> host driver
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_trained_fixture_meaningful_acceptance():
    """Round-3 VERDICT Weak #5: a REAL draft/target pair (both trained
    on the same synthetic text, train/spec_fixture.py) must land the
    acceptance rate strictly between the random-weights floor and the
    self-draft ceiling — and stay token-identical to plain greedy."""
    from pyspark_tf_gke_tpu.train.spec_fixture import make_spec_fixture

    target, tparams, draft, dparams, prompt = make_spec_fixture()
    # highest matmul precision = the fixture's training numerics
    # (conftest pins it globally for the suite; explicit here so the
    # test means the same thing standalone and on TPU backends)
    with jax.default_matmul_precision("highest"):
        out, stats = speculative_generate(
            target, tparams, draft, dparams, prompt, max_new_tokens=48,
            gamma=4, return_stats=True)
    acc = stats["accepted"] / max(stats["proposed"], 1)
    assert 0.5 < acc < 1.0, f"acceptance {acc} not in (0.5, 1.0)"
    # exactness holds on trained weights too
    ref = generate(target, tparams, prompt, max_new_tokens=48)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
