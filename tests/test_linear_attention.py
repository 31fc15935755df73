"""Chunked KDA (``ops/linear_attention.py``; the Pallas kernels of
``ops/pallas/kda.py`` in interpret mode, and the ``lax.scan`` form) against the
per-token recurrence of the benchmark's plain reference, outputs and all
gradients, at decays slow enough that the state carries across every chunk."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference.kimi_linear import kda_recurrence, l2_norm  # noqa: E402

from pyspark_tf_gke_tpu.ops import linear_attention as LA  # noqa: E402
from pyspark_tf_gke_tpu.ops.linear_attention import kda  # noqa: E402

IMPLS = {"scan": dict(pallas=False), "pallas_interpret": dict(pallas=True, interpret=True)}


def inputs(seed, b=1, s=256, h=2, d=128, fastest=16.0):
    """q, k normalised as the model does; log-decay in about [-1.6, -0.001]
    a token (A in [1, fastest], dt log-uniform in [0.001, 0.1])."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2_norm(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5
    k = l2_norm(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    a = 1.0 + (fastest - 1.0) * jax.random.uniform(ks[3], (h,))
    dt = jnp.exp(np.log(1e-3) + np.log(100.0) * jax.random.uniform(ks[4], (b, s, h, d)))
    g = -a[None, None, :, None] * dt
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, h)))
    return q, k, v, g, beta


def close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * max(scale, 1e-6)


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("seq", [256, 320], ids=["4chunks_1block", "5chunks_5blocks"])
def test_outputs_and_gradients_match_the_recurrence(impl, seq):
    args = inputs(3, s=seq)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    fn = lambda *a: kda(*a, **IMPLS[impl])
    close(fn(*args), kda_recurrence(*args), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(kda_recurrence(*a) * w), argnums=range(5))(*args)
    for g, r in zip(got, want):
        close(g, r, 2e-5)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_two_rows_and_bf16_operands(impl):
    """The program's setting: bf16 q, k, v and bf16 matmul operands, float32
    decays and state; two rows, each from a zero state."""
    args = inputs(5, b=2, s=256)
    want = kda_recurrence(*args)
    q, k, v = (x.astype(jnp.bfloat16) for x in args[:3])
    got = kda(q, k, v, *args[3:], **IMPLS[impl]).astype(jnp.float32)
    close(got, want, 3e-2)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_a_state_dropped_between_chunks_is_seen(impl):
    """What a kernel that lost the state between chunks would give (every
    chunk from a zero state) is far from the recurrence at these decays: the
    comparison above would fail it."""
    args = inputs(7, s=256)
    want = kda_recurrence(*args)
    dropped = jnp.concatenate(
        [kda(*(x[:, i:i + LA.CHUNK] for x in args), **IMPLS[impl])
         for i in range(0, 256, LA.CHUNK)], axis=1)
    first = slice(0, LA.CHUNK)
    close(dropped[:, first], want[:, first], 1e-5)        # the first chunk is right
    later = float(jnp.max(jnp.abs(dropped[:, LA.CHUNK:] - want[:, LA.CHUNK:])))
    assert later > 1e-2 * float(jnp.max(jnp.abs(want)))


def test_decays_past_float32_range_of_a_factored_chunk():
    """64 tokens at -1.6 sum to -102: exp(-G) over a whole chunk overflows
    float32; the sub-block form stays finite and right."""
    args = list(inputs(11, s=128, h=1))
    args[3] = jnp.full_like(args[3], -1.6)
    for kw in IMPLS.values():
        got = kda(*args, **kw)
        assert bool(jnp.all(jnp.isfinite(got)))
        close(got, kda_recurrence(*args), 1e-5)


@pytest.mark.parametrize("seq", [100, 65])
def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused(seq):
    args = inputs(1, s=seq)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        kda(*args, pallas=False)


def test_block_rows_take_the_most_chunks_that_divide():
    assert LA.block_rows(8192) == 256 and LA.block_rows(320) == 64
    assert LA.block_rows(384) == 192 and LA.block_rows(64) == 64
