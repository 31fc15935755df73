"""Chunked KDA (``ops/linear_attention.py``; the Pallas kernels of
``ops/pallas/kda.py`` in interpret mode, and the ``lax.scan`` form) against the
benchmark's plain reference on operands that are not normalised: its L2 norm,
per-token recurrence and per-head RMS, outputs and all five gradients, at
decays slow enough that the state carries across every chunk."""

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

IMPLS = {"scan": dict(pallas=False), "pallas_interpret": dict(pallas=True, interpret=True)}
EPS = 1e-5


def kda(*args, **kw):
    return LA.kda(*args, heads=args[4].shape[-1], eps=EPS, **kw)


def inputs(seed, b=1, s=256, h=2, d=128, fastest=16.0):
    """q, k, v ``[B, S, H*D]`` as a projection writes them; log-decay in about
    [-1.6, -0.001] a token (A in [1, fastest], dt log-uniform in [0.001, 0.1])."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(key, (b, s, h * d)) for key in ks[:3])
    a = 1.0 + (fastest - 1.0) * jax.random.uniform(ks[3], (h,))
    dt = jnp.exp(np.log(1e-3) + np.log(100.0) * jax.random.uniform(ks[4], (b, s, h, d)))
    g = (-a[None, None, :, None] * dt).reshape(b, s, h * d)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, h)))
    return q, k, v, g, beta


def reference(q, k, v, g, beta, zero_state_every=None):
    """``l2_norm`` -> ``kda_recurrence`` -> each head's columns over their RMS."""
    b, s, h = beta.shape
    heads = lambda x: x.reshape(b, s, h, -1)
    o = kda_recurrence(l2_norm(heads(q)) * (q.shape[-1] // h) ** -0.5, l2_norm(heads(k)),
                       heads(v), heads(g), beta, zero_state_every)
    return (o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + EPS)).reshape(
        b, s, -1)


def close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * max(scale, 1e-6)


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("seq", [256, 320], ids=["4chunks_1block", "5chunks_5blocks"])
def test_outputs_and_gradients_match_the_recurrence(impl, seq):
    args = inputs(3, s=seq)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    fn = lambda *a: kda(*a, **IMPLS[impl])
    close(fn(*args), reference(*args), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(reference(*a) * w), argnums=range(5))(*args)
    for g, r in zip(got, want):                            # dq dk dv dg dbeta
        assert g.shape == r.shape
        close(g, r, 2e-5)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_two_rows_and_bf16_operands(impl):
    """The program's setting: bf16 q, k, v and bf16 matmul operands, float32
    decays and state; two rows, each from a zero state."""
    args = inputs(5, b=2, s=256)
    want = reference(*args)
    q, k, v = (x.astype(jnp.bfloat16) for x in args[:3])
    got = kda(q, k, v, *args[3:], **IMPLS[impl])
    assert got.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), want, 3e-2)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_a_state_dropped_between_chunks_is_seen(impl):
    """What a kernel that lost the state between chunks would give (every
    chunk from a zero state) is far from the recurrence at these decays: the
    comparison above would fail it."""
    args = inputs(7, s=256)
    want = reference(*args)
    dropped = jnp.concatenate(
        [kda(*(x[:, i:i + LA.CHUNK] for x in args), **IMPLS[impl])
         for i in range(0, 256, LA.CHUNK)], axis=1)
    first = slice(0, LA.CHUNK)
    close(dropped[:, first], want[:, first], 1e-5)        # the first chunk is right
    later = float(jnp.max(jnp.abs(dropped[:, LA.CHUNK:] - want[:, LA.CHUNK:])))
    assert later > 1e-2 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_a_call_cut_into_chunks_is_the_state_zeroed_at_each(impl):
    """Five arrays with the sequence on axis 1 and the rest by keyword: a call
    cut into 64-row pieces there loses the state and nothing else (the L2
    norms, ``beta``'s products, the summed decay and the output's RMS are a
    chunk's own), which is the fault the benchmark plants in the program."""
    args = inputs(13, s=192)
    kw = dict(heads=2, eps=EPS, **IMPLS[impl])
    pieces = jnp.concatenate(
        [LA.kda(*(x[:, i:i + LA.CHUNK] for x in args), **kw)
         for i in range(0, 192, LA.CHUNK)], axis=1)
    close(pieces, reference(*args, zero_state_every=LA.CHUNK), 1e-5)


def test_decays_past_float32_range_of_a_factored_chunk():
    """64 tokens at -1.6 sum to -102: exp(-G) over a whole chunk overflows
    float32; the sub-block form stays finite and right."""
    args = list(inputs(11, s=128, h=1))
    args[3] = jnp.full_like(args[3], -1.6)
    for kw in IMPLS.values():
        got = kda(*args, **kw)
        assert bool(jnp.all(jnp.isfinite(got)))
        close(got, reference(*args), 1e-5)


@pytest.mark.parametrize("seq", [100, 65])
def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused(seq):
    args = inputs(1, s=seq)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        kda(*args, pallas=False)


@pytest.mark.parametrize("heads", [3, 4], ids=["not_a_divisor", "not_betas"])
def test_heads_that_do_not_divide_the_width_are_refused(heads):
    args = inputs(1, s=64)                                 # 256 columns, beta of 2 heads
    with pytest.raises(ValueError, match="heads do not divide"):
        LA.kda(*args, heads=heads, eps=EPS, pallas=False)


@pytest.mark.parametrize("reverse", [False, True])
def test_the_decays_running_sum_is_exact_to_float32(reverse):
    """Three bf16 passes against a triangle of ones lose nothing of a float32
    log-decay (a bf16 decay would be wrong in the third digit), and the sum
    from the last row back is its transpose: what the backward pulls."""
    g = -jnp.exp(jax.random.uniform(jax.random.PRNGKey(2), (LA.SUB, 128), minval=-7.0,
                                    maxval=0.5))
    flip = (lambda x: x[::-1]) if reverse else (lambda x: x)
    want = flip(np.cumsum(flip(np.asarray(g, np.float64)), axis=0))
    got = LA._running_sum(g, reverse)
    assert got.dtype == jnp.float32
    assert float(np.max(np.abs(got - want) / np.abs(want))) <= 2.0 ** -22
    w = jax.random.normal(jax.random.PRNGKey(4), g.shape)
    pulled = jax.grad(lambda x: jnp.sum(LA._running_sum(x, reverse) * w))(g)
    close(pulled, LA._running_sum(w, not reverse), 1e-6)


def test_block_rows_take_the_most_chunks_that_divide():
    assert LA.block_rows(8192) == 256 and LA.block_rows(320) == 64
    assert LA.block_rows(384) == 192 and LA.block_rows(64) == 64
