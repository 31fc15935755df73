"""Chunked KDA (``ops/linear_attention.py``; the Pallas kernels of
``ops/pallas/kda.py`` in interpret mode, and the ``lax.scan`` form) against the
benchmark's plain reference on operands that are not normalised: its L2 norm,
per-token recurrence and per-head RMS, outputs and all five gradients, at
decays slow enough that the state carries across every chunk; and with the
taps of the short convolution, against its ``silu(causal_conv(x))`` before
that."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference.kimi_linear import causal_conv, kda_recurrence, l2_norm  # noqa: E402

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


def taps_for(args, seed=21, taps=4):
    """Three ``[taps, H*D]``, large enough that every tap weighs."""
    return tuple(0.5 * jax.random.normal(key, (taps, x.shape[-1]))
                 for key, x in zip(jax.random.split(jax.random.PRNGKey(seed), 3), args))


def mixed(x, w):
    return jax.nn.silu(causal_conv(x, w))


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("seq", [256, 320], ids=["1block_no_halo", "5blocks_4_borders"])
def test_with_taps_it_is_the_convolution_and_silu_before_it(impl, seq):
    """``conv=`` against ``silu(causal_conv(x))`` handed to today's call and to
    the reference, outputs and all eight gradients, the three taps' among
    them; two rows, so that a row's halo and its taps' sums are its own."""
    args = inputs(17, b=2, s=seq)
    conv = taps_for(args)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    fused = lambda five, conv: kda(*five, conv=conv, **IMPLS[impl])
    composed = lambda five, conv: kda(*(mixed(x, t) for x, t in zip(five, conv)), *five[3:],
                                      **IMPLS[impl])
    plain = lambda five, conv: reference(*(mixed(x, t) for x, t in zip(five, conv)), *five[3:])
    got = fused(args, conv)
    close(got, plain(args, conv), 1e-5)
    close(got, composed(args, conv), 1e-5)
    grads = lambda fn: jax.tree.leaves(
        jax.grad(lambda five, conv: jnp.sum(fn(five, conv) * w), argnums=(0, 1))(args, conv))
    got, want, same = grads(fused), grads(plain), grads(composed)
    assert len(got) == 8
    for g, r, c in zip(got, want, same):                  # dq dk dv dg dbeta dwq dwk dwv
        assert g.shape == r.shape
        close(g, r, 5e-5)
        close(g, c, 5e-5)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_taps_and_bf16_operands(impl):
    """bf16 projections' outputs with float32 taps, as the program calls it;
    gradients come back in the operands' dtypes."""
    args = inputs(19, b=2, s=128)
    conv = taps_for(args)
    want = reference(*(mixed(x, t) for x, t in zip(args, conv)), *args[3:])
    q, k, v = (x.astype(jnp.bfloat16) for x in args[:3])
    fn = lambda q, k, v, conv: kda(q, k, v, *args[3:], conv=conv, **IMPLS[impl])
    got = fn(q, k, v, conv)
    assert got.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), want, 3e-2)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=(0, 3))(
        q, k, v, conv)
    assert grads[0].dtype == jnp.bfloat16
    assert [g.dtype for g in grads[1]] == [jnp.float32] * 3


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_a_halo_dropped_between_blocks_is_seen(impl):
    """What a kernel that lost the three rows before a block would give (every
    64-row block convolved from zeros, the state carried on as it should be)
    differs from the reference by far more than the tolerance, from the
    border's first row on."""
    args = inputs(23, s=320)
    conv = taps_for(args)
    want = reference(*(mixed(x, t) for x, t in zip(args, conv)), *args[3:])
    rows = LA.block_rows(320)
    lost = [jnp.concatenate([mixed(x[:, i:i + rows], t) for i in range(0, 320, rows)], axis=1)
            for x, t in zip(args, conv)]
    dropped = kda(*lost, *args[3:], **IMPLS[impl])
    close(dropped[:, :rows], want[:, :rows], 1e-5)        # the first block is right
    assert float(jnp.max(jnp.abs(dropped[:, rows] - want[:, rows]))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))


def test_taps_that_do_not_fit_the_operands_are_refused():
    args = inputs(1, s=64)
    for conv in (taps_for(args)[:2] + (jnp.zeros((4, 128)),), taps_for(args, taps=LA.SUB + 1)):
        with pytest.raises(ValueError, match="taps"):
            kda(*args, conv=conv, pallas=False)


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
