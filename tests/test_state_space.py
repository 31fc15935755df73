"""The chunked state-space scan (``ops/state_space.py``; the Pallas kernels of
``ops/pallas/ssd.py`` in interpret mode, and the ``lax.scan`` form) against the
benchmark's plain reference, the recurrence one token at a time: outputs and
all six gradients, at steps small enough that the state carries across every
chunk and large enough that a factored decay would overflow."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference.nemotron_h import ssd_recurrence  # noqa: E402

from pyspark_tf_gke_tpu.ops import state_space as SS  # noqa: E402

IMPLS = {"scan": dict(pallas=False), "pallas_interpret": dict(pallas=True, interpret=True)}
H, G, P, N = 4, 2, 64, 128


def ssd(*args, **kw):
    return SS.ssd(*args, heads=H, groups=G, **kw)


def inputs(seed, b=1, s=256, fastest=16.0):
    """``x [B, S, H*P]``, ``b, c [B, S, G*N]`` as a projection writes them; ``dt``
    log-uniform in [0.001, 0.1] a token and a head, ``A`` in [-fastest, -1]: the
    log-decay lies in about [-1.6, -0.001] a token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, H * P))
    bb, cc = (jax.random.normal(key, (b, s, G * N)) for key in ks[1:3])
    dt = jnp.exp(np.log(1e-3) + np.log(100.0) * jax.random.uniform(ks[3], (b, s, H)))
    a = -(1.0 + (fastest - 1.0) * jax.random.uniform(ks[4], (H,)))
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    return x, dt, a, bb, cc, d


def reference(x, dt, a, b, c, d, zero_state_every=None):
    bsz, s, _ = x.shape
    y = ssd_recurrence(x.reshape(bsz, s, H, P), dt, a, b.reshape(bsz, s, G, N),
                       c.reshape(bsz, s, G, N), d, zero_state_every)
    return y.reshape(bsz, s, H * P)


def close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * max(scale, 1e-6)


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("seq", [128, 512, 384],
                         ids=["1chunk", "4chunks_2blocks", "3chunks_3blocks"])
def test_outputs_and_gradients_match_the_recurrence(impl, seq):
    args = inputs(3, s=seq)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    fn = lambda *a: ssd(*a, **IMPLS[impl])
    close(fn(*args), reference(*args), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(reference(*a) * w), argnums=range(6))(*args)
    for g, r in zip(got, want):                            # dx ddt da db dc dd
        assert g.shape == r.shape
        close(g, r, 3e-5)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_two_rows_and_bf16_operands(impl):
    """The program's setting: bf16 x, b, c and bf16 matmul operands, float32
    steps, decays and state; two rows, each from a zero state. Gradients come
    back in the operands' dtypes."""
    args = inputs(5, b=2, s=256)
    want = reference(*args)
    x, b, c = (m.astype(jnp.bfloat16) for m in (args[0], args[3], args[4]))
    fn = lambda x, b, c: ssd(x, args[1], args[2], b, c, args[5], **IMPLS[impl])
    got = fn(x, b, c)
    assert got.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), want, 3e-2)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=(0, 1, 2))(x, b, c)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3
    rows = jax.grad(lambda x: jnp.sum(reference(x, *args[1:])[1]))(args[0])
    assert float(jnp.max(jnp.abs(rows[0]))) == 0.0           # a row's state is its own
    close(grads[0].astype(jnp.float32)[1], rows[1], 3e-2)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_a_state_dropped_between_chunks_is_seen(impl):
    """What a kernel that lost the state between chunks would give (every
    chunk from a zero state) is far from the recurrence at these decays: the
    comparison above would fail it."""
    args = inputs(7, s=512)
    want = reference(*args)
    cut = lambda i: [m[:, i:i + SS.CHUNK] if m.ndim == 3 else m for m in args]
    dropped = jnp.concatenate([ssd(*cut(i), **IMPLS[impl]) for i in range(0, 512, SS.CHUNK)],
                              axis=1)
    close(dropped[:, :SS.CHUNK], want[:, :SS.CHUNK], 1e-5)      # the first chunk is right
    later = float(jnp.max(jnp.abs(dropped[:, SS.CHUNK:] - want[:, SS.CHUNK:])))
    assert later > 1e-2 * float(jnp.max(jnp.abs(want)))
    # and it is the fault the benchmark plants in the reference
    close(dropped, reference(*args, zero_state_every=SS.CHUNK), 1e-5)


def test_decays_past_float32_range_of_a_factored_chunk():
    """128 tokens at ``dt A`` = -6.4 sum to -819: ``exp(-cs)`` over a chunk
    overflows float32 (and at -0.8 a token already past the 88th row); the
    differences taken here stay finite and right, gradients too."""
    for rate in (6.4, 0.8):
        x, dt, a, b, c, d = inputs(11, s=256)
        dt, a = jnp.full_like(dt, 0.1), jnp.full_like(a, -rate / 0.1)
        want = reference(x, dt, a, b, c, d)
        for kw in IMPLS.values():
            fn = lambda x, dt: ssd(x, dt, a, b, c, d, **kw)
            close(fn(x, dt), want, 1e-5)
            grads = jax.grad(lambda x, dt: jnp.sum(fn(x, dt)), argnums=(0, 1))(x, dt)
            assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_heads_of_a_whole_lane_tile():
    """Heads of 128 are a slab each (``slab_heads`` 1): the same algebra."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (1, 256, 2 * 128))
    b, c = (jax.random.normal(k, (1, 256, 128)) for k in ks[1:])
    dt = jnp.full((1, 256, 2), 0.02)
    a, d = jnp.array([-1.0, -4.0]), jnp.array([1.0, 0.5])
    want = ssd_recurrence(x.reshape(1, 256, 2, 128), dt, a, b[:, :, None], c[:, :, None],
                          d).reshape(x.shape)
    assert SS.slab_heads(128) == 1 and SS.slab_heads(64) == 2
    for kw in IMPLS.values():
        close(SS.ssd(x, dt, a, b, c, d, heads=2, groups=1, **kw), want, 1e-5)


@pytest.mark.parametrize("seq", [100, 129])
def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused(seq):
    args = inputs(1, s=seq)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd(*args, pallas=False)


@pytest.mark.parametrize("kw,match", [
    (dict(heads=3, groups=1), "are not"), (dict(heads=4, groups=3), "are not"),
    (dict(heads=8, groups=8), "are not"), (dict(heads=4, groups=4), "whole 128-lane slabs")],
    ids=["heads_not_dts", "groups_do_not_divide", "not_a_dt_a_head", "a_slab_spans_groups"])
def test_shapes_that_do_not_fit_are_refused(kw, match):
    x, dt, a, b, c, d = inputs(1, s=128)
    if kw["groups"] == 4:
        b = c = jnp.zeros((1, 128, 4 * N))
    with pytest.raises(ValueError, match=match):
        SS.ssd(x, dt, a, b, c, d, pallas=False, **kw)


def test_the_kernels_refuse_widths_that_are_no_lane_tiles():
    x, dt, a, d = jnp.zeros((1, 128, 4 * 48)), jnp.ones((1, 128, 4)), -jnp.ones(4), jnp.ones(4)
    b = jnp.zeros((1, 128, 2 * N))
    SS.ssd(x, dt, a, b, b, d, heads=4, groups=2, pallas=False)       # the scan form takes it
    with pytest.raises(ValueError, match="multiples of 128"):
        SS.ssd(x, dt, a, b, b, d, heads=4, groups=2, pallas=True, interpret=True)


def test_block_rows_take_the_most_chunks_that_divide():
    assert SS.block_rows(8192, 128) == 256 and SS.block_rows(384, 128) == 128
    assert SS.block_rows(128, 128) == 128 and SS.block_rows(256, 64) == 128
