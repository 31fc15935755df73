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


# -- the chunk's triangular inverse --------------------------------------------------

SCALES = {"entries_0.05": 0.05, "entries_0.6": 0.6}


def strictly_lower(seed, scale, c=LA.CHUNK):
    return jnp.tril(scale * jax.random.normal(jax.random.PRNGKey(seed), (c, c)), -1)


def inverse(a):
    """The program's, of one chunk."""
    (t,) = LA._unit_lower_inverse((a,))
    return t


def ten_product_series(a):
    """What the program ran before PR 33, differentiated as it stands: five
    rounds of ``p = p p; t = t + t p``, and backward the transpose of each."""
    t, p, span = jnp.eye(a.shape[0], dtype=a.dtype) - a, a, 2
    while span < a.shape[0]:
        p = LA._dot32(p, p)
        t = t + LA._dot32(t, p)
        span *= 2
    return t


def pulled_through_the_mask(fn, a, ct):
    """The cotangent as :func:`_chunk` gets it: through the ``where`` that keeps
    the strict lower triangle."""
    return jax.grad(lambda x: jnp.sum(fn(jnp.tril(x, -1)) * ct))(a)


@pytest.mark.parametrize("scale", sorted(SCALES))
def test_the_inverse_and_its_cotangent_against_float64(scale):
    a = strictly_lower(31, SCALES[scale])
    ct = jax.random.normal(jax.random.PRNGKey(33), a.shape)
    a64, ct64 = np.asarray(a, np.float64), np.asarray(ct, np.float64)
    want = np.linalg.inv(np.eye(a.shape[0]) + a64)
    if scale == "entries_0.6":         # the series cut a round short would not pass
        cut = np.linalg.matrix_power(a64, 32) @ want
        assert np.max(np.abs(cut)) > 10 * 2e-6 * np.max(np.abs(want))
    got = inverse(a)
    assert got.dtype == jnp.float32
    close(got, want, 2e-6)
    pulled = jax.grad(lambda x: jnp.sum(inverse(x) * ct))(a)
    close(pulled, -want.T @ ct64 @ want.T, 2e-6)


@pytest.mark.parametrize("scale", sorted(SCALES))
def test_six_products_are_the_ten_product_series(scale):
    a = strictly_lower(35, SCALES[scale])
    ct = jax.random.normal(jax.random.PRNGKey(37), a.shape)
    close(inverse(a), ten_product_series(a), 2e-6)
    got = pulled_through_the_mask(inverse, a, ct)
    assert float(jnp.max(jnp.abs(jnp.triu(got)))) == 0.0
    close(got, pulled_through_the_mask(ten_product_series, a, ct), 2e-6)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.tree.leaves(eqn.params, is_leaf=lambda x: hasattr(x, "eqns") or hasattr(
                x, "jaxpr")):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from equations(sub)


def products(jaxpr):
    return [e for e in equations(jaxpr) if e.primitive.name == "dot_general"]


def float32_at_highest(eqn):
    precision = eqn.params["precision"]
    precision = precision if isinstance(precision, tuple) else (precision,) * 2
    return (all(p == jax.lax.Precision.HIGHEST for p in precision)
            and all(v.aval.dtype == jnp.float32 for v in (*eqn.invars, *eqn.outvars)))


def test_the_inverse_is_six_products_and_its_cotangent_two():
    """A count on the jaxpr, so that neither half can fall back to the series
    and its transpose unseen: six ``[C, C] x [C, 2C]`` forward (``a [a | I]``,
    four rounds of ``p [p | t]`` and the last update); two backward, and a
    ``vjp`` holds the eight and no more."""
    a, c = strictly_lower(39, 0.3), LA.CHUNK
    forward = products(jax.make_jaxpr(inverse)(a).jaxpr)
    assert [e.outvars[0].aval.shape for e in forward] == [(c, 2 * c)] * 6
    cotangent = products(jax.make_jaxpr(LA._unit_lower_inverse_bwd)((a,), (a,)).jaxpr)
    assert [e.outvars[0].aval.shape for e in cotangent] == [(c, c)] * 2
    # the transposing is the contraction's: no product's operand is a transpose
    assert [e.params["dimension_numbers"] for e in cotangent] == [LA._NT, LA._TN]
    both = products(jax.make_jaxpr(lambda a, ct: jax.vjp(inverse, a)[1](ct))(a, a).jaxpr)
    assert len(both) == 8
    assert all(float32_at_highest(e) for e in forward + cotangent + both)


def test_the_chunks_chains_are_issued_link_by_link():
    """Three chunks' inverses at once: every chain's first product, then every
    chain's second, and so on (a chain after a chain costs the kernels a fifth
    of a forward launch: the compiler keeps the order it is given); each is
    the inverse of its own chunk, and so is each cotangent."""
    chunks = tuple(strictly_lower(seed, 0.3) for seed in (45, 47, 49))
    c, n = LA.CHUNK, len(chunks)
    forward = products(jax.make_jaxpr(LA._unit_lower_inverse)(chunks).jaxpr)
    assert [e.outvars[0].aval.shape for e in forward] == [(c, 2 * c)] * 6 * n
    for i in range(0, 6 * n, n):                    # a link's left operands: one chain each
        assert len({e.invars[0] for e in forward[i:i + n]}) == n
    cts = tuple(jax.random.normal(jax.random.PRNGKey(51 + i), (c, c)) for i in range(n))
    got, pull = jax.vjp(LA._unit_lower_inverse, chunks)
    for a, t, ct, pulled in zip(chunks, got, cts, pull(cts)[0]):
        close(t, inverse(a), 0.0)
        close(pulled, jax.vjp(inverse, a)[1](ct)[0], 0.0)


def test_inside_the_kernels_the_inverse_is_the_custom_vjp(monkeypatch):
    """``[1, 256, 2 * 128]`` through the kernels in the Pallas interpreter: the
    backward's body, ``jax.vjp`` of the block, holds a chunk's six products and
    the cotangent's two (the series and its transpose would be thirty), and
    outputs and all five gradients are those of the same kernels with the
    ten-product series under them."""
    from pyspark_tf_gke_tpu.ops.pallas import kda as K

    args = inputs(41, s=256)
    w = jax.random.normal(jax.random.PRNGKey(43), args[2].shape)
    run = lambda: (kda(*args, pallas=True, interpret=True),) + jax.grad(
        lambda *a: jnp.sum(kda(*a, pallas=True, interpret=True) * w), argnums=range(5))(*args)

    def inverse_products(fn, *more):
        kw = dict(heads=2, eps=EPS, mxu=jnp.dtype("float32"), interpret=True, caller="")
        traced = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args, None, *more)
        (call,) = [e for e in equations(traced.jaxpr) if e.primitive.name == "pallas_call"]
        return [e for e in products(call.params["jaxpr"])
                if float32_at_highest(e) and e.invars[0].aval.shape == (LA.CHUNK,) * 2]

    chunks = 256 // LA.CHUNK
    assert len(inverse_products(K._forward)) == 6 * chunks
    kept = jnp.zeros((1, 2, 1, 128, 128), jnp.float32)
    assert len(inverse_products(K._backward, kept, w)) == 8 * chunks
    try:
        with monkeypatch.context() as patch:
            patch.setattr(LA, "_unit_lower_inverse",
                          lambda chunks: tuple(ten_product_series(a) for a in chunks))
            jax.clear_caches()                  # the launches are jitted: trace them anew
            assert len(inverse_products(K._backward, kept, w)) == 30 * chunks
            want = run()
    finally:
        jax.clear_caches()
    for g, r in zip(run(), want):               # o dq dk dv dg dbeta
        close(g, r, 1e-5)
