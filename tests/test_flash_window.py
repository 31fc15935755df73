"""Flash attention inside a window (``flash_attention(window=...)``): the three
kernels in interpret mode against dense attention for windows under, at, a
multiple of and not a multiple of the grid block and of the sequence's length
or more; the schedule's count of what it computes against a count of the mask;
which full steps a mirrored window unrolls beside its edge strips, in the
schedule and in the launches' programs; the windowed launches' names; and with
no window the launches are the
parent's, operation for operation (``tests/test_flash_value_width.py`` holds
their digests)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.ops.attention import dot_product_attention

F = importlib.import_module("pyspark_tf_gke_tpu.ops.pallas.flash_attention")


def qkv(seed, s, d, dv, b=1, h=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d)), jax.random.normal(ks[1], (b, s, h, d)),
            jax.random.normal(ks[2], (b, s, h, dv)), jax.random.normal(ks[3], (b, s, h, dv)))


def visible(s, window):
    rows, keys = np.arange(s)[:, None], np.arange(s)[None, :]
    return (keys <= rows) & (rows - keys < window)


def test_the_dense_window_is_the_rows_own_position_and_those_before_it():
    q, k, v, _ = qkv(0, 16, 8, 8)
    for window in (1, 5, 16, 40):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 8 ** -0.5
        probs = jax.nn.softmax(jnp.where(visible(16, window), scores, -jnp.inf), axis=-1)
        want = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        got = dot_product_attention(q, k, v, causal=True, window=window)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, window=4)


# (S, block, D, Dv): one tile a block at the hybrid decoders' widths (GQA 128, MLA
# 192 / 128) and two tiles a block at 64
SHAPES = {"d128": (512, 128, 128, 128), "mla": (512, 128, 192, 128), "d64": (1024, 256, 64, 64)}
# windows by what they are to the block: under it, the block, a multiple, not one;
# then a multiple whose edge branch unrolls two full steps
WINDOWS = {"d128": (64, 128, 256, 192, 384), "mla": (128, 384), "d64": (128, 256, 512, 384, 768)}


@pytest.mark.parametrize("shape,window", [(n, w) for n in SHAPES for w in WINDOWS[n]])
def test_forward_and_both_backward_kernels_inside_a_window(shape, window):
    s, block, d, dv = SHAPES[shape]
    q, k, v, w = qkv(1, s, d, dv)
    flash = lambda q, k, v: F.flash_attention(q, k, v, causal=True, window=window,
                                              block_q=block, block_k=block, interpret=True)
    dense = lambda q, k, v: dot_product_attention(q, k, v, causal=True, window=window)
    assert float(jnp.max(jnp.abs(flash(q, k, v) - dense(q, k, v)))) < 2e-5
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert float(jnp.max(jnp.abs(g - r))) < 5e-5 * max(1.0, float(jnp.max(jnp.abs(r))))
    # the mirrored schedule where the window is whole blocks, the banded one elsewhere
    assert F._schedule(s, block, block, True, window=window).mirrored == (window % block == 0)


@pytest.mark.parametrize("s,block,window", [
    (128, 32, 8), (128, 32, 32), (128, 32, 96), (128, 32, 48), (1024, 256, 256),
    (1024, 256, 384), (1024, 256, 128), (8192, 512, 2048), (8192, 512, 1000), (8192, 512, 512)])
@pytest.mark.parametrize("walks_rows", [False, True], ids=["walks_keys", "walks_rows"])
def test_the_schedule_computes_what_the_mask_shows_and_throws_the_rest_away(
        s, block, window, walks_rows):
    sched = F._schedule(s, block, block, True, walks_rows, window)
    computed, masked, thrown = sched.counts()
    rows = np.arange(s)
    assert computed - thrown == int(np.sum(np.minimum(rows + 1, window)))
    assert 0 < masked <= computed <= F._schedule(s, block, block, True, walks_rows).counts()[0]
    if s <= 1024:
        assert computed - thrown == int(visible(s, window).sum())
    if sched.mirrored:   # only the edge and the diagonal tiles pass through a mask
        t, edges = sched.tile, sum(bool(sched.has_edge(i)) for i in range(s // block))
        assert masked == (s // block + edges) * (block // t) * t * t


def test_a_window_layer_of_the_cell_computes_under_half_of_the_causal_schedule():
    windowed = F._schedule(8192, 512, 512, True, window=2048).counts()
    causal = F._schedule(8192, 512, 512, True).counts()
    assert windowed[0] - windowed[2] == 2048 * 2049 // 2 + 6144 * 2048 == 14_681_088
    assert causal[0] - causal[2] == 8192 * 8193 // 2
    assert 0.45 < windowed[0] / causal[0] < 0.46


@pytest.mark.parametrize("window,steps", [(2048, (36, 6)), (None, (0, 120)), (1000, (0, 29))],
                         ids=["mirrored", "no_window", "banded"])
@pytest.mark.parametrize("walks_rows", [False, True], ids=["walks_keys", "walks_rows"])
def test_a_window_layer_of_the_cell_unrolls_the_full_steps_beside_its_edge_strips(
        window, steps, walks_rows):
    """At the cell's shape each block with an edge square (12 of 16) takes
    three full steps in straight-line code; the four without one loop over
    0 + 1 + 2 + 3. Without a mirrored window every full step is looped. The
    work is the same: ``counts`` is what the schedule computed before."""
    sched = F._schedule(8192, 512, 512, True, walks_rows, window)
    assert sched.looped_steps() == steps
    if window == 2048:
        assert sched.edge_steps() == 3
        assert sched.counts() == (15_597_568, 1_835_008, 916_480)


def test_a_static_count_that_disagrees_with_the_schedule_is_refused():
    """A walk wider than the grid block (no ``_schedule`` makes one) leaves a
    block no full step, where the window's count says two."""
    sched = F._Schedule(8192, 512, 1024, 128, True, False, window=2048)
    with pytest.raises(ValueError, match="0 full steps beside its edge square, not 2"):
        sched.edge_steps()


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _count(jaxpr, name):
    """Equations named ``name`` in ``jaxpr`` and every jaxpr inside it."""
    return sum((e.primitive.name == name) + sum(_count(j, name) for j in _subjaxprs(e))
               for e in jaxpr.eqns)


def _kernels(jaxpr):
    """The body of every ``pallas_call`` in ``jaxpr``, in launch order."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e.params["jaxpr"]
        else:
            for j in _subjaxprs(e):
                yield from _kernels(j)


def test_a_mirrored_launch_unrolls_its_full_steps_inside_the_edge_branch():
    """S 2048, block 512, window 1024: one full step beside the edge square's
    four strips in the ``cond``'s true branch, the loop alone in its false
    branch, and nothing looped outside the ``cond``."""
    x = jax.ShapeDtypeStruct((4, 2048, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((4, 1, 2048), jnp.float32)
    kw = dict(causal=True, block_q=512, block_k=512, interpret=False, caller="a", window=1024)
    fwd = jax.make_jaxpr(lambda q, k, v: F._fwd_call(q, k, v, None, None, **kw))(x, x, x)
    bwd = jax.make_jaxpr(lambda q, k, v, l, o, do: F._bwd_call(
        q, k, v, None, l, o, do, None, None, **kw))(x, x, x, lse, x, x)
    bodies = [*_kernels(fwd.jaxpr), *_kernels(bwd.jaxpr)]
    # products a piece: q k and p v forward; q k, dO v and ds k in dQ; k q, p dO,
    # v dO and ds q in dK/dV
    for body, products in zip(bodies, (2, 3, 4), strict=True):
        (cond,) = [e for e in body.eqns if e.primitive.name == "cond"]
        assert not [e for e in body.eqns if e.primitive.name == "while"]
        looped, unrolled = (b.jaxpr for b in cond.params["branches"])
        assert (_count(looped, "while"), _count(unrolled, "while")) == (1, 0)
        assert _count(looped, "dot_general") == products
        assert _count(unrolled, "dot_general") == (1 + 4) * products


def _launches(window, s=2048, block=512, caller="a"):
    x = jax.ShapeDtypeStruct((4, s, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((4, 1, s), jnp.float32)
    kw = dict(causal=True, block_q=block, block_k=block, interpret=False, caller=caller)
    if window != "absent":
        kw["window"] = window
    fwd = jax.make_jaxpr(lambda q, k, v: F._fwd_call(q, k, v, None, None, **kw))(x, x, x)
    bwd = jax.make_jaxpr(lambda q, k, v, l, o, do: F._bwd_call(
        q, k, v, None, l, o, do, None, None, **kw))(x, x, x, lse, x, x)
    return str(fwd), str(bwd)


def test_windowed_launches_carry_names_of_their_own():
    """XLA names a Mosaic call after the innermost scope at the launch
    (``ops/pallas/scope.py``): the component before ``pallas_call`` in the
    launch's location."""
    x = jax.ShapeDtypeStruct((4, 2048, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((4, 1, 2048), jnp.float32)

    def lowered(window):
        kw = dict(causal=True, block_q=512, block_k=512, interpret=True,
                  caller="attention._causal_attend", window=window)
        both = lambda q, k, v, l, o, do: (F._fwd_call(q, k, v, None, None, **kw), F._bwd_call(
            q, k, v, None, l, o, do, None, None, **kw))
        return jax.jit(both).lower(x, x, x, lse, x, x).as_text(debug_info=True)

    text = lowered(1024)
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"attention._causal_attend.window_{kernel}/pallas_call" in text
        assert f"attention._causal_attend.{kernel}/pallas_call" not in text
    text = lowered(None)
    assert "window_flash" not in text
    assert "attention._causal_attend.flash_fwd/pallas_call" in text


def test_no_window_and_a_window_of_the_sequences_length_are_the_causal_launches():
    assert _launches(None) == _launches("absent")
    q, k, v, _ = qkv(3, 256, 16, 16)
    traced = lambda **kw: str(jax.make_jaxpr(lambda *a: F.flash_attention(
        *a, causal=True, block_q=64, block_k=64, interpret=True, **kw))(q, k, v))
    assert traced() == traced(window=None) == traced(window=256) == traced(window=4096)
    assert traced(window=128) != traced()


def test_a_window_has_to_be_causal_and_of_a_key_at_least():
    q, k, v, _ = qkv(4, 128, 16, 16)
    with pytest.raises(ValueError, match="causal"):
        F.flash_attention(q, k, v, window=64, interpret=True)
    with pytest.raises(ValueError, match="at least one key"):
        F.flash_attention(q, k, v, causal=True, window=0, interpret=True)


def test_the_first_rows_of_a_window_layer_see_everything_before_them():
    """Rows under the window's length are plain causal attention; later rows
    are not (the window binds)."""
    q, k, v, _ = qkv(5, 256, 16, 16)
    kw = dict(causal=True, block_q=64, block_k=64, interpret=True)
    windowed, causal = F.flash_attention(q, k, v, window=128, **kw), F.flash_attention(q, k, v, **kw)
    assert float(jnp.max(jnp.abs(windowed[:, :128] - causal[:, :128]))) < 1e-6
    assert float(jnp.max(jnp.abs(windowed[:, 128:] - causal[:, 128:]))) > 1e-3
