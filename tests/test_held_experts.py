"""``models/moe.py::HeldExpertsLayer``: one chip's share of a dropless expert
layer, against a loop over tokens; and the shares of a whole expert-parallel
group, with the shared expert counted once, add up to the uncut layer. With
``swiglu`` experts (three matrices, the shared one some routed widths) and
with ``relu2`` ones (two matrices, a shared one of a width of its own)."""

import jax
import jax.numpy as jnp
import pytest

from pyspark_tf_gke_tpu.models.moe import HeldExpertsLayer

E, K, H, W, SCALE = 16, 4, 32, 24, 2.446


def layer(held, shared=1, slab_rows=0):
    return HeldExpertsLayer(num_experts=E, held=held, top_k=K, hidden_size=H,
                            intermediate_size=W, route_scale=SCALE, shared=shared,
                            slab_rows=slab_rows, dtype=jnp.float32)


@pytest.fixture(scope="module")
def whole():
    """Parameters of the uncut layer (all 16 experts held) and an input."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, H))
    params = layer((0, E)).init(jax.random.PRNGKey(1), x)["params"]
    noise = lambda p, i: p + 0.3 * jax.random.normal(jax.random.PRNGKey(i), p.shape)
    leaves, tree = jax.tree.flatten(params)
    return jax.tree.unflatten(tree, [noise(p, i) for i, p in enumerate(leaves)]), x


def share_of(params, first, count):
    cut = dict(params)
    for name in ("w_gate", "w_up", "w_down"):
        cut[name] = params[name][first:first + count]
    return cut


def expert(p, e, x):
    return (jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])) @ p["w_down"][e]


def shared_expert(p, x):
    s = p["shared"]
    return (jax.nn.silu(x @ s["gate"]["kernel"]) * (x @ s["up"]["kernel"])) @ s["down"]["kernel"]


def by_token(params, x, first, count, shared=True):
    """The layer's equations, one token at a time."""
    xt = x.reshape(-1, H)
    out = []
    for t in range(xt.shape[0]):
        s = jax.nn.sigmoid(xt[t] @ params["router"]["kernel"])
        chosen = jnp.argsort(-(s + params["router_bias"]))[:K]
        weights = SCALE * s[chosen] / s[chosen].sum()
        y = shared_expert(params, xt[t]) if shared else jnp.zeros(H)
        for j, e in enumerate(int(c) for c in chosen):
            if first <= e < first + count:
                y = y + weights[j] * expert(params, e, xt[t])
        out.append(y)
    return jnp.stack(out).reshape(x.shape)


@pytest.mark.parametrize("held,slab_rows", [((4, 4), 0), ((4, 4), 8), ((0, 8), 16), ((12, 4), 256)],
                         ids=["one_slab", "many_slabs", "half", "last_share"])
def test_held_experts_against_a_loop_over_tokens(whole, held, slab_rows):
    params, x = whole
    out, counters = layer(held, slab_rows=slab_rows).apply(
        {"params": share_of(params, *held)}, x)
    want = by_token(params, x, *held)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    # the counters: every assignment to a held expert, none dropped
    s = jax.nn.sigmoid(x.reshape(-1, H) @ params["router"]["kernel"])
    _, chosen = jax.lax.top_k(s + params["router_bias"], K)
    loads = [int(jnp.sum(chosen == e)) for e in range(held[0], held[0] + held[1])]
    assert int(counters["held_assignments"]) == sum(loads)
    assert int(counters["held_load_max"]) == max(loads)


def test_gradients_against_a_dense_masked_sum(whole):
    params, x = whole
    held = (4, 4)
    cut = share_of(params, *held)

    def dense(p, x):
        xt = x.reshape(-1, H)
        s = jax.nn.sigmoid(xt @ p["router"]["kernel"])
        _, chosen = jax.lax.top_k(s + p["router_bias"], K)
        kept = s * jnp.sum(jax.nn.one_hot(chosen, E), axis=-2)
        weights = SCALE * kept / kept.sum(-1, keepdims=True)
        y = shared_expert(p, xt)
        for e in range(held[1]):
            y = y + weights[:, held[0] + e, None] * expert(p, e, xt)
        return y.reshape(x.shape)

    for slab_rows in (0, 8):
        got = jax.grad(lambda p, x: jnp.sum(layer(held, slab_rows=slab_rows).apply(
            {"params": p}, x)[0] ** 2), argnums=(0, 1))(cut, x)
        want = jax.grad(lambda p, x: jnp.sum(dense(p, x) ** 2), argnums=(0, 1))(cut, x)
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * max(float(jnp.max(jnp.abs(r))), 1e-3)
    assert float(jnp.max(jnp.abs(got[0]["router_bias"]))) == 0.0      # a buffer


@pytest.mark.parametrize("count", [4, 8, 2])
def test_the_shares_add_up_to_the_uncut_layer(whole, count):
    """Every chip of the group computes its own experts' part; the shared
    expert is on every chip alike and counts once."""
    params, x = whole
    uncut, _ = layer((0, E)).apply({"params": params}, x)
    parts, assignments = 0.0, 0
    for first in range(0, E, count):
        part, counters = layer((first, count), shared=0).apply(
            {"params": {k: v for k, v in share_of(params, first, count).items()
                        if k != "shared"}}, x)
        parts = parts + part
        assignments += int(counters["held_assignments"])
    total = parts + shared_expert(params, x.reshape(-1, H)).reshape(x.shape)
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-4 * float(jnp.max(jnp.abs(uncut)))
    assert assignments == x.shape[0] * x.shape[1] * K          # no token dropped anywhere
    want = by_token(params, x, 0, E)
    assert float(jnp.max(jnp.abs(uncut - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


# -- relu2 experts: E(x) = relu(x W_up)^2 W_down, a shared expert of its own width -----

SHARED_WIDTH = 40


def relu2_layer(held, shared=1, slab_rows=0):
    return HeldExpertsLayer(num_experts=E, held=held, top_k=K, hidden_size=H,
                            intermediate_size=W, route_scale=SCALE, shared=shared,
                            slab_rows=slab_rows, dtype=jnp.float32, activation="relu2",
                            shared_width=SHARED_WIDTH)


@pytest.fixture(scope="module")
def whole_relu2():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, H))
    params = relu2_layer((0, E)).init(jax.random.PRNGKey(1), x)["params"]
    assert "w_gate" not in params and set(params["shared"]) == {"up", "down"}
    assert params["shared"]["up"]["kernel"].shape == (H, SHARED_WIDTH)
    noise = lambda p, i: p + 0.3 * jax.random.normal(jax.random.PRNGKey(i), p.shape)
    leaves, tree = jax.tree.flatten(params)
    return jax.tree.unflatten(tree, [noise(p, i) for i, p in enumerate(leaves)]), x


def relu2_share_of(params, first, count):
    return dict(params, w_up=params["w_up"][first:first + count],
                w_down=params["w_down"][first:first + count])


def relu2_expert(p, e, x):
    return jnp.square(jax.nn.relu(x @ p["w_up"][e])) @ p["w_down"][e]


def relu2_shared(p, x):
    s = p["shared"]
    return jnp.square(jax.nn.relu(x @ s["up"]["kernel"])) @ s["down"]["kernel"]


def relu2_by_token(params, x, first, count):
    xt = x.reshape(-1, H)
    out = []
    for t in range(xt.shape[0]):
        s = jax.nn.sigmoid(xt[t] @ params["router"]["kernel"])
        chosen = jnp.argsort(-(s + params["router_bias"]))[:K]
        weights = SCALE * s[chosen] / s[chosen].sum()
        y = relu2_shared(params, xt[t])
        for j, e in enumerate(int(c) for c in chosen):
            if first <= e < first + count:
                y = y + weights[j] * relu2_expert(params, e, xt[t])
        out.append(y)
    return jnp.stack(out).reshape(x.shape)


@pytest.mark.parametrize("held,slab_rows", [((4, 4), 0), ((4, 4), 8), ((0, 8), 16)],
                         ids=["one_slab", "many_slabs", "half"])
def test_relu2_held_experts_against_a_loop_over_tokens(whole_relu2, held, slab_rows):
    params, x = whole_relu2
    out, counters = relu2_layer(held, slab_rows=slab_rows).apply(
        {"params": relu2_share_of(params, *held)}, x)
    want = relu2_by_token(params, x, *held)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))
    s = jax.nn.sigmoid(x.reshape(-1, H) @ params["router"]["kernel"])
    _, chosen = jax.lax.top_k(s + params["router_bias"], K)
    loads = [int(jnp.sum(chosen == e)) for e in range(held[0], held[0] + held[1])]
    assert int(counters["held_assignments"]) == sum(loads)
    assert int(counters["held_load_max"]) == max(loads)


def test_relu2_gradients_against_a_dense_masked_sum(whole_relu2):
    params, x = whole_relu2
    held = (4, 4)
    cut = relu2_share_of(params, *held)

    def dense(p, x):
        xt = x.reshape(-1, H)
        s = jax.nn.sigmoid(xt @ p["router"]["kernel"])
        _, chosen = jax.lax.top_k(s + p["router_bias"], K)
        kept = s * jnp.sum(jax.nn.one_hot(chosen, E), axis=-2)
        weights = SCALE * kept / kept.sum(-1, keepdims=True)
        y = relu2_shared(p, xt)
        for e in range(held[1]):
            y = y + weights[:, held[0] + e, None] * relu2_expert(p, e, xt)
        return y.reshape(x.shape)

    for slab_rows in (0, 8):
        got = jax.grad(lambda p, x: jnp.sum(relu2_layer(held, slab_rows=slab_rows).apply(
            {"params": p}, x)[0] ** 2), argnums=(0, 1))(cut, x)
        want = jax.grad(lambda p, x: jnp.sum(dense(p, x) ** 2), argnums=(0, 1))(cut, x)
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * max(float(jnp.max(jnp.abs(r))), 1e-3)


@pytest.mark.parametrize("count", [4, 8, 2])
def test_the_relu2_shares_add_up_to_the_uncut_layer(whole_relu2, count):
    """As for ``swiglu``: every chip of the group computes its own experts'
    part; the shared expert, of its own width, is on every chip alike and
    counts once."""
    params, x = whole_relu2
    uncut, _ = relu2_layer((0, E)).apply({"params": params}, x)
    parts, assignments = 0.0, 0
    for first in range(0, E, count):
        part, counters = relu2_layer((first, count), shared=0).apply(
            {"params": {k: v for k, v in relu2_share_of(params, first, count).items()
                        if k != "shared"}}, x)
        parts = parts + part
        assignments += int(counters["held_assignments"])
    total = parts + relu2_shared(params, x.reshape(-1, H)).reshape(x.shape)
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-4 * float(jnp.max(jnp.abs(uncut)))
    assert assignments == x.shape[0] * x.shape[1] * K          # no token dropped anywhere
    want = relu2_by_token(params, x, 0, E)
    assert float(jnp.max(jnp.abs(uncut - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("first_held,load", [(4, "none"), (0, "some")])
def test_the_first_slab_walked_for_nothing_changes_nothing(whole_relu2, first_held, load,
                                                           monkeypatch):
    """The first slab is walked even where no token chose a held expert: the
    same outputs, counters and gradients as a loop that skips it, and at a
    load of nought the shared expert's part alone."""
    from pyspark_tf_gke_tpu.models import moe

    params, x = whole_relu2
    held = (first_held, 4)
    cut = relu2_share_of(params, *held)
    if load == "none":          # every token sent to the experts held elsewhere
        cut = dict(cut, router_bias=params["router_bias"].at[4:8].set(-100.0))
    layer = relu2_layer(held)

    def run():
        (out, counters), pull = jax.vjp(lambda p, x: layer.apply({"params": p}, x), cut, x)
        return out, counters, pull((jnp.ones_like(out), jax.tree.map(jnp.zeros_like, counters)))

    walked = run()
    monkeypatch.setattr(moe, "_slabs_walked", lambda total, rows: -(-total // rows))
    skipped = run()
    assert (int(walked[1]["held_assignments"]) == 0) == (load == "none")
    if load == "none":
        want = relu2_shared(params, x.reshape(-1, H)).reshape(x.shape)
        assert float(jnp.max(jnp.abs(walked[0] - want))) < 1e-5 * float(jnp.max(jnp.abs(want)))
    for a, b in zip(jax.tree.leaves(skipped), jax.tree.leaves(walked)):
        assert bool(jnp.all(jnp.isfinite(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * max(float(jnp.max(jnp.abs(a))), 1.0)
