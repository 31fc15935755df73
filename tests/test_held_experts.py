"""``models/moe.py::HeldExpertsLayer``: one chip's share of a dropless expert
layer, against a loop over tokens; and the shares of a whole expert-parallel
group, with the shared expert counted once, add up to the uncut layer."""

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
