"""``models/moe.py::HeldExpertsLayer``: one chip's share of a dropless expert
layer, against a loop over tokens; and the shares of a whole expert-parallel
group, with the shared expert counted once, add up to the uncut layer. With
``swiglu`` experts (three matrices, the shared one some routed widths) and
with ``relu2`` ones (two matrices, a shared one of a width of its own). The
walk over the held assignments in fine steps, each against a window of the
held experts: every load, the rows it takes, and that a step holds no
operation of the layer's size."""

import jax
import jax.numpy as jnp
import numpy as np
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


def dense_sum(params, x, held, one_expert, shared):
    """The layer as a masked sum over the held experts, every token through
    each of them."""
    xt = x.reshape(-1, H)
    s = jax.nn.sigmoid(xt @ params["router"]["kernel"])
    _, chosen = jax.lax.top_k(s + params["router_bias"], K)
    kept = s * jnp.sum(jax.nn.one_hot(chosen, E), axis=-2)
    weights = SCALE * kept / kept.sum(-1, keepdims=True)
    y = shared(params, xt)
    for e in range(held[1]):
        y = y + weights[:, held[0] + e, None] * one_expert(params, e, xt)
    return y.reshape(x.shape)


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

    dense = lambda p, x: dense_sum(p, x, held, expert, shared_expert)

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

    dense = lambda p, x: dense_sum(p, x, held, relu2_expert, relu2_shared)

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
    # the rows walked are the one thing that tells the two apart, at a load of nought
    rows = moe.step_and_window(x.shape[0] * x.shape[1], K, held[1], E)[0]
    assert int(walked[1].pop("held_rows_walked")) == (rows if load == "none" else
                                                      int(skipped[1]["held_rows_walked"]))
    assert int(skipped[1].pop("held_rows_walked")) == (0 if load == "none" else rows)
    for a, b in zip(jax.tree.leaves(skipped), jax.tree.leaves(walked)):
        assert bool(jnp.all(jnp.isfinite(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * max(float(jnp.max(jnp.abs(a))), 1.0)


# -- the walk in fine steps: any load, the rows it takes, nothing layer-sized in a step ----

def steps_of_the_walk(loads, rows, window):
    """The walk's steps from the held experts' loads, as the layer takes them:
    a step ends after ``rows`` assignments or at the last expert of its window
    (the ``window`` experts from the first that still has an assignment)."""
    ends, start, steps = np.cumsum(loads), 0, 0
    while start < ends[-1] or steps == 0:
        first = min(int(np.sum(ends <= start)), len(loads) - window)
        start, steps = min(start + rows, int(ends[first + window - 1])), steps + 1
    return steps


FAMILIES = {"swiglu": (layer, share_of, expert, shared_expert, "whole"),
            "relu2": (relu2_layer, relu2_share_of, relu2_expert, relu2_shared, "whole_relu2")}

# held, slab_rows, tokens a row, the router's bias on experts (those not named keep theirs)
LOADS = {
    # 4,096 tokens: the default step, 512 rows against a window of 2, and a load near 4,096
    "default_step": ((4, 4), 0, 2048, {}),
    # expert 5 chosen by every token beside two empty ones: it spans six steps of
    # 8, and the step that ends it would run past its window's empty second expert
    "one_busy_expert": ((4, 4), 8, 24, {5: 100.0, 4: -100.0, 6: -100.0}),
    "load_nought": ((4, 4), 8, 24, {4: -100.0, 5: -100.0, 6: -100.0, 7: -100.0}),
    "every_assignment_held": ((0, E), 16, 24, {}),
}


@pytest.mark.parametrize("load", list(LOADS))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_walk_at_any_load_and_the_rows_it_takes(request, family, load):
    from pyspark_tf_gke_tpu.models import moe

    make, cut, one_expert, shared, fixture = FAMILIES[family]
    held, slab_rows, seq, bias = LOADS[load]
    params, _ = request.getfixturevalue(fixture)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, seq, H))
    for e, b in bias.items():
        params = dict(params, router_bias=params["router_bias"].at[e].set(b))
    mine = cut(params, *held)
    run = lambda p, x: make(held, slab_rows=slab_rows).apply({"params": p}, x)
    out, counters = run(mine, x)
    want = dense_sum(mine, x, held, one_expert, shared)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))

    tokens = 2 * seq
    s = jax.nn.sigmoid(x.reshape(-1, H) @ params["router"]["kernel"])
    _, chosen = jax.lax.top_k(s + params["router_bias"], K)
    loads = [int(jnp.sum(chosen == e)) for e in range(held[0], held[0] + held[1])]
    total = sum(loads)
    assert int(counters["held_assignments"]) == total
    assert (total == 0) == (load == "load_nought")
    assert (total == tokens * K) == (load == "every_assignment_held")
    rows, window = moe.step_and_window(tokens, K, held[1], E, slab_rows)
    assert (rows, window) == {"default_step": (512, 2), "one_busy_expert": (8, 2),
                              "load_nought": (8, 2), "every_assignment_held": (16, 3)}[load]
    if load == "default_step":
        assert total % rows and total > 4 * rows
    if load == "one_busy_expert":
        assert loads[1] == tokens and loads[0] == loads[2] == 0 < loads[3]
    steps = steps_of_the_walk(loads, rows, window)
    assert int(counters["held_rows_walked"]) == steps * rows
    # at most a step of slack, and one more for every window a step may end at
    assert total <= steps * rows <= max(rows, (total // rows + -(-held[1] // window)) * rows)
    assert steps >= -(-total // rows)

    loss = lambda f: lambda p, x: jnp.sum(f(p, x) ** 2)
    got = jax.grad(loss(lambda p, x: run(p, x)[0]), argnums=(0, 1))(mine, x)
    ref = jax.grad(loss(lambda p, x: dense_sum(p, x, held, one_expert, shared)),
                   argnums=(0, 1))(mine, x)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(ref)):
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * max(float(jnp.max(jnp.abs(r))), 1e-3), \
            jax.tree_util.keystr(path)
    assert float(jnp.max(jnp.abs(got[0]["router"]["kernel"]))) > 0 or load == "load_nought"


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_no_operation_inside_a_step_is_the_size_of_the_layer(family):
    """In the forward's loop and in the backward's, nothing reads or writes an
    array of the layer's size (``[T, H]``, ``[T * k, H]``, a weight leaf or
    its transpose) but the in-place update of a carry (a scatter-add, a
    ``dynamic_update_slice``), the gathers and slices that take a step's rows
    and window out of one, and ``ragged_dot`` with the weights' bf16 copies on
    its right, of which it reads the groups that have rows (the structure the
    chip showed cheaper than a sliced window of them: PERF.md §6, PR 36); and
    those copies are made before the loops, once each way."""
    tokens, rows = 64, 8
    make = FAMILIES[family][0]
    module = make((4, 4), slab_rows=rows).clone(dtype=jnp.bfloat16)
    x = jnp.zeros((2, tokens // 2, H), jnp.bfloat16)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), x))["params"]
    traced = jax.make_jaxpr(jax.value_and_grad(lambda p, x: jnp.sum(
        module.apply({"params": p}, x)[0].astype(jnp.float32)), argnums=(0, 1)))(params, x)
    leaves = {(4, H, W), (4, W, H)}
    large = leaves | {(tokens, H), (tokens * K, H)}
    reads, updates = {"gather", "dynamic_slice"}, {"scatter-add", "dynamic_update_slice"}
    shape = lambda v: tuple(getattr(v.aval, "shape", ()))

    loops = [e for e in _eqns(traced.jaxpr) if e.primitive.name == "while"]
    assert len(loops) == 2                                   # the forward's and the backward's
    inside = 0
    for loop in loops:
        for eqn in _eqns(loop.params["body_jaxpr"].jaxpr):
            if eqn.primitive.name in ("pjit", "jit", "custom_jvp_call", "custom_vjp_call"):
                continue                                     # its equations come by themselves
            touched = [v for v in (*eqn.invars, *eqn.outvars) if shape(v) in large]
            if not touched:
                continue
            inside += 1
            name = eqn.primitive.name
            assert name in reads | updates | {"ragged_dot_general"}, (
                name, [shape(v) for v in touched])
            if name in updates:     # a carry updated in place: float32, the sum over steps
                assert shape(eqn.invars[0]) == shape(eqn.outvars[0]) in large
                assert eqn.outvars[0].aval.dtype == jnp.float32, eqn
                continue
            # a step's rows or window taken out, or multiplied by their groups: nothing large made
            assert all(shape(v) not in large for v in eqn.outvars), eqn
            if name == "ragged_dot_general":      # the weights, bf16, and no other large operand
                assert [shape(v) in leaves for v in eqn.invars] == [False, True, False], eqn
                assert eqn.invars[1].aval.dtype == jnp.bfloat16, eqn
    assert inside >= 15                                      # a dozen and more such places
    # the bf16 copies of the weight leaves: outside the loops, the forward's and the backward's
    bodies = {id(e) for loop in loops for e in _eqns(loop.params["body_jaxpr"].jaxpr)}
    casts = [e for e in _eqns(traced.jaxpr) if e.primitive.name == "convert_element_type"
             and shape(e.invars[0]) in leaves and e.outvars[0].aval.dtype == jnp.bfloat16]
    assert len(casts) == 2 * (3 if family == "swiglu" else 2)
    assert not any(id(e) in bodies for e in casts)
