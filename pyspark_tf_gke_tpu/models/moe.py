"""Mixture-of-Experts FFN with expert parallelism over the ``ep`` axis.

Absent from the reference (SURVEY §2b: expert parallelism "absent"), but a
first-class scale axis here. Designed for the MXU + pjit, GShard/Switch
style:

* **Dense dispatch, static shapes**: routing is expressed as einsums with
  a ``[B, S, E, C]`` one-hot dispatch tensor (capacity ``C`` per expert per
  batch group) — no gathers, no dynamic shapes, so XLA tiles everything
  onto the MXU and inserts the token all-to-alls implied by the sharding
  annotations.
* **Expert parallelism via logical annotation**: expert-stacked weights
  carry the ``expert`` logical axis (→ ``ep`` mesh axis,
  ``parallel.sharding.LOGICAL_RULES``); the dispatched activation tensor
  is constrained to ``("expert", ...)`` so tokens physically travel to
  their expert's chip over ICI (XLA all-to-all), compute locally, and
  travel back — composing freely with dp/fsdp/tp.
* **Top-k routing (k=1 Switch, k=2 GShard)** with softmax gates, capacity
  dropping (overflow tokens fall through the residual), and the
  load-balance auxiliary loss ``E * Σ_e f_e · p_e``.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from pyspark_tf_gke_tpu.ops.pallas.scope import part_scope


class MoELayer(nn.Module):
    """Expert-parallel FFN block: ``x -> combine(expert_ffn(dispatch(x)))``.

    Shape-preserving on ``[B, S, H]``; returns ``(out, aux_loss)``.
    """

    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        b, s, h = x.shape
        E, k = self.num_experts, self.top_k
        # Per-(batch-row) expert capacity; ≥1 so tiny test shapes route.
        C = max(1, int(self.capacity_factor * k * s / E))

        router = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("embed", "expert")
            ),
            (h, E), jnp.float32,
        )
        w_in = self.param(
            "w_in",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("expert", "embed", "mlp")
            ),
            (E, h, self.intermediate_size), jnp.float32,
        )
        b_in = self.param(
            "b_in",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("expert", "mlp")),
            (E, self.intermediate_size), jnp.float32,
        )
        w_out = self.param(
            "w_out",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("expert", "mlp", "embed")
            ),
            (E, self.intermediate_size, h), jnp.float32,
        )
        b_out = self.param(
            "b_out",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("expert", "embed")),
            (E, h), jnp.float32,
        )

        # ---- routing (float32 throughout) --------------------------------
        gates = jax.nn.softmax(
            x.astype(jnp.float32) @ router, axis=-1
        )  # [B,S,E]

        dispatch = jnp.zeros((b, s, E, C), jnp.float32)
        combine = jnp.zeros((b, s, E, C), jnp.float32)
        remaining = gates
        # Track how many slots each expert has used per batch row as the
        # k routing rounds claim positions.
        used = jnp.zeros((b, E), jnp.float32)
        top1_mask = None
        for _ in range(k):
            idx = jnp.argmax(remaining, axis=-1)  # [B,S]
            mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [B,S,E]
            # Queue position of each token at its chosen expert this round.
            pos = jnp.cumsum(mask, axis=1) * mask - mask + used[:, None, :]  # [B,S,E]
            keep = mask * (pos < C)  # overflow tokens dropped
            pos_c = jax.nn.one_hot(
                jnp.sum(pos * keep, axis=-1).astype(jnp.int32), C, dtype=jnp.float32
            )  # [B,S,C]
            slot = keep[..., None] * pos_c[:, :, None, :]  # [B,S,E,C]
            gate_k = jnp.sum(remaining * keep, axis=-1, keepdims=True)  # [B,S,1]
            dispatch = dispatch + slot
            combine = combine + slot * gate_k[..., None]
            used = used + jnp.sum(keep, axis=1)
            if top1_mask is None:
                top1_mask = mask
            remaining = remaining * (1.0 - mask)

        # Normalize combine weights over the k selected experts. For k == 1
        # the raw softmax gate must be kept (Switch Transformer): dividing by
        # itself would make every kept weight exactly 1 and cut the router
        # out of the differentiable forward path, leaving only the aux loss
        # to train it.
        if k > 1:
            denom = jnp.sum(combine, axis=(2, 3), keepdims=True)
            combine = combine / jnp.maximum(denom, 1e-9)

        # Switch-style load-balance aux loss: E * Σ_e fraction_e · prob_e.
        frac = jnp.mean(top1_mask, axis=(0, 1))  # [E]
        prob = jnp.mean(gates, axis=(0, 1))  # [E]
        aux_loss = E * jnp.sum(frac * prob)

        # ---- dispatch → expert FFN → combine -----------------------------
        xe = jnp.einsum("bsec,bsh->ebch", dispatch.astype(self.dtype),
                        x.astype(self.dtype))
        xe = nn.with_logical_constraint(xe, ("expert", "batch", None, "embed"))
        hmid = jnp.einsum("ebch,ehi->ebci", xe, w_in.astype(self.dtype))
        hmid = nn.gelu(hmid + b_in[:, None, None, :].astype(self.dtype),
                       approximate=True)
        hmid = nn.with_logical_constraint(hmid, ("expert", "batch", None, "mlp"))
        ye = jnp.einsum("ebci,eih->ebch", hmid, w_out.astype(self.dtype))
        ye = ye + b_out[:, None, None, :].astype(self.dtype)
        ye = nn.with_logical_constraint(ye, ("expert", "batch", None, "embed"))
        out = jnp.einsum("bsec,ebch->bsh", combine.astype(self.dtype), ye)
        return out.astype(x.dtype), aux_loss.astype(jnp.float32)


# the walk's step is this part of the held experts' mean load, and a step's
# window the experts that this many steps span at that load: settled by a sweep
# on the chip with the layer alone at three cells' sizes (PERF.md §6, PR 36)
STEP_SHARE = 8
WINDOW_STEPS = 2

# [rows, K] x [rows, N] -> [groups, K, N]: a group's rows contracted
_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())), lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[])


def step_and_window(tokens, top_k, count, num_experts, rows=0):
    """``(rows, experts)`` of one step of the walk: ``rows`` sorted held
    assignments (``rows`` as given, or a ``STEP_SHARE``-th of the held experts'
    mean load rounded up to 512: XLA's ``ragged_dot`` takes 768 rows in the time
    of 1,024) against a window of ``experts`` consecutive held experts (those
    that ``WINDOW_STEPS`` steps span at the mean load, at least 2). From the
    shapes alone, the same for every caller."""
    mean_load = max(tokens * top_k * count // num_experts, 1)
    rows = min(rows or -(-mean_load // (STEP_SHARE * 512)) * 512, tokens * top_k)
    return rows, min(max(-(-WINDOW_STEPS * rows * count // mean_load), 2), count)


def _slabs_walked(total, rows):
    """``ceil(total / rows)`` and never nought, the least number of steps: the
    first step is walked whatever the load (at a load of nought all its rows
    are padding), so a layer costs the same at one held assignment or none."""
    return jnp.maximum(-(-total // rows), 1)


def _walk(step, carry, ends, rows):
    """``(steps, carry)`` after ``taken, carry = step(start, carry)`` from the
    first of the sorted held assignments to the last (``ends[-1]`` of them). A
    step takes ``rows`` assignments or ends short, at the last expert of its
    window, so the steps are :func:`_slabs_walked` at least and ``load // rows
    + ceil(count / window)`` at most."""
    def more(state):
        start, steps, _ = state
        return (start < ends[-1]) | (steps < _slabs_walked(ends[-1], rows))

    def one(state):
        start, steps, carry = state
        taken, carry = step(start, carry)
        return start + taken, steps + 1, carry

    nought = jnp.zeros((), ends.dtype)
    # the loop is the step's part ``experts_walk``: entered here, where the
    # rules of ``_held_experts`` run it, since each is traced apart from its call
    with part_scope("experts_walk"):
        _, steps, carry = jax.lax.while_loop(more, one, (nought, nought, carry))
    return steps, carry


def _step(start, order, ends, loads, rows, window):
    """The step that begins at the sorted held assignment ``start``: its
    ``rows`` assignments (``mine``, indices into ``[T * k]``); how many of them
    it takes, those of its ``window`` of held experts, from the first expert
    that still has one; which rows those are (``valid``: the others are the
    next step's, or past the load); their group sizes over all held experts
    and over the window alone, and where the window begins."""
    count = ends.shape[0]
    mine = jax.lax.dynamic_slice(order, (start,), (rows,))
    first = jnp.minimum(jnp.sum(ends <= start), count - window)
    near = jax.lax.dynamic_slice(ends, (first,), (window,))
    own = jax.lax.dynamic_slice(loads, (first,), (window,))
    sizes = jnp.clip(near - start, 0, rows) - jnp.clip(near - own - start, 0, rows)
    taken = jnp.sum(sizes)
    # rows that do not count join the window's last group: they are computed
    # on real tokens and discarded, never left undefined
    sizes = sizes.at[-1].add(rows - taken)
    held = jax.lax.dynamic_update_slice(jnp.zeros_like(ends), sizes, (first,))
    return mine, taken, jnp.arange(rows) < taken, held, sizes, first


def _activation(pre):
    """``silu(x W_gate) * (x W_up)`` of ``pre = [x W_gate, x W_up]``, or
    ``relu(x W_up)^2`` of ``[x W_up]``."""
    if len(pre) == 1:
        return jnp.square(jax.nn.relu(pre[0]))
    return jax.nn.silu(pre[0]) * pre[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _held_experts(xt, flat_w, w_gate, w_up, w_down, order, ends, loads, rows,
                  window, k, dtype):
    """``(out, steps)``: the sorted held assignments ``order``, each the
    weighted output of its expert added to its token's row (``[T, H]``
    float32), and the steps walked. ``ends`` and ``loads`` are the held
    experts' cumulative and own assignment counts; ``w_gate`` is ``None`` for
    experts without a gate (``relu(x W_up)^2 W_down``).

    A loop whose trip count follows the load (:func:`_walk`), in the forward
    and in the backward alike, so no step's operands are kept; a step costs
    its ``rows`` and, in the backward, its ``window`` of experts, and nothing
    the size of the layer: the weights' ``dtype`` copies are made before the
    loop (``ragged_dot`` reads the groups that have rows), and the sums over
    steps are float32 carries updated in place."""
    return _held_experts_fwd(xt, flat_w, w_gate, w_up, w_down, order, ends, loads,
                             rows, window, k, dtype)[0]


def _held_experts_fwd(xt, flat_w, w_gate, w_up, w_down, order, ends, loads, rows,
                      window, k, dtype):
    into = [w.astype(dtype) for w in (w_gate, w_up) if w is not None]
    back = w_down.astype(dtype)

    def step(start, out):
        mine, taken, valid, held, _, _ = _step(start, order, ends, loads, rows, window)
        token = mine // k
        xs = xt[token].astype(dtype)
        mid = _activation([jax.lax.ragged_dot(xs, w, held) for w in into])
        ys = jax.lax.ragged_dot(mid, back, held, preferred_element_type=jnp.float32)
        ys = jnp.where(valid[:, None], ys * flat_w[mine][:, None], 0.0)
        return taken, out.at[token].add(ys)

    steps, out = _walk(step, jnp.zeros(xt.shape, jnp.float32), ends, rows)
    return (out, steps), ((xt, flat_w, w_gate, w_up, w_down), (order, ends, loads))


def _held_experts_bwd(rows, window, k, dtype, residuals, cotangents):
    (xt, flat_w, w_gate, w_up, w_down), (order, ends, loads) = residuals
    g = cotangents[0]                   # the steps have no gradient
    into = [w.astype(dtype) for w in (w_gate, w_up) if w is not None]
    back = w_down.astype(dtype)
    # the transposes the rows' gradients are multiplied by, made once as well
    *turned_into, turned_back = [jnp.swapaxes(w, 1, 2) for w in (*into, back)]
    float32 = dict(preferred_element_type=jnp.float32)

    def step(start, carry):
        dxt, dflat, dinto, dback = carry
        mine, taken, valid, held, sizes, first = _step(start, order, ends, loads, rows,
                                                      window)
        token = mine // k
        xs = xt[token].astype(dtype)
        mid, pull = jax.vjp(_activation, [jax.lax.ragged_dot(xs, w, held) for w in into])
        ys = jax.lax.ragged_dot(mid, back, held, **float32)
        gs = jnp.where(valid[:, None], g[token], 0.0)
        dys = (gs * flat_w[mine][:, None]).astype(dtype)
        dpre, = pull(jax.lax.ragged_dot(dys, turned_back, held))
        dxs = sum(jax.lax.ragged_dot(d, w, held, **float32)
                  for d, w in zip(dpre, turned_into))

        def add(total, rows_in, rows_out):
            """A weight's gradient from this step's rows, ``[window, K, N]``
            float32, added where the window's experts lie."""
            part = jax.lax.ragged_dot_general(rows_in, rows_out, sizes, _ROWS_CONTRACTED,
                                              **float32)
            there = jax.lax.dynamic_slice_in_dim(total, first, window)
            return jax.lax.dynamic_update_slice_in_dim(total, there + part, first, 0)

        return taken, (dxt.at[token].add(dxs),
                       dflat.at[mine].add(jnp.sum(ys * gs, axis=-1)),
                       [add(t, xs, d) for t, d in zip(dinto, dpre)],
                       add(dback, mid, dys))

    zeros = lambda like: jnp.zeros(like.shape, jnp.float32)
    _, (dxt, dflat, dinto, dback) = _walk(
        step, (zeros(xt), zeros(flat_w), [zeros(w) for w in into], zeros(back)),
        ends, rows)
    dinto = [None] * (w_gate is None) + [d.astype(w_up.dtype) for d in dinto]
    return (dxt.astype(xt.dtype), dflat.astype(flat_w.dtype), *dinto,
            dback.astype(w_down.dtype), None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class SwiGLU(nn.Module):
    """``(silu(x W_gate) * (x W_up)) W_down``, no biases."""

    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=nn.initializers.normal(stddev=0.02), name=name)

        mid = jax.nn.silu(dense(self.intermediate_size, "gate")(x)) \
            * dense(self.intermediate_size, "up")(x)
        return dense(self.hidden_size, "down")(mid)


class HeldExpertsLayer(nn.Module):
    """One chip's share of a dropless expert layer (expert parallelism seen
    from one member of the group).

    The router keeps its full width ``num_experts`` and its ``top_k``; this
    chip holds the experts ``held = (first, count)`` and computes their part
    of ``y = sum_chosen w_e E_e(x)``, with ``E(x) = (silu(x W1) * (x W3)) W2``
    (``activation`` ``swiglu``) or ``E(x) = relu(x W_up)^2 W_down`` (``relu2``:
    no ``w_gate`` leaf), plus the ``shared`` always-on expert of the same
    activation, ``shared_width`` wide where that is given and ``shared`` routed
    widths otherwise. What the absent experts would add is
    left out; on one chip there is no exchange. Routing is sigmoid scores,
    the top ``top_k`` of ``s + b`` (``router_bias``, a buffer: no gradient),
    weights ``route_scale * s_e / sum_chosen s``. No capacity, no dropped
    token, no auxiliary loss.

    The assignments to held experts are sorted by expert and walked in steps
    of ``slab_rows`` rows, an eighth of the held experts' mean load by default
    (:func:`step_and_window`): a step gathers its rows' tokens, multiplies
    them by groups (``jax.lax.ragged_dot``) against a window of the few
    consecutive experts its rows fall in, sliced from bf16 copies of the
    weights made once before the loop, and adds the weighted outputs to their
    tokens in the loop's float32 carry; it ends after ``slab_rows`` rows or at
    its window's last expert. The loop runs until the last held assignment and
    at least once, so the work follows the load, a step costs its rows and its
    window and nothing the size of the layer, and however many tokens choose a
    held expert (at most ``top_k`` x tokens assignments) every one is
    computed. The backward walks the same steps: float32 carries for the
    gradients of the tokens, of the routing weights and of the expert weights,
    the last added to a window at a time where the step's rows fall.

    Shape-preserving on ``[B, S, H]``; returns ``(out, counters)`` with
    ``held_assignments`` (assignments to held experts in this call),
    ``held_load_max`` (those of the busiest held expert) and
    ``held_rows_walked`` (steps walked times a step's rows: what the walk
    cost, of which the assignments are the real part), float32 scalars.
    """

    num_experts: int
    held: Tuple[int, int]
    top_k: int
    hidden_size: int
    intermediate_size: int
    route_scale: float = 1.0
    shared: int = 0               # shared experts, as one FFN of that many widths
    slab_rows: int = 0            # a step's rows; 0 = an eighth of the held experts' mean load
    dtype: Any = jnp.bfloat16
    activation: str = "swiglu"    # "swiglu" | "relu2"
    shared_width: int = 0         # the shared expert's width; 0 = ``shared`` routed widths

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown expert activation {self.activation!r}; "
                             f"know {sorted(ACTIVATIONS)}")
        b, s, h = x.shape
        tokens, k = b * s, self.top_k
        first, count = self.held
        wide = self.intermediate_size
        init = nn.initializers.normal(stddev=0.02)
        bias = self.param("router_bias", nn.initializers.zeros_init(),
                          (self.num_experts,), jnp.float32)
        w_gate = None if self.activation == "relu2" else self.param(
            "w_gate", init, (count, h, wide), jnp.float32)
        w_up = self.param("w_up", init, (count, h, wide), jnp.float32)
        w_down = self.param("w_down", init, (count, wide, h), jnp.float32)
        xt = x.reshape(tokens, h)

        # ---- routing, float32 -------------------------------------------
        scores = jax.nn.sigmoid(nn.Dense(
            self.num_experts, use_bias=False, dtype=jnp.float32, kernel_init=init,
            precision=jax.lax.Precision.HIGHEST, name="router")(
                xt.astype(jnp.float32)))
        _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)   # [T, k]
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = self.route_scale * picked / jnp.sum(picked, axis=-1, keepdims=True)

        # ---- held assignments, sorted by expert -------------------------
        local = chosen.reshape(-1) - first                  # [T * k]
        # the held expert's index, or ``count`` for an expert held elsewhere
        group = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(group, stable=True)
        # counted by a one-hot sum: a bincount is a scatter, 1.2 ms a call on the
        # v5e against 0.1 (PERF.md §6, PR 27)
        loads = jnp.sum(jax.nn.one_hot(group, count, dtype=jnp.int32), axis=0)
        ends = jnp.cumsum(loads)
        total = ends[-1]
        rows, window = step_and_window(tokens, k, count, self.num_experts, self.slab_rows)
        # a step that begins at the last assignment still reads ``rows`` of them
        order = jnp.pad(order, (0, rows))
        out, steps = _held_experts(xt, weights.reshape(-1), w_gate, w_up, w_down, order,
                                   ends, loads, rows, window, k, jnp.dtype(self.dtype))
        if self.shared:
            out = out + ACTIVATIONS[self.activation](
                h, self.shared_width or wide * self.shared, self.dtype, name="shared")(xt)
        counters = {"held_assignments": total.astype(jnp.float32),
                    "held_load_max": jnp.max(loads).astype(jnp.float32),
                    "held_rows_walked": (steps * rows).astype(jnp.float32)}
        return out.reshape(b, s, h).astype(x.dtype), counters


class Relu2FFN(nn.Module):
    """``relu(x W_up)^2 W_down``, no gate and no biases."""

    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            kernel_init=nn.initializers.normal(stddev=0.02), name=name)

        return dense(self.hidden_size, "down")(
            jnp.square(jax.nn.relu(dense(self.intermediate_size, "up")(x))))


# an expert's activation -> the shared expert's module
ACTIVATIONS = {"swiglu": SwiGLU, "relu2": Relu2FFN}
