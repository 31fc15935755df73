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


def _slab(start, xt, flat_w, w_gate, w_up, w_down, order, ends, loads, rows, k,
          dtype):
    """The held assignments ``[start, start + rows)`` of the sorted list
    ``order``, as their weighted outputs scattered to their tokens
    (``[T, H]`` float32). ``ends`` and ``loads`` are the held experts'
    cumulative and own assignment counts. ``w_gate`` is ``None`` for experts
    without a gate (``relu(x W_up)^2 W_down``)."""
    mine = jax.lax.dynamic_slice(order, (start,), (rows,))
    valid = start + jnp.arange(rows) < ends[-1]
    token = mine // k
    sizes = jnp.clip(ends - start, 0, rows) - jnp.clip(ends - loads - start, 0, rows)
    # rows past the last assignment join the last group: they are computed
    # on real tokens and discarded, never left undefined
    sizes = sizes.at[-1].add(rows - jnp.sum(sizes))
    cast = lambda w: w.astype(dtype)
    xs = cast(xt[token])
    if w_gate is None:                  # relu2: two matrices an expert
        mid = jnp.square(jax.nn.relu(jax.lax.ragged_dot(xs, cast(w_up), sizes)))
    else:
        mid = jax.nn.silu(jax.lax.ragged_dot(xs, cast(w_gate), sizes)) \
            * jax.lax.ragged_dot(xs, cast(w_up), sizes)
    ys = jax.lax.ragged_dot(mid, cast(w_down), sizes,
                            preferred_element_type=jnp.float32)
    ys = jnp.where(valid[:, None], ys * flat_w[mine][:, None], 0.0)
    return jnp.zeros(xt.shape, jnp.float32).at[token].add(ys)


def _slabs_walked(total, rows):
    """``ceil(total / rows)`` and never nought: the first slab is walked
    whatever the load (at a load of nought all its rows are padding), so a
    layer costs the same at one held assignment or none."""
    return jnp.maximum(-(-total // rows), 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _held_experts(xt, flat_w, w_gate, w_up, w_down, order, ends, loads, rows, k,
                  dtype):
    """Sum of :func:`_slab` over the slabs that hold an assignment: a loop
    whose trip count follows the load (:func:`_slabs_walked`), in the forward
    and in the backward alike, so no slab's operands are kept."""
    return _held_experts_fwd(xt, flat_w, w_gate, w_up, w_down, order, ends, loads,
                             rows, k, dtype)[0]


def _held_experts_fwd(xt, flat_w, w_gate, w_up, w_down, order, ends, loads, rows,
                      k, dtype):
    diff, ints = (xt, flat_w, w_gate, w_up, w_down), (order, ends, loads)
    out = jax.lax.fori_loop(
        0, _slabs_walked(ends[-1], rows),
        lambda i, acc: acc + _slab(i * rows, *diff, *ints, rows, k, dtype),
        jnp.zeros(xt.shape, jnp.float32))
    return out, (diff, ints)


def _held_experts_bwd(rows, k, dtype, residuals, g):
    diff, ints = residuals

    def one(i, acc):
        _, pull = jax.vjp(
            lambda *d: _slab(i * rows, *d, *ints, rows, k, dtype), *diff)
        return jax.tree.map(jnp.add, acc, pull(g))

    grads = jax.lax.fori_loop(0, _slabs_walked(ints[1][-1], rows), one,
                              jax.tree.map(jnp.zeros_like, diff))
    return (*grads, None, None, None)


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

    The assignments to held experts are sorted by expert and multiplied by
    groups (``jax.lax.ragged_dot``) in slabs of ``slab_rows`` rows by a loop
    that runs as many slabs as hold an assignment and at least one, so the
    work follows the load in whole slabs, and however many tokens choose a
    held expert (at most ``top_k`` x tokens assignments) every one is computed.

    Shape-preserving on ``[B, S, H]``; returns ``(out, counters)`` with
    ``held_assignments`` (assignments to held experts in this call) and
    ``held_load_max`` (those of the busiest held expert), float32 scalars.
    """

    num_experts: int
    held: Tuple[int, int]
    top_k: int
    hidden_size: int
    intermediate_size: int
    route_scale: float = 1.0
    shared: int = 0               # shared experts, as one FFN of that many widths
    slab_rows: int = 0            # 0 = twice the mean load of the held experts
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
        # a slab of twice the mean load, so that one slab is the usual case
        mean_load = tokens * k * count // self.num_experts
        rows = self.slab_rows or -(-2 * max(mean_load, 128) // 256) * 256
        rows = min(rows, tokens * k)
        slabs = -(-tokens * k // rows)
        order = jnp.pad(order, (0, slabs * rows - tokens * k))
        out = _held_experts(xt, weights.reshape(-1), w_gate, w_up, w_down,
                            order, ends, loads, rows, k, jnp.dtype(self.dtype))
        if self.shared:
            out = out + ACTIVATIONS[self.activation](
                h, self.shared_width or wide * self.shared, self.dtype, name="shared")(xt)
        counters = {"held_assignments": total.astype(jnp.float32),
                    "held_load_max": jnp.max(loads).astype(jnp.float32)}
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
