"""Pipeline parallelism over the ``pp`` mesh axis.

Absent from the reference (SURVEY §2b lists pipeline parallelism as
"absent"), but first-class here: the framework targets pod-scale models
where the layer stack itself must be split across chips.

Design (TPU-first): a **GPipe-schedule SPMD pipeline** expressed as a
single ``shard_map`` over the ``pp`` axis — NOT a per-stage process group
with point-to-point sends (the reference's gRPC idiom). Each device holds
one *stage* (a contiguous group of layers, stage-stacked as a leading
param dim sharded over ``pp``); activations hop stage→stage with
``lax.ppermute`` over ICI; the microbatch loop is a ``lax.scan`` so the
whole schedule is one compiled XLA program, differentiable end-to-end
(gradient accumulation across microbatches falls out of the scan's
transpose — no hand-written backward schedule).

Schedule: ``T = M + P - 1`` ticks for ``M`` microbatches over ``P``
stages; bubble fraction ``(P-1)/T``, amortized by choosing ``M >= 2P``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES


def _stage_param_spec(leaf) -> P:
    """Stage-stacked param leaf: leading dim is the stage index, sharded
    over ``pp``; everything else device-local."""
    return P("pp", *([None] * (jnp.ndim(leaf) - 1)))


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray, Any], jnp.ndarray],
    stage_params: Any,
    x: jnp.ndarray,
    extras: Any,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
) -> jnp.ndarray:
    """Run ``x`` through ``P`` pipeline stages with a GPipe schedule.

    Args:
      stage_fn: ``(params_for_one_stage, activation_mb, extras_mb) ->
        activation_mb``. Must be shape-preserving on the activation (the
        hidden-state contract of a transformer stack). Runs device-local
        inside ``shard_map`` — no sharding constraints inside.
      stage_params: pytree whose leaves have leading dim ``P`` (stage-
        stacked), sharded over ``pp``.
      x: global activation batch ``[B, ...]`` (batch sharded over the data
        axes). ``B_local`` must divide by ``num_microbatches``.
      extras: pytree of per-example side inputs riding along with the
        activation (e.g. an attention-bias ``[B, S]``); rotated through
        the ring together with it. Float/int leaves only.
      mesh: mesh containing the ``pp`` axis.
      num_microbatches: ``M``; the batch is split into ``M`` equal
        microbatches along dim 0.

    Returns the final-stage activations ``[B, ...]``, replicated over
    ``pp`` (psum of the masked output buffer) and still batch-sharded
    over the data axes.
    """
    n_stages = mesh.shape[axis]
    if n_stages == 1:
        params = jax.tree.map(lambda a: a[0], stage_params)
        return stage_fn(params, x, extras)

    M = num_microbatches
    data_shards = int(np.prod([mesh.shape.get(a, 1) for a in DATA_AXES]))
    b_local, rem = divmod(x.shape[0], data_shards)
    if rem or b_local % M:
        raise ValueError(
            f"global batch {x.shape[0]} over {data_shards} data shards gives "
            f"per-shard batch {x.shape[0] / data_shards}, which must be a "
            f"multiple of num_microbatches={M}"
        )

    def body(params, x_loc, extras_loc):
        params = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)
        idx = lax.axis_index(axis)
        xm = x_loc.reshape(M, -1, *x_loc.shape[1:])
        em = jax.tree.map(lambda a: a.reshape(M, -1, *a.shape[1:]), extras_loc)
        T = M + n_stages - 1
        perm = [(s, s + 1) for s in range(n_stages - 1)]

        act0 = jnp.zeros_like(xm[0])
        ex0 = jax.tree.map(lambda a: jnp.zeros_like(a[0]), em)
        out_buf = jnp.zeros_like(xm)

        def step(carry, t):
            act, ex, out_buf = carry
            # Stage 0 ingests microbatch t (clamped during the drain
            # bubble — those extra computations are never stored).
            t_in = jnp.clip(t, 0, M - 1)
            x_t = lax.dynamic_index_in_dim(xm, t_in, keepdims=False)
            e_t = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, t_in, keepdims=False), em
            )
            is_first = idx == 0
            inp = jnp.where(is_first, x_t, act)
            ex_in = jax.tree.map(
                lambda fresh, held: jnp.where(is_first, fresh, held), e_t, ex
            )

            out = stage_fn(params, inp, ex_in)

            # Last stage: at tick t it finishes microbatch t-(P-1).
            store_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            should_store = jnp.logical_and(idx == n_stages - 1, t >= n_stages - 1)
            cur = lax.dynamic_index_in_dim(out_buf, store_idx, keepdims=False)
            out_buf = lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(should_store, out, cur), store_idx, 0
            )

            act_next = lax.ppermute(out, axis, perm)
            ex_next = jax.tree.map(lambda a: lax.ppermute(a, axis, perm), ex_in)
            return (act_next, ex_next, out_buf), None

        (_, _, out_buf), _ = lax.scan(step, (act0, ex0, out_buf), jnp.arange(T))
        # Only the last stage wrote non-zeros; psum replicates the result
        # across the pp ring so downstream (head/loss) sees it everywhere.
        out = lax.psum(out_buf, axis)
        return out.reshape(-1, *out.shape[2:])

    data_spec = DATA_AXES
    act_spec = P(data_spec, *([None] * (x.ndim - 1)))
    param_specs = jax.tree.map(_stage_param_spec, stage_params)
    extras_specs = jax.tree.map(
        lambda a: P(data_spec, *([None] * (jnp.ndim(a) - 1))), extras
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, act_spec, extras_specs),
        out_specs=act_spec,
        check_vma=False,
    )(stage_params, x, extras)


def split_stages(stacked: Any, n_stages: int) -> Any:
    """Reshape layer-stacked leaves ``[L, ...]`` to stage-stacked
    ``[P, L/P, ...]`` (contiguous layer groups per stage)."""

    def r(a):
        L = a.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])

    return jax.tree.map(r, stacked)


def merge_stages(staged: Any) -> Any:
    """Inverse of :func:`split_stages`: ``[P, L/P, ...] -> [L, ...]``."""
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), staged)
