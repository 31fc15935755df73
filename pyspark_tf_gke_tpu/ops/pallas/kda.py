"""KDA's chunked walk as Pallas TPU kernels (``ops/linear_attention.py`` has
the algebra and the ``custom_vjp`` these sit under).

Operands stay ``[B, S, H*D]`` as the projections write them, not normalised:
a head is a 128-lane column slab, so a grid step's block ``(1, rows, D)`` at
``(b, n, h)`` needs no transpose, and what is per head and per row (the L2
norms, ``beta``'s products, the chunk-wise sum of the log-decay, the output's
RMS) is computed on that block in VMEM by :func:`block_step`, in float32.
``beta [B, S, H]`` comes as the block ``(1, rows, H)``: ``H`` is the array's
whole last axis, which makes a 32-lane block legal, and :func:`block_step`
picks column ``program_id(1)`` of it. The grid is ``(B, H, S / rows)`` with
the last axis sequential: the ``[Dv, Dk]`` state (float32, the transpose of
``S``) lives in VMEM scratch from one block of chunks to the next.

* ``kda_fwd``: zeroes the state at a row's first block, writes the state at
  each block's start to HBM (what the backward restarts from) and the block's
  outputs, through :func:`block_step`.
* ``kda_bwd``: the same grid walked from the last block to the first; per
  block it reruns :func:`block_step` from the kept state and pulls the output's
  and the later blocks' cotangents back through it (``jax.vjp`` inside the
  kernel body: the backward is the transpose of the very algebra the forward
  ran), carrying the state's cotangent in scratch. Heads are a ``parallel``
  grid axis, so no two of them may write one ``[rows, H]`` block of
  ``dbeta``: each head writes lane-dense rows ``[B, H, S / rows, 1, rows]`` (as
  flash attention lays out ``lse``; a block's last two axes are the array's
  whole ones at any length), transposed outside: 1 MB.

Sub-blocks of ``SUB`` rows are read from and written to the refs, so the
differentiated function slices nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.linear_attention import (_NT, CHUNK, SUB, block_rows,
                                                     block_step)
from pyspark_tf_gke_tpu.ops.pallas.scope import caller_scope, kernel_scope

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _load(refs, rows):
    return tuple(tuple(ref[0, i:i + SUB, :] for i in range(0, rows, SUB))
                 for ref in refs)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, kept_ref, state, *,
                rows, mxu, eps):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    kept_ref[0, 0, 0] = state[...]
    outs, new = block_step(_load((q_ref, k_ref, v_ref, g_ref, beta_ref), rows),
                           state[...], pl.program_id(1), mxu=mxu, eps=eps)
    for c, o in enumerate(outs):
        o_ref[0, c * CHUNK:(c + 1) * CHUNK, :] = o.astype(o_ref.dtype)
    state[...] = new


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, kept_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *, rows, mxu, eps):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    subs = _load((q_ref, k_ref, v_ref, g_ref, beta_ref), rows)
    do = tuple(do_ref[0, i:i + CHUNK, :].astype(jnp.float32)
               for i in range(0, rows, CHUNK))
    step = functools.partial(block_step, head=pl.program_id(1), mxu=mxu, eps=eps)
    _, pull = jax.vjp(step, subs, kept_ref[0, 0, 0])
    (*d_subs, d_beta), d_prev = pull((do, dstate[...]))
    for ref, grads in zip((dq_ref, dk_ref, dv_ref, dg_ref), d_subs):
        for i, g in enumerate(grads):
            ref[0, i * SUB:(i + 1) * SUB, :] = g.astype(ref.dtype)
    # [rows, H], zero off this head's column: its row sums, onto lanes
    d_beta = jnp.concatenate(d_beta, axis=0)
    dbeta_ref[0, 0, 0] = jax.lax.dot_general(
        jnp.ones((1, d_beta.shape[1]), jnp.float32), d_beta, _NT,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    dstate[...] = d_prev


_STATICS = ("heads", "eps", "mxu", "interpret", "caller")


def forward(q, k, v, g, beta, *, heads, eps, mxu, interpret):
    """``(o [B, S, H*Dv], kept [B, H, S/rows, Dv, Dk])`` of :func:`kda`'s
    operands."""
    return _forward(q, k, v, g, beta, heads=heads, eps=eps, mxu=mxu,
                    interpret=interpret, caller=caller_scope())


def backward(q, k, v, g, beta, kept, do, *, heads, eps, mxu, interpret):
    """The five operands' gradients, ``dbeta`` as ``[B, S, H]``."""
    *grads, dbeta = _backward(q, k, v, g, beta, kept, do, heads=heads, eps=eps, mxu=mxu,
                              interpret=interpret, caller=caller_scope())
    b, s, _ = q.shape
    return (*grads, dbeta.reshape(b, heads, s).transpose(0, 2, 1))


_VMEM = {"memory_space": pltpu.VMEM}


def _slab(rows, width, order):
    """Head ``j``'s ``width`` lanes of rows ``[order(n) * rows, ...)`` of row ``i``."""
    return pl.BlockSpec((1, rows, width), lambda i, j, n: (i, order(n), j), **_VMEM)


def _all_heads(rows, heads, order):
    """The same rows of ``beta``, every head's column."""
    return pl.BlockSpec((1, rows, heads), lambda i, j, n: (i, order(n), 0), **_VMEM)


def _kept(dv, d, order):
    return pl.BlockSpec((1, 1, 1, dv, d), lambda i, j, n: (i, j, order(n), 0, 0), **_VMEM)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _forward(q, k, v, g, beta, *, heads, eps, mxu, interpret, caller):
    b, s, hd = q.shape
    d, dv, rows = hd // heads, v.shape[-1] // heads, block_rows(s)
    nb = s // rows
    forth = lambda n: n
    slab, vslab = _slab(rows, d, forth), _slab(rows, dv, forth)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows, mxu=mxu, eps=eps),
        grid=(b, heads, nb),
        in_specs=[slab, slab, vslab, slab, _all_heads(rows, heads, forth)],
        out_specs=[vslab, _kept(dv, d, forth)],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, nb, dv, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("kda_fwd", caller):
        return call(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _backward(q, k, v, g, beta, kept, do, *, heads, eps, mxu, interpret, caller):
    b, s, hd = q.shape
    d, dv, rows = hd // heads, v.shape[-1] // heads, block_rows(s)
    nb = s // rows
    back = lambda n: nb - 1 - n                      # the last block first
    slab, vslab = _slab(rows, d, back), _slab(rows, dv, back)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, mxu=mxu, eps=eps),
        grid=(b, heads, nb),
        in_specs=[slab, slab, vslab, slab, _all_heads(rows, heads, back),
                  _kept(dv, d, back), vslab],
        out_specs=[slab, slab, vslab, slab,
                   pl.BlockSpec((1, 1, 1, 1, rows), lambda i, j, n: (i, j, back(n), 0, 0),
                                **_VMEM)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g)] + [
            jax.ShapeDtypeStruct((b, heads, nb, 1, rows), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("kda_bwd", caller):
        return tuple(call(q, k, v, g, beta, kept, do))
