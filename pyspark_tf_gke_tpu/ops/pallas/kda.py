"""KDA's chunked walk as Pallas TPU kernels (``ops/linear_attention.py`` has
the algebra and the ``custom_vjp`` these sit under).

Operands stay ``[B, S, H*D]`` as the projections write them: a head is a
128-lane column slab, so a grid step's block ``(1, rows, D)`` at ``(b, n, h)``
needs no transpose. The grid is ``(B, H, S / rows)`` with the last axis
sequential: the ``[Dv, Dk]`` state (float32, the transpose of ``S``) lives in
VMEM scratch from one block of chunks to the next.

* ``kda_fwd``: zeroes the state at a row's first block, writes the state at
  each block's start to HBM (what the backward restarts from) and the block's
  outputs, through :func:`block_step`.
* ``kda_bwd``: the same grid walked from the last block to the first; per
  block it reruns :func:`block_step` from the kept state and pulls the output's
  and the later blocks' cotangents back through it (``jax.vjp`` inside the
  kernel body: the backward is the transpose of the very algebra the forward
  ran), carrying the state's cotangent in scratch.

Sub-blocks of ``SUB`` rows are read from and written to the refs, so the
differentiated function slices nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.linear_attention import (CHUNK, SUB, block_rows,
                                                     block_step)
from pyspark_tf_gke_tpu.ops.pallas.scope import caller_scope, kernel_scope

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _load(refs, rows):
    return tuple(tuple(ref[0, i:i + SUB, :] for i in range(0, rows, SUB))
                 for ref in refs)


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, gc_ref, o_ref, kept_ref, state, *,
                rows, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    kept_ref[0, 0, 0] = state[...]
    outs, new = block_step(_load((q_ref, k_ref, kb_ref, vb_ref, gc_ref), rows),
                           state[...], mxu)
    for c, o in enumerate(outs):
        o_ref[0, c * CHUNK:(c + 1) * CHUNK, :] = o.astype(o_ref.dtype)
    state[...] = new


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, gc_ref, kept_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dgc_ref, dstate, *, rows, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    subs = _load((q_ref, k_ref, kb_ref, vb_ref, gc_ref), rows)
    do = tuple(do_ref[0, i:i + CHUNK, :].astype(jnp.float32)
               for i in range(0, rows, CHUNK))
    _, pull = jax.vjp(functools.partial(block_step, mxu=mxu), subs, kept_ref[0, 0, 0])
    d_subs, d_prev = pull((do, dstate[...]))
    for ref, grads in zip((dq_ref, dk_ref, dkb_ref, dvb_ref, dgc_ref), d_subs):
        for i, g in enumerate(grads):
            ref[0, i * SUB:(i + 1) * SUB, :] = g.astype(ref.dtype)
    dstate[...] = d_prev


_STATICS = ("heads", "mxu", "interpret", "caller")


def forward(q, k, kb, vb, gc, *, heads, mxu, interpret):
    """``(o [B, S, H*Dv], kept [B, H, S/rows, Dv, Dk])`` of ``[B, S, H*D]``
    operands."""
    return _forward(q, k, kb, vb, gc, heads=heads, mxu=mxu, interpret=interpret,
                    caller=caller_scope())


def backward(q, k, kb, vb, gc, kept, do, *, heads, mxu, interpret):
    return _backward(q, k, kb, vb, gc, kept, do, heads=heads, mxu=mxu,
                     interpret=interpret, caller=caller_scope())


_VMEM = {"memory_space": pltpu.VMEM}


def _slab(rows, width, order):
    """Head ``j``'s ``width`` lanes of rows ``[order(n) * rows, ...)`` of row ``i``."""
    return pl.BlockSpec((1, rows, width), lambda i, j, n: (i, order(n), j), **_VMEM)


def _kept(dv, d, order):
    return pl.BlockSpec((1, 1, 1, dv, d), lambda i, j, n: (i, j, order(n), 0, 0), **_VMEM)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _forward(q, k, kb, vb, gc, *, heads, mxu, interpret, caller):
    b, s, hd = q.shape
    d, dv, rows = hd // heads, vb.shape[-1] // heads, block_rows(s)
    nb = s // rows
    forth = lambda n: n
    slab, vslab = _slab(rows, d, forth), _slab(rows, dv, forth)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows, mxu=mxu),
        grid=(b, heads, nb),
        in_specs=[slab, slab, slab, vslab, slab],
        out_specs=[vslab, _kept(dv, d, forth)],
        out_shape=[jax.ShapeDtypeStruct((b, s, heads * dv), vb.dtype),
                   jax.ShapeDtypeStruct((b, heads, nb, dv, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("kda_fwd", caller):
        return call(q, k, kb, vb, gc)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _backward(q, k, kb, vb, gc, kept, do, *, heads, mxu, interpret, caller):
    b, s, hd = q.shape
    d, dv, rows = hd // heads, vb.shape[-1] // heads, block_rows(s)
    nb = s // rows
    back = lambda n: nb - 1 - n                      # the last block first
    slab, vslab = _slab(rows, d, back), _slab(rows, dv, back)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, mxu=mxu),
        grid=(b, heads, nb),
        in_specs=[slab, slab, slab, vslab, slab, _kept(dv, d, back), vslab],
        out_specs=[slab, slab, slab, vslab, slab],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, kb, vb, gc)],
        scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("kda_bwd", caller):
        return tuple(call(q, k, kb, vb, gc, kept, do))
