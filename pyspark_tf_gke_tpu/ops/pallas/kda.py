"""KDA's chunked walk as Pallas TPU kernels (``ops/linear_attention.py`` has
the algebra and the ``custom_vjp`` these sit under).

Operands stay ``[B, S, H*D]`` as the projections write them, not normalised
and, with ``conv``, not yet convolved: a head is a 128-lane column slab, so a
grid step's block ``(1, rows, D)`` at ``(b, n, h)`` needs no transpose, and
what is per head and per row (the short convolution over time with its SiLU,
the L2 norms, ``beta``'s products, the chunk-wise sum of the log-decay, the
output's RMS) is computed on that block in VMEM by :func:`block_step`, in
float32. ``beta [B, S, H]`` comes as the block ``(1, rows, H)``: ``H`` is the
array's whole last axis, which makes a 32-lane block legal, and
:func:`block_step` picks column ``program_id(1)`` of it. The grid is ``(B, H,
S / rows)`` with the last axis sequential: the ``[Dv, Dk]`` state (float32,
the transpose of ``S``) lives in VMEM scratch from one block of chunks to the
next.

With ``conv = (wq, wk, wv)``, each ``[taps, H*D]`` float32, ``q``, ``k`` and
``v`` are passed a second time under a ``(1, SUB, D)`` block, the ``SUB`` rows
before the grid step's (the convolution reaches ``taps - 1`` rows back; read
as zeros at a row's first block), and each operand's taps as the head's
``(taps, D)`` block. Without it the launches are those of operands already
mixed.

* ``kda_fwd``: zeroes the state at a row's first block, writes the state at
  each block's start to HBM (what the backward restarts from) and the block's
  outputs, through :func:`block_step`.
* ``kda_bwd``: the same grid walked from the last block to the first; per
  block it reruns :func:`block_step` from the kept state and pulls the output's
  and the later blocks' cotangents back through it (``jax.vjp`` inside the
  kernel body: the backward is the transpose of the very algebra the forward
  ran, but for the chunk's triangular inverse, whose ``custom_vjp`` pulls
  ``-T^T dT T^T`` back in two products where the transpose of its series
  would take twenty), carrying the state's cotangent in scratch. Heads are a
  ``parallel`` grid axis, so no two of them may write one ``[rows, H]`` block
  of ``dbeta``: each head writes lane-dense rows ``[B, H, S / rows, 1, rows]``
  (as flash attention lays out ``lse``; a block's last two axes are the
  array's whole ones at any length), transposed outside: 1 MB. With ``conv`` the
  cotangent of the rows before a block belongs to the block walked next: it
  waits in scratch (float32, ``[SUB, D]`` an operand) and is added to that
  block's last rows before they are written; and the taps' gradients sum up
  over the walk in an output block ``[B, H, taps, D]`` an operand whose index
  does not follow the sequential axis, summed over ``B`` outside.

Sub-blocks of ``SUB`` rows are read from and written to the refs, so the
differentiated function slices nothing.
"""

from __future__ import annotations

import functools
import itertools

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


def _take(refs, *counts):
    """``refs`` cut into one tuple a count, in order."""
    refs = iter(refs)
    return [tuple(itertools.islice(refs, n)) for n in counts]


def _load_conv(halo_refs, tap_refs, first):
    """:func:`block_step`'s ``conv`` (``None`` where there are no refs): each
    operand's taps a row ``[1, D]`` a tap, and the ``SUB`` rows before the
    block in float32, zeros at grid step ``first`` of the sequential axis,
    which holds a row's first block."""
    if not tap_refs:
        return None
    inside = pl.program_id(2) != first
    return (tuple(tuple(ref[j:j + 1, :] for j in range(ref.shape[0])) for ref in tap_refs),
            tuple(jnp.where(inside, ref[0].astype(jnp.float32), 0.0) for ref in halo_refs))


def _fwd_kernel(*refs, rows, mxu, eps, mixes):
    five, halos, taps, (o_ref, kept_ref), (state,) = _take(refs, 5, mixes, mixes, 2, 1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    kept_ref[0, 0, 0] = state[...]
    outs, new = block_step(_load(five, rows), state[...], pl.program_id(1), mxu=mxu, eps=eps,
                           conv=_load_conv(halos, taps, 0))
    for c, o in enumerate(outs):
        o_ref[0, c * CHUNK:(c + 1) * CHUNK, :] = o.astype(o_ref.dtype)
    state[...] = new


def _bwd_kernel(*refs, rows, mxu, eps, mixes):
    (five, (kept_ref, do_ref), halos, taps, (dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref),
     dtap_refs, (dstate,), late) = _take(refs, 5, 2, mixes, mixes, 5, mixes, 1, mixes)
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        for ref in (dstate, *late, *dtap_refs):
            ref[...] = jnp.zeros_like(ref)

    subs = _load(five, rows)
    if mixes:            # float32 cotangents: a halo's meets its rows' before rounding
        subs = tuple(tuple(x.astype(f32) for x in xs) for xs in subs[:3]) + subs[3:]
    do = tuple(do_ref[0, i:i + CHUNK, :].astype(f32) for i in range(0, rows, CHUNK))
    step = functools.partial(block_step, head=pl.program_id(1), mxu=mxu, eps=eps)
    _, pull = jax.vjp(lambda subs, state, conv: step(subs, state, conv=conv), subs,
                      kept_ref[0, 0, 0], _load_conv(halos, taps, pl.num_programs(2) - 1))
    (*d_subs, d_beta), d_prev, d_conv = pull((do, dstate[...]))
    if mixes:
        # the rows before this block are the last of the block walked next:
        # their cotangent waits in ``late`` for it; the taps' sum up over the walk
        d_taps, d_halos = d_conv
        d_subs = [grads[:-1] + (grads[-1] + ref[...],) for grads, ref in zip(d_subs, late)
                  ] + d_subs[3:]
        for ref, d_halo in zip(late, d_halos):
            ref[...] = d_halo
        for ref, grads in zip(dtap_refs, d_taps):
            for j, g in enumerate(grads):
                ref[0, 0, j:j + 1, :] += g
    for ref, grads in zip((dq_ref, dk_ref, dv_ref, dg_ref), d_subs):
        for i, g in enumerate(grads):
            ref[0, i * SUB:(i + 1) * SUB, :] = g.astype(ref.dtype)
    # [rows, H], zero off this head's column: its row sums, onto lanes
    d_beta = jnp.concatenate(d_beta, axis=0)
    dbeta_ref[0, 0, 0] = jax.lax.dot_general(
        jnp.ones((1, d_beta.shape[1]), f32), d_beta, _NT,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)
    dstate[...] = d_prev


_STATICS = ("heads", "eps", "mxu", "interpret", "caller")


def forward(q, k, v, g, beta, conv, *, heads, eps, mxu, interpret):
    """``(o [B, S, H*Dv], kept [B, H, S/rows, Dv, Dk])`` of :func:`kda`'s
    operands, ``conv`` the three ``[taps, H*D]`` or ``None``."""
    return _forward(q, k, v, g, beta, conv, heads=heads, eps=eps, mxu=mxu,
                    interpret=interpret, caller=caller_scope())


def backward(q, k, v, g, beta, conv, kept, do, *, heads, eps, mxu, interpret):
    """The five operands' gradients, ``dbeta`` as ``[B, S, H]``, and the taps'
    as ``conv`` has them (``None`` without)."""
    *grads, dbeta, dtaps = _backward(q, k, v, g, beta, conv, kept, do, heads=heads, eps=eps,
                                     mxu=mxu, interpret=interpret, caller=caller_scope())
    b, s, _ = q.shape
    return (*grads, dbeta.reshape(b, heads, s).transpose(0, 2, 1),
            conv and tuple(jnp.sum(d, axis=0).transpose(1, 0, 2).reshape(w.shape)
                           for d, w in zip(dtaps, conv)))


_VMEM = {"memory_space": pltpu.VMEM}


def _slab(rows, width, order):
    """Head ``j``'s ``width`` lanes of rows ``[order(n) * rows, ...)`` of row ``i``."""
    return pl.BlockSpec((1, rows, width), lambda i, j, n: (i, order(n), j), **_VMEM)


def _halo(rows, width, order):
    """The ``SUB`` rows before that slab (at a row's first block any: the
    kernels read zeros there)."""
    return pl.BlockSpec(
        (1, SUB, width),
        lambda i, j, n: (i, jnp.maximum(order(n) * (rows // SUB) - 1, 0), j), **_VMEM)


def _taps(taps, width):
    """Head ``j``'s columns of an operand's ``[taps, H*D]``."""
    return pl.BlockSpec((taps, width), lambda i, j, n: (0, j), **_VMEM)


def _all_heads(rows, heads, order):
    """The same rows of ``beta``, every head's column."""
    return pl.BlockSpec((1, rows, heads), lambda i, j, n: (i, order(n), 0), **_VMEM)


def _kept(dv, d, order):
    return pl.BlockSpec((1, 1, 1, dv, d), lambda i, j, n: (i, j, order(n), 0, 0), **_VMEM)


def _conv_operands(q, k, v, conv, heads, rows, order):
    """What a launch takes beside the rest when ``q``, ``k`` and ``v`` are still
    to be convolved: ``(operands, their specs)``, the three once more for their
    halos and the three taps; empty without ``conv``."""
    if conv is None:
        return (), []
    return (q, k, v, *conv), [
        _halo(rows, x.shape[-1] // heads, order) for x in (q, k, v)] + [
        _taps(w.shape[0], w.shape[1] // heads) for w in conv]


@functools.partial(jax.jit, static_argnames=_STATICS)
def _forward(q, k, v, g, beta, conv, *, heads, eps, mxu, interpret, caller):
    b, s, hd = q.shape
    d, dv, rows = hd // heads, v.shape[-1] // heads, block_rows(s)
    nb = s // rows
    forth = lambda n: n
    slab, vslab = _slab(rows, d, forth), _slab(rows, dv, forth)
    more, more_specs = _conv_operands(q, k, v, conv, heads, rows, forth)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows, mxu=mxu, eps=eps, mixes=len(more) // 2),
        grid=(b, heads, nb),
        in_specs=[slab, slab, vslab, slab, _all_heads(rows, heads, forth)] + more_specs,
        out_specs=[vslab, _kept(dv, d, forth)],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, nb, dv, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("kda_fwd", caller):
        return call(q, k, v, g, beta, *more)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _backward(q, k, v, g, beta, conv, kept, do, *, heads, eps, mxu, interpret, caller):
    """``dq, dk, dv, dg``, ``dbeta [B, H, S/rows, 1, rows]`` and the tuple of the
    taps' gradients a row and a head, ``[B, H, taps, D]`` an operand (empty
    without ``conv``)."""
    b, s, hd = q.shape
    d, dv, rows = hd // heads, v.shape[-1] // heads, block_rows(s)
    nb = s // rows
    back = lambda n: nb - 1 - n                      # the last block first
    slab, vslab = _slab(rows, d, back), _slab(rows, dv, back)
    more, more_specs = _conv_operands(q, k, v, conv, heads, rows, back)
    # a head's taps' gradients stay in VMEM over the sequential axis
    dtaps = [jax.ShapeDtypeStruct((b, heads, w.shape[0], w.shape[1] // heads), jnp.float32)
             for w in conv or ()]
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, mxu=mxu, eps=eps, mixes=len(dtaps)),
        grid=(b, heads, nb),
        in_specs=[slab, slab, vslab, slab, _all_heads(rows, heads, back),
                  _kept(dv, d, back), vslab] + more_specs,
        out_specs=[slab, slab, vslab, slab,
                   pl.BlockSpec((1, 1, 1, 1, rows), lambda i, j, n: (i, j, back(n), 0, 0),
                                **_VMEM)] + [
            pl.BlockSpec((1, 1) + x.shape[2:], lambda i, j, n: (i, j, 0, 0), **_VMEM)
            for x in dtaps],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g)] + [
            jax.ShapeDtypeStruct((b, heads, nb, 1, rows), jnp.float32)] + dtaps,
        scratch_shapes=[pltpu.VMEM((dv, d), jnp.float32)] + [
            pltpu.VMEM((SUB, x.shape[-1]), jnp.float32) for x in dtaps],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("kda_bwd", caller):
        out = call(q, k, v, g, beta, kept, do, *more)
        return (*out[:5], tuple(out[5:]))
