"""The chunked state-space scan as Pallas TPU kernels (``ops/state_space.py``
has the algebra and the ``custom_vjp`` these sit under).

Operands stay ``[B, S, H*P]`` / ``[B, S, G*N]`` as the layer's projection
writes them. The grid is ``(B, G, S / rows)`` with the last axis sequential: a
grid step holds one group's block of ``rows`` = ``BLOCK_CHUNKS`` chunks, ``x``
as ``(1, rows, H/G * P)`` (whole 128-lane slabs of ``128 / P`` heads each),
``b`` and ``c`` as ``(1, rows, N)``, and ``dt [B, S, H]`` as ``(1, rows, H)``
(``H`` is the array's whole last axis, which makes a 64-lane block legal;
:func:`block_step` picks the group's columns). The slabs' ``[128, N]`` states
(float32) live in VMEM scratch from one block to the next.

* ``ssd_fwd``: zeroes the states at a row's first block, writes the states at
  each block's start to HBM (what the backward restarts from) and the block's
  outputs, through :func:`block_step`.
* ``ssd_bwd``: the same grid walked from the last block to the first; per
  block it reruns :func:`block_step` from the kept states and pulls the
  output's and the later blocks' cotangents back through it (``jax.vjp``
  inside the kernel body), carrying the states' cotangent in scratch. Groups
  are a ``parallel`` grid axis, so no two of them may write one ``[rows, H]``
  block of ``ddt``: each group writes its own ``[B, G, S, H]`` (zero off its
  columns), summed over ``G`` outside, 33 MB; ``da`` and ``dd`` sum up over
  the walk in ``[B, G, 1, H]`` blocks whose index does not follow the
  sequential axis, summed over ``B`` and ``G`` outside.

Chunks and slabs are read from and written to the refs piece by piece, so the
differentiated function slices nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.pallas.scope import caller_scope, kernel_scope
from pyspark_tf_gke_tpu.ops.state_space import (LANES, block_rows, block_step,
                                                slab_heads)

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_VMEM = {"memory_space": pltpu.VMEM}


def _slabs(ref, rows, chunk, lanes, cast):
    """A ``(1, rows, slabs * lanes)`` ref as a tuple a chunk of its slabs."""
    return tuple(tuple(cast(ref[0, i:i + chunk, j:j + lanes])
                       for j in range(0, ref.shape[2], lanes)) for i in range(0, rows, chunk))


def _load(x_ref, b_ref, c_ref, dt_ref, rows, chunk, lanes, cast=lambda m: m):
    """The block's operands as :func:`block_step` takes them."""
    chunks = range(0, rows, chunk)
    b, c = (tuple(cast(ref[0, i:i + chunk, :]) for i in chunks) for ref in (b_ref, c_ref))
    return (_slabs(x_ref, rows, chunk, lanes, cast), b, c,
            tuple(dt_ref[0, i:i + chunk, :] for i in chunks))


def _store(ref, pieces, chunk, lanes):
    """Pieces laid out as ``x`` is (a tuple a chunk of slabs) into a ``(1, rows,
    slabs * lanes)`` ref."""
    for i, row in enumerate(pieces):
        for j, piece in enumerate(row):
            ref[0, i * chunk:(i + 1) * chunk, j * lanes:(j + 1) * lanes] = piece.astype(ref.dtype)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, kept_ref, state, *,
                rows, chunk, head_dim, mxu):
    lanes = state.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    kept_ref[0, 0, 0] = state[...]
    x, b, c, dt = _load(x_ref, b_ref, c_ref, dt_ref, rows, chunk, lanes)
    ys, new = block_step(x, b, c, dt, a_ref[...], d_ref[...],
                         tuple(state[k] for k in range(state.shape[0])), pl.program_id(1),
                         head_dim=head_dim, mxu=mxu)
    _store(y_ref, ys, chunk, lanes)
    for k, h in enumerate(new):
        state[k] = h


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, kept_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref, dstate, *,
                rows, chunk, head_dim, mxu):
    f32 = jnp.float32
    slabs, lanes = dstate.shape[:2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        for ref in (dstate, da_ref, dd_ref):
            ref[...] = jnp.zeros_like(ref)

    # float32 cotangents: b's and c's sum over the group's heads before rounding
    widen = lambda m: m.astype(f32)
    x, b, c, dt = _load(x_ref, b_ref, c_ref, dt_ref, rows, chunk, lanes, widen)
    dy = _slabs(dy_ref, rows, chunk, lanes, widen)
    step = functools.partial(block_step, group=pl.program_id(1), head_dim=head_dim, mxu=mxu)
    _, pull = jax.vjp(step, x, b, c, dt, a_ref[...], d_ref[...],
                      tuple(kept_ref[0, 0, 0, k] for k in range(slabs)))
    dx, db, dc, ddt, da, dd, dprev = pull((dy, tuple(dstate[k] for k in range(slabs))))
    _store(dx_ref, dx, chunk, lanes)
    for ref, grads in ((db_ref, db), (dc_ref, dc)):
        for i, g in enumerate(grads):
            ref[0, i * chunk:(i + 1) * chunk, :] = g.astype(ref.dtype)
    for i, g in enumerate(ddt):
        ddt_ref[0, 0, i * chunk:(i + 1) * chunk, :] = g
    da_ref[0, 0] += da
    dd_ref[0, 0] += dd
    for k, h in enumerate(dprev):
        dstate[k] = h


_STATICS = ("heads", "groups", "chunk", "mxu", "interpret", "caller")


def forward(x, dt, a, b, c, d, *, heads, groups, chunk, mxu, interpret):
    """``(y [B, S, H*P], kept [B, G, S/rows, slabs, lanes, N])`` of
    :func:`ssd`'s operands."""
    return _forward(x, dt, a, b, c, d, heads=heads, groups=groups, chunk=chunk, mxu=mxu,
                    interpret=interpret, caller=caller_scope())


def backward(x, dt, a, b, c, d, kept, dy, *, heads, groups, chunk, mxu, interpret):
    """The six operands' gradients as :func:`ssd` takes them."""
    dx, ddt, da, db, dc, dd = _backward(x, dt, a, b, c, d, kept, dy, heads=heads,
                                        groups=groups, chunk=chunk, mxu=mxu,
                                        interpret=interpret, caller=caller_scope())
    return (dx, jnp.sum(ddt, axis=1), jnp.sum(da, axis=(0, 1, 2)), db, dc,
            jnp.sum(dd, axis=(0, 1, 2)))


def _geometry(x, b, heads, groups, chunk):
    """``(rows a block, lanes a slab, slabs a group, P, N, blocks)``; refuses
    widths that are no whole lane tiles."""
    head_dim, n = x.shape[-1] // heads, b.shape[-1] // groups
    lanes = head_dim * slab_heads(head_dim)
    if lanes % LANES or n % LANES:
        raise ValueError(
            f"ssd kernels: a slab of {lanes} lanes (heads of {head_dim}) and a state of {n} "
            f"have to be multiples of {LANES}")
    rows = block_rows(x.shape[1], chunk)
    return rows, lanes, heads // groups * head_dim // lanes, head_dim, n, x.shape[1] // rows


def _specs(rows, wide, n, heads, order):
    """Block specs of ``x`` (or ``y``, ``dy``, ``dx``), ``dt``, ``a`` / ``d``
    and ``b`` / ``c`` at grid step ``(i, j, n)``: row ``i``, group ``j``, block
    ``order(n)``."""
    slab = pl.BlockSpec((1, rows, wide), lambda i, j, k: (i, order(k), j), **_VMEM)
    steps = pl.BlockSpec((1, rows, heads), lambda i, j, k: (i, order(k), 0), **_VMEM)
    per_head = pl.BlockSpec((1, heads), lambda i, j, k: (0, 0), **_VMEM)
    keys = pl.BlockSpec((1, rows, n), lambda i, j, k: (i, order(k), j), **_VMEM)
    return slab, steps, per_head, keys


def _kept(slabs, lanes, n, order):
    return pl.BlockSpec((1, 1, 1, slabs, lanes, n),
                        lambda i, j, k: (i, j, order(k), 0, 0, 0), **_VMEM)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _forward(x, dt, a, b, c, d, *, heads, groups, chunk, mxu, interpret, caller):
    bsz = x.shape[0]
    rows, lanes, slabs, head_dim, n, nb = _geometry(x, b, heads, groups, chunk)
    forth = lambda k: k
    slab, steps, per_head, keys = _specs(rows, slabs * lanes, n, heads, forth)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows, chunk=chunk, head_dim=head_dim, mxu=mxu),
        grid=(bsz, groups, nb),
        in_specs=[slab, steps, per_head, keys, keys, per_head],
        out_specs=[slab, _kept(slabs, lanes, n, forth)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, groups, nb, slabs, lanes, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((slabs, lanes, n), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("ssd_fwd", caller):
        return call(x, dt, a.reshape(1, heads), b, c, d.reshape(1, heads))


@functools.partial(jax.jit, static_argnames=_STATICS)
def _backward(x, dt, a, b, c, d, kept, dy, *, heads, groups, chunk, mxu, interpret, caller):
    """``dx``, ``ddt [B, G, S, H]``, ``da [B, G, 1, H]``, ``db``, ``dc``,
    ``dd [B, G, 1, H]``."""
    bsz, s, _ = x.shape
    rows, lanes, slabs, head_dim, n, nb = _geometry(x, b, heads, groups, chunk)
    back = lambda k: nb - 1 - k                      # the last block first
    slab, steps, per_head, keys = _specs(rows, slabs * lanes, n, heads, back)
    # a group's sums over the walk stay in VMEM over the sequential axis
    summed = pl.BlockSpec((1, 1, 1, heads), lambda i, j, k: (i, j, 0, 0), **_VMEM)
    sums = jax.ShapeDtypeStruct((bsz, groups, 1, heads), jnp.float32)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, chunk=chunk, head_dim=head_dim, mxu=mxu),
        grid=(bsz, groups, nb),
        in_specs=[slab, steps, per_head, keys, keys, per_head,
                  _kept(slabs, lanes, n, back), slab],
        out_specs=[slab,
                   pl.BlockSpec((1, 1, rows, heads), lambda i, j, k: (i, j, back(k), 0),
                                **_VMEM),
                   summed, keys, keys, summed],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, groups, s, heads), jnp.float32), sums,
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype), sums],
        scratch_shapes=[pltpu.VMEM((slabs, lanes, n), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )
    with kernel_scope("ssd_bwd", caller):
        return call(x, dt, a.reshape(1, heads), b, c, d.reshape(1, heads), kept, dy)
