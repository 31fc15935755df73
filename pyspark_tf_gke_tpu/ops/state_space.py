"""Mamba-2's selective state-space scan, chunked (the state-space-duality form).

Per head, with inputs ``x_t`` of width ``P``, keys ``B_t`` and queries ``C_t``
of width ``N`` shared by the heads of a group, a step ``dt_t > 0`` and a decay
rate ``A < 0`` a head::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T          h in R^{P x N}, h_0 = 0
    y_t = h_t C_t + D x_t

One token at a time that is ``S`` rank-1 updates. The chunked form takes
``chunk`` = 128 tokens at once. With ``cs_t`` the sum of ``dt_r A`` from the
chunk's first token to ``t`` and ``h`` the state at the chunk's start:

    L_ts   = exp(cs_t - cs_s)                            (s <= t, else 0)
    Y      = (L * (C B^T)) (dt x) + exp(cs) (C h^T) + D x
    h_next = exp(cs_end) h + ((dt x) exp(cs_end - cs))^T B

so a chunk is four matmuls a head and only the ``[P, N]`` state passes from
chunk to chunk. The decay is never factored into ``exp(cs_t) exp(-cs_s)``:
``dt A`` reaches -6.4 a token, 128 tokens sum to -819, and ``exp(819)`` is past
float32; every exponent here is a difference that is at most 0. ``cs`` is a
triangle of ones times ``dt A`` on the MXU at ``HIGHEST``, exact to float32.

The tiling (:func:`block_step`). Operands stay ``[B, S, H*P]`` / ``[B, S, G*N]``
as the layer's projection writes them. Heads are 64 wide, half a 128-lane
tile, so a *slab* is the ``128 / P`` heads that fill one: the slab's ``dt x``
is one ``[chunk, 128]`` array, its states one ``[128, N]``, and ``C h^T`` and
the state's update are one full-width matmul each for both heads; only ``L``
is a head's own, so ``Y``'s first term is one ``[chunk, chunk] x [chunk, 128]``
product a head with the other heads' lanes zeroed, which costs the MXU what a
64-wide product would. ``C B^T`` is computed once a chunk for the group's
heads. ``dt [B, S, H]`` comes whole (``H`` lanes) and a head's column is picked
by a mask, its row form by a one-hot product, so nothing is transposed.

:func:`ssd` is one ``jax.custom_vjp``: the forward keeps the state at the start
of every block of ``BLOCK_CHUNKS`` chunks (float32) and the backward walks the
blocks from the last to the first, reruns each from its kept state and pulls
the cotangents back through it. On the TPU both walks are Pallas kernels
(``ops/pallas/ssd.py``: the states ride in VMEM scratch across a sequential
grid axis); elsewhere the same algebra runs under ``lax.scan``. A per-token
scan is the reference's (``benchmark/reference/nemotron_h.py``), not the
program's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from pyspark_tf_gke_tpu.ops.linear_attention import _NN, _NT, _TN, _dot
from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu
from pyspark_tf_gke_tpu.ops.pallas.scope import name_results

CHUNK = 128
BLOCK_CHUNKS = 2          # chunks a grid step (or a scan step) takes
LANES = 128


def _dot32(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def slab_heads(head_dim: int) -> int:
    """Heads that share one 128-lane slab: ``128 / P`` where that is whole."""
    return LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 else 1


def _spread(values, part):
    """``values[i]`` where ``part == i``: a slab's heads' own factors laid over
    the slab's lanes (``part [1, lanes]``) or its state's rows (``[lanes, 1]``)."""
    out = values[0]
    for i, value in enumerate(values[1:], start=1):
        out = jnp.where(part == i, value, out)
    return out


def block_step(x, b, c, dt, a, d, state, group, *, head_dim, mxu):
    """A block of chunks, one after another, of the heads of group ``group``.
    ``x``: a tuple a chunk of the group's slabs ``[chunk, lanes]``; ``b, c``: a
    tuple a chunk of ``[chunk, N]``; ``dt``: a tuple a chunk of ``[chunk, H]``
    float32 with every head's step (the group's columns are picked here, so
    that their gradient comes out of the same ``vjp``); ``a, d [1, H]``
    float32; ``state``: the slabs' ``[lanes, N]`` float32, a slab's heads one
    under the other. Returns ``(y as x is laid out, float32; the state after
    the block)``. Matmul operands are cast to ``mxu``; ``cs``, the decays and
    the state are float32."""
    f32 = jnp.float32
    q, heads = dt[0].shape
    lanes = x[0][0].shape[1]
    pack = lanes // head_dim
    first = group * (len(state) * pack)
    r = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    causal = r >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    ones = jnp.where(causal, 1.0, 0.0)
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    part = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1), head_dim)
    part_rows = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0), head_dim)
    ys = []
    for xc, bc, cc, dtc in zip(x, b, c, dt):
        cs = _dot32(ones, dtc * a, _NN)                      # [chunk, H], summed from the first row
        cb = _dot(cc, bc, _NT, mxu)                          # [chunk, chunk], the group's
        row, after = [], []
        for k, (xs, h) in enumerate(zip(xc, state)):
            xs = xs.astype(f32)
            per = []
            for i in range(pack):
                mine = head_of == first + k * pack + i
                column = lambda m: jnp.sum(jnp.where(mine, m, 0.0), axis=1, keepdims=True)
                cs_col = column(cs)                                           # [chunk, 1]
                cs_row = _dot32(jnp.where(mine, 1.0, 0.0), cs, _NT)           # [1, chunk]
                end = jnp.sum(jnp.where(last, cs_col, 0.0), axis=0, keepdims=True)
                decay = jnp.exp(jnp.where(causal, cs_col - cs_row, 0.0))
                per.append((jnp.where(causal, decay, 0.0) * cb, column(dtc), jnp.exp(cs_col),
                            jnp.exp(end - cs_col), jnp.exp(end), column(d)))
            within, steps, lift, to_end, ends, skip = zip(*per)
            xd = xs * _spread(steps, part)
            y = sum(_dot(m, xd if pack == 1 else jnp.where(part == i, xd, 0.0), _NN, mxu)
                    for i, m in enumerate(within))
            row.append(y + _spread(lift, part) * _dot(cc, h, _NT, mxu)
                       + _spread(skip, part) * xs)
            after.append(h * _spread(ends, part_rows)
                         + _dot(xd * _spread(to_end, part), bc, _TN, mxu))
        ys.append(tuple(row))
        state = tuple(after)
    return tuple(ys), state


def block_rows(s: int, chunk: int) -> int:
    """Rows a block takes: the most chunks up to ``BLOCK_CHUNKS`` that divide
    the sequence."""
    if s % chunk:
        raise ValueError(
            f"ssd: sequence length {s} is not a multiple of the chunk ({chunk}); "
            "pad the rows")
    chunks = s // chunk
    return chunk * max(n for n in range(1, BLOCK_CHUNKS + 1) if chunks % n == 0)


# -- the same walk in jax.numpy (off the TPU) ------------------------------------

def _block_arrays(x, b, c, dt, a, d, state, group, chunk, head_dim, mxu):
    """:func:`block_step` of whole arrays: ``x [rows, heads-of-a-group * P]``,
    ``b, c [rows, N]``, ``dt [rows, H]``, ``state [slabs, lanes, N]``."""
    lanes = head_dim * slab_heads(head_dim)
    chunks = range(0, x.shape[0], chunk)
    cut = lambda m: tuple(m[i:i + chunk] for i in chunks)
    xs = tuple(tuple(m[:, j:j + lanes] for j in range(0, x.shape[1], lanes)) for m in cut(x))
    ys, state = block_step(xs, cut(b), cut(c), cut(dt), a, d, tuple(state), group,
                           head_dim=head_dim, mxu=mxu)
    return jnp.concatenate([jnp.concatenate(row, axis=1) for row in ys], axis=0), jnp.stack(state)


def _scan_step(chunk, head_dim, mxu):
    """:func:`_block_arrays` over rows ``B`` and groups ``G``: ``x, b, c
    [B, G, rows, ...]``, ``dt [B, rows, H]`` (every group reads the whole),
    ``a, d [1, H]``, ``state [B, G, slabs, lanes, N]``, ``group [G]``."""
    step = functools.partial(_block_arrays, chunk=chunk, head_dim=head_dim, mxu=mxu)
    return jax.vmap(jax.vmap(step, in_axes=(0, 0, 0, None, None, None, 0, 0)),
                    in_axes=(0, 0, 0, 0, None, None, 0, None))


def _to_blocks(m, groups, rows):
    b, s, wide = m.shape
    m = m.reshape(b, s // rows, rows, groups, wide // groups)
    return m.transpose(1, 0, 3, 2, 4)                       # [NB, B, G, rows, W]


def _from_blocks(m):
    nb, b, g, rows, w = m.shape
    return m.transpose(1, 0, 3, 2, 4).reshape(b, nb * rows, g * w)


def _scan_operands(x, dt, b, c, groups, rows):
    bsz, s, heads = dt.shape
    return (_to_blocks(x, groups, rows), _to_blocks(b, groups, rows),
            _to_blocks(c, groups, rows),
            dt.reshape(bsz, s // rows, rows, heads).transpose(1, 0, 2, 3))


def _state_shape(x, b, heads, groups):
    head_dim = x.shape[-1] // heads
    pack = slab_heads(head_dim)
    return (x.shape[0], groups, heads // groups // pack, head_dim * pack,
            b.shape[-1] // groups)


def _fwd_scan(x, dt, a, b, c, d, heads, groups, chunk, mxu):
    rows = block_rows(x.shape[1], chunk)
    step, group = _scan_step(chunk, x.shape[-1] // heads, mxu), jnp.arange(groups)
    a, d = a.reshape(1, heads), d.reshape(1, heads)

    def body(state, xs):
        xb, bb, cb, dtb = xs
        y, new = step(xb, bb, cb, dtb, a, d, state, group)
        return new, (y, state)

    _, (y, kept) = jax.lax.scan(body, jnp.zeros(_state_shape(x, b, heads, groups), jnp.float32),
                                _scan_operands(x, dt, b, c, groups, rows))
    return _from_blocks(y).astype(x.dtype), kept.transpose(1, 2, 0, 3, 4, 5)


def _bwd_scan(x, dt, a, b, c, d, kept, dy, heads, groups, chunk, mxu):
    bsz, s, _ = x.shape
    rows = block_rows(s, chunk)
    step, group = _scan_step(chunk, x.shape[-1] // heads, mxu), jnp.arange(groups)
    f32 = jnp.float32

    def body(carry, xs):
        dstate, da, dd = carry
        xb, bb, cb, dtb, state, g_y = xs
        _, pull = jax.vjp(lambda *ins: step(*ins, group), xb.astype(f32), bb.astype(f32),
                          cb.astype(f32), dtb, a.reshape(1, heads), d.reshape(1, heads), state)
        dx, db, dc, ddt, da_, dd_, dprev = pull((g_y.astype(f32), dstate))
        return (dprev, da + da_, dd + dd_), (dx, db, dc, ddt)

    xs = _scan_operands(x, dt, b, c, groups, rows) + (
        kept.transpose(2, 0, 1, 3, 4, 5), _to_blocks(dy, groups, rows))
    zero = jnp.zeros((1, heads), f32)
    (_, da, dd), (dx, db, dc, ddt) = jax.lax.scan(
        body, (jnp.zeros_like(kept[:, :, 0]), zero, zero), xs, reverse=True)
    return (_from_blocks(dx).astype(x.dtype), ddt.transpose(1, 0, 2, 3).reshape(bsz, s, heads),
            da.reshape(a.shape), _from_blocks(db).astype(b.dtype),
            _from_blocks(dc).astype(c.dtype), dd.reshape(d.shape))


# -- one custom_vjp over either walk -----------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _ssd_core(x, dt, a, b, c, d, heads, groups, chunk, mxu, pallas, interpret):
    return _core_fwd(x, dt, a, b, c, d, heads, groups, chunk, mxu, pallas, interpret)[0]


def _core_fwd(x, dt, a, b, c, d, heads, groups, chunk, mxu, pallas, interpret):
    """``y`` and ``kept`` are named for a remat policy (``scope.KERNEL_RESULTS``):
    a block rematted with it keeps them, and its backward runs no forward again."""
    if pallas:
        from pyspark_tf_gke_tpu.ops.pallas import ssd as kernels

        y, kept = kernels.forward(x, dt, a, b, c, d, heads=heads, groups=groups, chunk=chunk,
                                  mxu=mxu, interpret=interpret)
    else:
        y, kept = _fwd_scan(x, dt, a, b, c, d, heads, groups, chunk, mxu)
    y, kept = name_results("ssd", y, kept)
    return y, (x, dt, a, b, c, d, kept)


def _core_bwd(heads, groups, chunk, mxu, pallas, interpret, residuals, dy):
    if pallas:
        from pyspark_tf_gke_tpu.ops.pallas import ssd as kernels

        return kernels.backward(*residuals, dy, heads=heads, groups=groups, chunk=chunk,
                                mxu=mxu, interpret=interpret)
    return _bwd_scan(*residuals, dy, heads, groups, chunk, mxu)


_ssd_core.defvjp(_core_fwd, _core_bwd)


def ssd(x: jnp.ndarray,                # [B, S, H*P]
        dt: jnp.ndarray,               # [B, S, H] the step, > 0 (after softplus)
        a: jnp.ndarray,                # [H] the decay rate, < 0
        b: jnp.ndarray,                # [B, S, G*N]
        c: jnp.ndarray,                # [B, S, G*N]
        d: jnp.ndarray,                # [H] the skip
        *, heads: int, groups: int, chunk: int = CHUNK, pallas: Optional[bool] = None,
        interpret: bool = False) -> jnp.ndarray:
    """The chunked scan, forward and backward (module docstring), of operands
    as a projection writes them: head ``h`` is columns ``[h P, (h + 1) P)`` of
    ``x`` and reads group ``h // (H / G)``'s ``N`` columns of ``b`` and ``c``.
    Returns ``y [B, S, H*P]`` in ``x``'s dtype. The state starts at 0 in every
    row. ``pallas=None`` takes the kernels on the TPU and ``lax.scan``
    elsewhere; ``interpret`` runs the kernels in the Pallas interpreter
    (tests). The chunk's matmuls take their operands in ``x``'s dtype; ``dt``,
    ``a``, ``d``, the summed decay and the state are float32."""
    bsz, s, wide = x.shape
    if (heads % groups or wide % heads or b.shape != c.shape or b.shape[-1] % groups
            or dt.shape != (bsz, s, heads) or a.shape != (heads,) or d.shape != (heads,)):
        raise ValueError(
            f"ssd: x {x.shape}, dt {dt.shape}, a {a.shape}, b {b.shape}, c {c.shape}, d {d.shape} "
            f"are not [B, S, H*P], [B, S, H], [H], [B, S, G*N] twice and [H] of {heads} heads "
            f"in {groups} groups")
    block_rows(s, chunk)                                    # refuses a ragged sequence
    if (heads // groups) % slab_heads(wide // heads):
        raise ValueError(
            f"ssd: a group's {heads // groups} heads of {wide // heads} do not fill whole "
            f"{LANES}-lane slabs")
    if pallas is None:
        pallas = on_tpu() or interpret
    f32 = jnp.float32
    return _ssd_core(x, dt.astype(f32), a.astype(f32), b, c, d.astype(f32), int(heads),
                     int(groups), int(chunk), jnp.dtype(x.dtype), bool(pallas), bool(interpret))
