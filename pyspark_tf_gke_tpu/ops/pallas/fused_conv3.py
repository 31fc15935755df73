"""Fused 3x3 conv (stride 1, SAME) with BN-stat epilogue + on-read norm.

Completes the conv+BN fusion family started in ``fused_matmul.py``:
with only the 1x1 convs fused, each bottleneck block still pays one
materialized normalized tensor (norm1's output feeding the XLA 3x3
conv) and one statistics reduction read (norm2's stats over the 3x3
output). Owning the 3x3 conv removes both: the kernel reads the RAW
conv1 output, applies norm1's ``relu(x*a+b)`` per tile in VMEM, runs
the nine tap matmuls from a zero-padded VMEM scratch (SAME padding:
the pad ring is zero AFTER normalize+relu, matching XLA's semantics of
padding the normalized input), and writes the raw output together with
its per-channel sum/sumsq partials.

Grid is ``(B,)`` — one image per step; every ResNet-50 stage's full
H x W x C activation fits VMEM comfortably (largest: 56x56x64 bf16 =
400 KB). The nine taps are static slices of the padded scratch, so no
halo exchange or dynamic indexing is needed. Backward reuses the same
shapes: ``dxn`` is the flipped-tap convolution of ``dy`` (same padded-
scratch trick), masked and scaled in-epilogue with the ``d a``/``d b``
reductions; ``dw`` accumulates the nine ``win^T @ dy`` products across
the batch grid — the output block's index map is constant, so the
accumulator stays VMEM-resident for the whole (consecutive) grid and
cross-step accumulation is well-defined.

Stride-2 blocks keep the XLA conv (3 of 16 blocks in ResNet-50): the
strided halo bookkeeping isn't worth kernel complexity for <20% of the
3x3 FLOPs. ``models/resnet.py::FusedBottleneckBlock`` picks per-block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from pyspark_tf_gke_tpu.ops.pallas.fused_matmul import (
    _mem, _resolve_interpret)
from pyspark_tf_gke_tpu.ops.pallas.scope import kernel_scope


def _transform(x, a_ref, b_ref, transform: bool, relu: bool):
    if not transform:
        return x
    # a/b arrive as (1, K) blocks (Mosaic's 1-D operand layout check
    # rejects partial 1-D tiles on real TPUs — see fused_matmul.py);
    # [None] lifts them to (1, 1, K) to broadcast over (H, W, K).
    t = x.astype(jnp.float32) * a_ref[...][None] + b_ref[...][None]
    if relu:
        t = jnp.maximum(t, 0.0)
    return t.astype(x.dtype)


def _fwd_kernel(x_ref, w_ref, a_ref, b_ref, y_ref, s_ref, pad_ref, *,
                transform: bool, relu: bool, want_stats: bool):
    h, w_, k = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    n = w_ref.shape[3]
    xn = _transform(x_ref[0], a_ref, b_ref, transform, relu)
    pad_ref[...] = jnp.zeros_like(pad_ref)
    pad_ref[1:h + 1, 1:w_ + 1, :] = xn
    acc = jnp.zeros((h * w_, n), jnp.float32)
    for dh in range(3):
        for dw in range(3):
            win = pad_ref[dh:dh + h, dw:dw + w_, :].reshape(h * w_, k)
            acc += jax.lax.dot_general(
                win, w_ref[dh, dw], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    y_ref[0] = acc.reshape(h, w_, n).astype(y_ref.dtype)
    if want_stats:
        yr = acc.astype(y_ref.dtype).astype(jnp.float32)
        s_ref[0] = jnp.stack([yr.sum(axis=0), (yr * yr).sum(axis=0)])


def _fwd_call(x, w, a, b, *, relu, want_stats, interpret):
    bsz, h, w_, k = x.shape
    n = w.shape[3]
    transform = a is not None
    if not transform:
        a = jnp.ones((k,), jnp.float32)
        b = jnp.zeros((k,), jnp.float32)
    mem = _mem()
    kernel = functools.partial(_fwd_kernel, transform=transform, relu=relu,
                               want_stats=want_stats)
    call = pl.pallas_call(
        kernel,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, h, w_, k), lambda i: (i, 0, 0, 0), **mem),
            pl.BlockSpec((3, 3, k, n), lambda i: (0, 0, 0, 0), **mem),
            pl.BlockSpec((1, k), lambda i: (0, 0), **mem),
            pl.BlockSpec((1, k), lambda i: (0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, h, w_, n), lambda i: (i, 0, 0, 0), **mem),
            pl.BlockSpec((1, 2, n), lambda i: (i, 0, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, w_, n), x.dtype),
            jax.ShapeDtypeStruct((bsz, 2, n), jnp.float32),
        ],
        scratch_shapes=[_pad_scratch(h, w_, k, x.dtype)],
        interpret=interpret,
    )
    with kernel_scope("fused_conv3_fwd"):
        y, stats = call(x, w, a.reshape(1, k), b.reshape(1, k))
    return y, stats.sum(axis=0)


def _pad_scratch(h, w_, k, dtype):
    from pyspark_tf_gke_tpu.ops.pallas.fused_matmul import pltpu

    return pltpu.VMEM((h + 2, w_ + 2, k), dtype)


def _dx_kernel(dy_ref, w_ref, x_ref, a_ref, b_ref, dx_ref, ds_ref, pad_ref,
               *, transform: bool, relu: bool):
    h, w_, n = dy_ref.shape[1], dy_ref.shape[2], dy_ref.shape[3]
    k = w_ref.shape[2]
    pad_ref[...] = jnp.zeros_like(pad_ref)
    pad_ref[1:h + 1, 1:w_ + 1, :] = dy_ref[0]
    u = jnp.zeros((h * w_, k), jnp.float32)
    for dh in range(3):
        for dw in range(3):
            # transposed conv: tap (dh, dw) of the forward gathers
            # x[p + (dh-1, dw-1)] into y[p]; its adjoint scatters
            # dy[p - (dh-1, dw-1)] into dx[p] — i.e. the FLIPPED tap
            # window over padded dy
            win = pad_ref[2 - dh:2 - dh + h,
                          2 - dw:2 - dw + w_, :].reshape(h * w_, n)
            u += jax.lax.dot_general(
                win, w_ref[dh, dw], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    if transform:
        xf = x_ref[0].astype(jnp.float32).reshape(h * w_, k)
        a = a_ref[...]  # (1, k): broadcasts over rows
        if relu:
            t = xf * a + b_ref[...]
            u = jnp.where(t > 0.0, u, 0.0)
        dx_ref[0] = (u * a).reshape(h, w_, k).astype(dx_ref.dtype)
        ds_ref[0] = jnp.stack([(u * xf).sum(axis=0), u.sum(axis=0)])
    else:
        dx_ref[0] = u.reshape(h, w_, k).astype(dx_ref.dtype)


def _dx_call(dy, w, x, a, b, *, relu, interpret):
    bsz, h, w_, n = dy.shape
    k = w.shape[2]
    transform = a is not None
    if not transform:
        a = jnp.ones((k,), jnp.float32)
        b = jnp.zeros((k,), jnp.float32)
    mem = _mem()
    kernel = functools.partial(_dx_kernel, transform=transform, relu=relu)
    call = pl.pallas_call(
        kernel,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, h, w_, n), lambda i: (i, 0, 0, 0), **mem),
            pl.BlockSpec((3, 3, k, n), lambda i: (0, 0, 0, 0), **mem),
            pl.BlockSpec((1, h, w_, k), lambda i: (i, 0, 0, 0), **mem),
            pl.BlockSpec((1, k), lambda i: (0, 0), **mem),
            pl.BlockSpec((1, k), lambda i: (0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, h, w_, k), lambda i: (i, 0, 0, 0), **mem),
            pl.BlockSpec((1, 2, k), lambda i: (i, 0, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, w_, k), x.dtype),
            jax.ShapeDtypeStruct((bsz, 2, k), jnp.float32),
        ],
        scratch_shapes=[_pad_scratch(h, w_, n, dy.dtype)],
        interpret=interpret,
    )
    with kernel_scope("fused_conv3_dx"):
        dx, dstats = call(dy, w, x, a.reshape(1, k), b.reshape(1, k))
    return dx, dstats.sum(axis=0)


def _dw_kernel(x_ref, dy_ref, a_ref, b_ref, dw_ref, pad_ref, *,
               transform: bool, relu: bool):
    i = pl.program_id(0)
    h, w_, k = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    n = dy_ref.shape[3]
    xn = _transform(x_ref[0], a_ref, b_ref, transform, relu)
    pad_ref[...] = jnp.zeros_like(pad_ref)
    pad_ref[1:h + 1, 1:w_ + 1, :] = xn
    dy = dy_ref[0].reshape(h * w_, n)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for dh in range(3):
        for dw in range(3):
            win = pad_ref[dh:dh + h, dw:dw + w_, :].reshape(h * w_, k)
            dw_ref[dh, dw] += jax.lax.dot_general(
                win, dy, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _dw_call(x, dy, a, b, *, relu, interpret):
    bsz, h, w_, k = x.shape
    n = dy.shape[3]
    transform = a is not None
    if not transform:
        a = jnp.ones((k,), jnp.float32)
        b = jnp.zeros((k,), jnp.float32)
    mem = _mem()
    kernel = functools.partial(_dw_kernel, transform=transform, relu=relu)
    # out index map is CONSTANT over the (only) grid dim, so the f32
    # accumulator block stays resident across consecutive steps — the
    # safe accumulation pattern (cf. fused_matmul's no-revisit rule)
    call = pl.pallas_call(
        kernel,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, h, w_, k), lambda i: (i, 0, 0, 0), **mem),
            pl.BlockSpec((1, h, w_, n), lambda i: (i, 0, 0, 0), **mem),
            pl.BlockSpec((1, k), lambda i: (0, 0), **mem),
            pl.BlockSpec((1, k), lambda i: (0, 0), **mem),
        ],
        out_specs=pl.BlockSpec((3, 3, k, n), lambda i: (0, 0, 0, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((3, 3, k, n), jnp.float32),
        scratch_shapes=[_pad_scratch(h, w_, k, x.dtype)],
        interpret=interpret,
    )
    with kernel_scope("fused_conv3_dw"):
        return call(x, dy, a.reshape(1, k), b.reshape(1, k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv3(x, w, a, b, relu, want_stats, interpret):
    y, stats = _fwd_call(x, w, a, b, relu=relu, want_stats=want_stats,
                         interpret=interpret)
    return (y, stats[0], stats[1]) if want_stats else y


def _conv3_fwd(x, w, a, b, relu, want_stats, interpret):
    out = _conv3(x, w, a, b, relu, want_stats, interpret)
    y = out[0] if want_stats else out
    return out, (x, w, a, b, y)


def _conv3_bwd(relu, want_stats, interpret, res, g):
    x, w, a, b, y = res
    if want_stats:
        gy, gs, gss = g
        dy = (gy.astype(jnp.float32) + gs[None, None, None, :]
              + 2.0 * y.astype(jnp.float32) * gss[None, None, None, :]
              ).astype(y.dtype)
    else:
        dy = g
    transform = a is not None
    dx, dstats = _dx_call(dy, w, x, a, b, relu=relu, interpret=interpret)
    dw = _dw_call(x, dy, a, b, relu=relu, interpret=interpret).astype(w.dtype)
    if transform:
        return dx, dw, dstats[0].astype(a.dtype), dstats[1].astype(b.dtype)
    return dx, dw, None, None


_conv3.defvjp(_conv3_fwd, _conv3_bwd)


def conv3_norm_stats(
    x: jnp.ndarray,               # [B, H, W, K] RAW producer output
    w: jnp.ndarray,               # [3, 3, K, N]
    a: Optional[jnp.ndarray] = None,   # [K] f32 folded norm scale
    b: Optional[jnp.ndarray] = None,   # [K] f32 folded norm shift
    *,
    relu: bool = True,
    want_stats: bool = False,
    interpret: Optional[bool] = None,
):
    """Stride-1 SAME 3x3 conv of ``relu(x*a+b)`` (transform optional)
    with optional per-output-channel (sum, sumsq) epilogue."""
    if (a is None) != (b is None):
        raise ValueError("a and b must be provided together")
    if w.shape[:2] != (3, 3):
        raise ValueError(f"3x3 kernel expected, got {w.shape}")
    return _conv3(x, w, a, b, relu if a is not None else False,
                  want_stats, _resolve_interpret(interpret))
