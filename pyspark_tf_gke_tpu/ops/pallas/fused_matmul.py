"""Fused 1x1-conv (matmul) kernels with BN-stat epilogues for ResNet.

Why this exists (an investigation on a v5e in 2026-07, before the
benchmark; no cell runs it, ROADMAP.md Design 3): on the
v5e, ResNet-50's normalization costs 8.2 ms/step = 29% of the step while
the conv-only floor is 38.6% MFU. The probe pinned the cost on *pass
structure*, not the batch reduction: every BatchNorm between a conv and
its consumer is an unfused HBM read-modify-write of a full activation
tensor (GroupNorm — no batch reduction at all — measured the same), and
a standalone norm kernel cannot beat XLA's own fused elementwise passes.
The only way to remove the passes is to move the norm work inside the
convs' own HBM touches. A bottleneck block's 1x1 convs ARE matmuls
(NHWC: (B*H*W, Cin) @ (Cin, Cout)), so this file implements a Pallas
matmul with:

- **input transform**: ``relu((x - mean) * inv * scale + bias)`` applied
  per K-channel on tiles already in VMEM, so a consumer conv reads the
  producer's RAW output and normalizes for free (the separate
  normalize write + read disappears);
- **stats epilogue**: per-output-channel ``sum`` / ``sum-of-squares``
  accumulated while the f32 accumulator tile is still in registers, so
  the next norm's statistics cost no extra read of the conv output.

The input transform is folded to per-channel affine form
``relu(x * a + b)`` with ``a = scale * rsqrt(var + eps)`` and
``b = bias - mean * a`` — host-side f32 vector math, free.

Backward rides the same two kernel shapes (``dx = dy @ w^T`` with the
relu mask and ``d a/d b`` reductions fused into the epilogue;
``dw = xn^T @ dy`` re-applying the input transform on the fly), wrapped
in ``jax.custom_vjp`` at *kernel* granularity: the surrounding
statistics math (mean/var from sums, the ``a``/``b`` folding) is plain
JAX, so BatchNorm's gradient-through-statistics chain is handled by
autodiff, not hand-derived.

Stats are computed on the bf16-rounded output values (not the raw f32
accumulator): the consumer normalizes the bf16 tensor it reads, so the
statistics must describe exactly that tensor — this matches what a
separate XLA reduction over the stored output would compute.

Reference counterpart: none — the reference's largest model is a plain
CNN (``/root/reference/workloads/raw-tf/train_tf_ps.py:346-378``) and
its BatchNorm story is whatever Keras emits. This kernel family exists
to hit the TPU roofline the reference never approached.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.pallas.scope import kernel_scope

DEFAULT_BLOCK_M = 448   # divides B*H*W for every ResNet-50 stage at B=64k
DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_K = 512


def _pick(n: int, desired: int, multiple: int) -> int:
    from pyspark_tf_gke_tpu.ops.pallas.common import pick_block

    return pick_block(n, desired, multiple)


def _mem(spec_kwargs=None):
    return {"memory_space": pltpu.VMEM}


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel: y = xn @ w (+ stats), xn = relu(x*a + b) or raw x
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, a_ref, b_ref, y_ref, s_ref, acc_ref, *,
                nk: int, transform: bool, relu: bool, want_stats: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if transform:
        # a/b ride as (1, bk) 2-D blocks: Mosaic rejects 1-D operand
        # blocks that don't match XLA's 1-D layout tile (seen on real
        # v5e: "XLA layout {0:T(1024)} does not match Mosaic layout
        # {0:T(512)} for f32[1024]"), while (1, K) lanes-shaped vectors
        # follow the ordinary 2-D tiling rules.
        t = x.astype(jnp.float32) * a_ref[...] + b_ref[...]
        if relu:
            t = jnp.maximum(t, 0.0)
        xn = t.astype(x.dtype)  # bf16 feed matches the unfused norm's dtype
    else:
        xn = x
    acc_ref[...] += jax.lax.dot_general(
        xn, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _emit():
        acc = acc_ref[...]
        y_ref[...] = acc.astype(y_ref.dtype)
        if want_stats:
            # Per-M-tile PARTIAL stats over the ROUNDED values the
            # consumer will read. Each (i, j) writes its own partial —
            # no cross-iteration output-window accumulation, which is
            # undefined for non-consecutive revisits on real TPUs (the
            # i dim is outermost). The caller reduces the tiny
            # (m_tiles, 2, N) f32 array in one XLA pass.
            yr = acc.astype(y_ref.dtype).astype(jnp.float32)
            s_ref[...] = jnp.stack(
                [yr.sum(axis=0), (yr * yr).sum(axis=0)])[None]


def _fwd_call(x, w, a, b, *, relu, want_stats, block_m, block_n, block_k,
              interpret):
    m, kdim = x.shape
    _, n = w.shape
    bm = _pick(m, block_m, 8)
    bn = _pick(n, block_n, 128)
    bk = _pick(kdim, block_k, 128)
    nk = kdim // bk
    transform = a is not None
    if not transform:  # placeholder operands keep one kernel signature
        a = jnp.ones((kdim,), jnp.float32)
        b = jnp.zeros((kdim,), jnp.float32)
    mem = _mem()
    kernel = functools.partial(
        _fwd_kernel, nk=nk, transform=transform, relu=relu,
        want_stats=want_stats)
    call = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k), **mem),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j), **mem),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k), **mem),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k), **mem),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j), **mem),
            pl.BlockSpec((1, 2, bn), lambda i, j, k: (i, 0, j), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((m // bm, 2, n), jnp.float32),
        ],
        scratch_shapes=[_scratch((bm, bn))],
        interpret=interpret,
    )
    with kernel_scope("fused_matmul_fwd"):
        y, stats = call(x, w, a.reshape(1, kdim), b.reshape(1, kdim))
    # reduce the per-M-tile partials: (m_tiles, 2, n) f32 — a few MB at
    # most, one cheap XLA pass, no undefined revisit semantics
    return y, stats.sum(axis=0)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _dx_kernel(dy_ref, w_ref, x_ref, a_ref, b_ref, dx_ref, ds_ref, acc_ref,
               *, nn_: int, transform: bool, relu: bool):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        dy_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == nn_ - 1)
    def _emit():
        u = acc_ref[...]  # d xn
        if transform:
            xf = x_ref[...].astype(jnp.float32)
            a = a_ref[...]  # (1, bk): broadcasts over rows
            if relu:
                t = xf * a + b_ref[...]
                u = jnp.where(t > 0.0, u, 0.0)  # relu mask on d t
            dx_ref[...] = (u * a).astype(dx_ref.dtype)
            # per-M-tile partials for (da, db) — same no-revisit rule as
            # the forward stats epilogue; caller sums over M tiles
            ds_ref[...] = jnp.stack(
                [(u * xf).sum(axis=0), u.sum(axis=0)])[None]
        else:
            dx_ref[...] = u.astype(dx_ref.dtype)


def _dx_call(dy, w, x, a, b, *, relu, block_m, block_n, block_k, interpret):
    m, n = dy.shape
    kdim = w.shape[0]
    bm = _pick(m, block_m, 8)
    bk = _pick(kdim, block_k, 128)
    bn = _pick(n, block_n, 128)
    nn_ = n // bn
    transform = a is not None
    if not transform:
        a = jnp.ones((kdim,), jnp.float32)
        b = jnp.zeros((kdim,), jnp.float32)
    mem = _mem()
    kernel = functools.partial(_dx_kernel, nn_=nn_, transform=transform,
                               relu=relu)
    call = pl.pallas_call(
        kernel,
        grid=(m // bm, kdim // bk, nn_),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n), **mem),
            pl.BlockSpec((bk, bn), lambda i, j, n: (j, n), **mem),
            pl.BlockSpec((bm, bk), lambda i, j, n: (i, j), **mem),
            pl.BlockSpec((1, bk), lambda i, j, n: (0, j), **mem),
            pl.BlockSpec((1, bk), lambda i, j, n: (0, j), **mem),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, n: (i, j), **mem),
            pl.BlockSpec((1, 2, bk), lambda i, j, n: (i, 0, j), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, kdim), x.dtype),
            jax.ShapeDtypeStruct((m // bm, 2, kdim), jnp.float32),
        ],
        scratch_shapes=[_scratch((bm, bk))],
        interpret=interpret,
    )
    with kernel_scope("fused_matmul_dx"):
        dx, dstats = call(dy, w, x, a.reshape(1, kdim), b.reshape(1, kdim))
    return dx, dstats.sum(axis=0)


def _dw_kernel(x_ref, dy_ref, a_ref, b_ref, dw_ref, acc_ref, *,
               nm: int, transform: bool, relu: bool):
    mstep = pl.program_id(2)

    @pl.when(mstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if transform:
        t = x.astype(jnp.float32) * a_ref[...] + b_ref[...]
        if relu:
            t = jnp.maximum(t, 0.0)
        xn = t.astype(x.dtype)
    else:
        xn = x
    acc_ref[...] += jax.lax.dot_general(
        xn, dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(mstep == nm - 1)
    def _emit():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _dw_call(x, dy, a, b, *, relu, block_m, block_n, block_k, interpret):
    m, kdim = x.shape
    _, n = dy.shape
    bm = _pick(m, block_m, 8)
    bk = _pick(kdim, block_k, 128)
    bn = _pick(n, block_n, 128)
    nm = m // bm
    transform = a is not None
    if not transform:
        a = jnp.ones((kdim,), jnp.float32)
        b = jnp.zeros((kdim,), jnp.float32)
    mem = _mem()
    kernel = functools.partial(_dw_kernel, nm=nm, transform=transform,
                               relu=relu)
    call = pl.pallas_call(
        kernel,
        grid=(kdim // bk, n // bn, nm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, mstep: (mstep, i), **mem),
            pl.BlockSpec((bm, bn), lambda i, j, mstep: (mstep, j), **mem),
            pl.BlockSpec((1, bk), lambda i, j, mstep: (0, i), **mem),
            pl.BlockSpec((1, bk), lambda i, j, mstep: (0, i), **mem),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, mstep: (i, j), **mem),
        out_shape=jax.ShapeDtypeStruct((kdim, n), dy.dtype),
        scratch_shapes=[_scratch((bk, bn))],
        interpret=interpret,
    )
    with kernel_scope("fused_matmul_dw"):
        return call(x, dy, a.reshape(1, kdim), b.reshape(1, kdim))


# ---------------------------------------------------------------------------
# custom-vjp ops
# ---------------------------------------------------------------------------


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu

        return not on_tpu()
    return interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _nrm_mm(x, w, a, b, relu, want_stats, interpret):
    y, stats = _fwd_call(
        x, w, a, b, relu=relu, want_stats=want_stats,
        block_m=DEFAULT_BLOCK_M, block_n=DEFAULT_BLOCK_N,
        block_k=DEFAULT_BLOCK_K, interpret=interpret)
    return (y, stats[0], stats[1]) if want_stats else y


def _nrm_mm_fwd(x, w, a, b, relu, want_stats, interpret):
    out = _nrm_mm(x, w, a, b, relu, want_stats, interpret)
    y = out[0] if want_stats else out
    return out, (x, w, a, b, y)


def _nrm_mm_bwd(relu, want_stats, interpret, res, g):
    x, w, a, b, y = res
    if want_stats:
        gy, gs, gss = g
        # cotangent through the stat outputs: d sum -> +gs per column,
        # d sumsq -> +2*y*gss. One fused XLA elementwise pass.
        dy = (gy.astype(jnp.float32) + gs[None, :]
              + 2.0 * y.astype(jnp.float32) * gss[None, :]).astype(y.dtype)
    else:
        dy = g
    transform = a is not None
    dx, dstats = _dx_call(
        dy, w, x, a, b, relu=relu, block_m=DEFAULT_BLOCK_M,
        block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
        interpret=interpret)
    dw = _dw_call(
        x, dy, a, b, relu=relu, block_m=DEFAULT_BLOCK_M,
        block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
        interpret=interpret).astype(w.dtype)
    if transform:
        return dx, dw, dstats[0].astype(a.dtype), dstats[1].astype(b.dtype)
    return dx, dw, None, None


_nrm_mm.defvjp(_nrm_mm_fwd, _nrm_mm_bwd)


def norm_relu_matmul(
    x: jnp.ndarray,              # [M, K] RAW producer output (pre-norm)
    w: jnp.ndarray,              # [K, N]
    a: Optional[jnp.ndarray] = None,   # [K] f32: scale * rsqrt(var+eps)
    b: Optional[jnp.ndarray] = None,   # [K] f32: bias - mean * a
    *,
    relu: bool = True,
    want_stats: bool = False,
    interpret: Optional[bool] = None,
):
    """``relu(x*a + b) @ w`` with optional per-output-channel stats.

    With ``a``/``b`` None the transform is skipped (plain matmul +
    stats epilogue). Returns ``y`` or ``(y, sum, sumsq)`` where
    ``sum``/``sumsq`` are f32 per-column reductions of the rounded
    output — exactly what BatchNorm statistics need, for free.
    """
    if (a is None) != (b is None):
        raise ValueError("a and b must be provided together")
    return _nrm_mm(x, w, a, b, relu if a is not None else False,
                   want_stats, _resolve_interpret(interpret))


def bn_fold(mean: jnp.ndarray, var: jnp.ndarray, scale: jnp.ndarray,
            bias: jnp.ndarray, eps: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold BN parameters+statistics to the per-channel affine
    ``(a, b)`` the kernels consume: ``norm(x) = x*a + b``."""
    a = scale.astype(jnp.float32) * jax.lax.rsqrt(
        var.astype(jnp.float32) + eps)
    b = bias.astype(jnp.float32) - mean.astype(jnp.float32) * a
    return a, b


def stats_to_moments(s: jnp.ndarray, ss: jnp.ndarray,
                     count: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum, sumsq, N) -> (mean, biased variance) — flax BatchNorm's
    biased-variance convention (``mean(x^2) - mean(x)^2``)."""
    mean = s / count
    var = jnp.maximum(ss / count - mean * mean, 0.0)
    return mean, var
