"""Fused LayerNorm as a Pallas TPU kernel.

One VMEM-resident pass per row block: mean, variance (rsqrt), scale+shift
— a single kernel instead of the half-dozen HBM round-trips a naive
implementation costs. f32 statistics regardless of input dtype.

An optional **residual input** is summed inside the kernel
(``y = LN(x + r)``): transformer blocks are exactly this pattern, and
keeping the add inside recovers the add+LN fusion XLA would otherwise do
itself — without it the opaque kernel boundary costs one extra HBM pass
and the Pallas LN loses to plain XLA in-graph.

Backward via custom_vjp with the standard closed-form LN gradient
(plain JAX; XLA fuses it into two passes).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.pallas.scope import kernel_scope

DEFAULT_BLOCK_ROWS = 256


def _ln_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)                       # [rows, D]
    mean = x.mean(axis=-1, keepdims=True)
    xc = x - mean
    var = (xc * xc).mean(axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = xc * inv * scale_ref[:].astype(jnp.float32)[None, :] + \
        bias_ref[:].astype(jnp.float32)[None, :]
    o_ref[:] = y.astype(o_ref.dtype)


def _ln_add_kernel(x_ref, r_ref, scale_ref, bias_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    xc = x - mean
    var = (xc * xc).mean(axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = xc * inv * scale_ref[:].astype(jnp.float32)[None, :] + \
        bias_ref[:].astype(jnp.float32)[None, :]
    o_ref[:] = y.astype(o_ref.dtype)


def _ln_forward(x2, scale, bias, eps, block_rows, interpret, r2=None):
    n, d = x2.shape
    block_rows = min(block_rows, n)
    if n % block_rows:
        raise ValueError(f"rows {n} not divisible by block_rows {block_rows}")
    mem = {"memory_space": pltpu.VMEM}
    row_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0), **mem)
    vec_spec = pl.BlockSpec((d,), lambda i: (0,), **mem)
    if r2 is None:
        kernel, in_specs, args = (
            functools.partial(_ln_kernel, eps=eps),
            [row_spec, vec_spec, vec_spec],
            (x2, scale, bias),
        )
    else:
        kernel, in_specs, args = (
            functools.partial(_ln_add_kernel, eps=eps),
            [row_spec, row_spec, vec_spec, vec_spec],
            (x2, r2, scale, bias),
        )
    call = pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        interpret=interpret,
    )
    with kernel_scope("layernorm_fwd"):
        return call(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x2, scale, bias, eps, block_rows, interpret):
    return _ln_forward(x2, scale, bias, eps, block_rows, interpret)


def _ln_fwd(x2, scale, bias, eps, block_rows, interpret):
    return _ln_forward(x2, scale, bias, eps, block_rows, interpret), (x2, scale)


def _ln_bwd(eps, block_rows, interpret, residuals, g):
    x2, scale = residuals
    x = x2.astype(jnp.float32)
    g = g.astype(jnp.float32)
    d = x.shape[-1]
    mean = x.mean(-1, keepdims=True)
    xc = x - mean
    var = (xc * xc).mean(-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xc * inv
    gs = g * scale.astype(jnp.float32)[None, :]
    dx = inv / d * (d * gs - gs.sum(-1, keepdims=True) - xhat * (gs * xhat).sum(-1, keepdims=True))
    dscale = (g * xhat).sum(0)
    dbias = g.sum(0)
    return dx.astype(x2.dtype), dscale.astype(scale.dtype), dbias.astype(scale.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ln_res(x2, r2, scale, bias, eps, block_rows, interpret):
    return _ln_forward(x2, scale, bias, eps, block_rows, interpret, r2=r2)


def _ln_res_fwd(x2, r2, scale, bias, eps, block_rows, interpret):
    out = _ln_forward(x2, scale, bias, eps, block_rows, interpret, r2=r2)
    return out, (x2, r2, scale)


def _ln_res_bwd(eps, block_rows, interpret, residuals, g):
    x2, r2, scale = residuals
    # d(x+r) flows identically to both inputs; reuse the closed-form LN
    # gradient on the recomputed sum (XLA fuses the add into the bwd).
    xsum = (x2.astype(jnp.float32) + r2.astype(jnp.float32)).astype(x2.dtype)
    dx, dscale, dbias = _ln_bwd(eps, block_rows, interpret, (xsum, scale), g)
    return dx, dx.astype(r2.dtype), dscale, dbias


_ln_res.defvjp(_ln_res_fwd, _ln_res_bwd)


def _pick_block(n: int, block_rows: int) -> int:
    from pyspark_tf_gke_tpu.ops.pallas.common import pick_block

    return pick_block(n, block_rows, 8)


def fused_layernorm(
    x: jnp.ndarray,                  # [..., D]
    scale: jnp.ndarray,              # [D]
    bias: jnp.ndarray,               # [D]
    eps: float = 1e-6,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
    residual: Optional[jnp.ndarray] = None,  # same shape as x; y = LN(x+r)
) -> jnp.ndarray:
    if interpret is None:
        from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu

        interpret = not on_tpu()
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    br = _pick_block(x2.shape[0], block_rows)
    if residual is None:
        return _ln(x2, scale, bias, eps, br, interpret).reshape(shape)
    r2 = residual.reshape(-1, shape[-1])
    return _ln_res(x2, r2, scale, bias, eps, br, interpret).reshape(shape)
