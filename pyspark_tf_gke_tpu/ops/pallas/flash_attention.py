"""Flash attention forward as a Pallas TPU kernel.

The S×S score matrix never touches HBM: each grid step owns one Q block in
VMEM, loops over K/V blocks with the online-softmax recurrence (running
max ``m``, normalizer ``l``, accumulator in f32), and writes one O block.
Q·Kᵀ and P·V hit the MXU with f32 accumulation.

Layout: inputs are ``[BH, S, D]`` (batch×heads collapsed — each grid row
is independent). Optional additive bias ``[BH, S]`` implements padding
masks (0 for keep, -inf/NEG_INF for drop). ``causal=True`` masks with
block-level skipping (a K block fully in the future is never read).

Backward: ``jax.custom_vjp`` with **Pallas backward kernels** — the
forward additionally emits the per-row logsumexp ``L = m + log(l)``, and
two kernels recompute P blockwise from (q, k, bias, L): one walks K
blocks to produce dQ, the other walks Q blocks to produce dK/dV (the
standard flash-attention backward split). No S×S tensor ever exists in
either pass; residuals are (q, k, v, bias, L, D=rowsum(dO·O)).

The public entry ``flash_attention`` takes ``[B, S, H, D]`` like
``ops.attention.dot_product_attention`` and reshapes. Falls back to the
dense path on non-TPU backends unless ``interpret=True`` (used in tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.pallas.scope import kernel_scope

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, *rest, block_k: int,
                causal: bool, scale: float, use_segs: bool):
    # Shapes: q [1, bq, D], k/v [1, S, D], bias [1, 1, S], o [1, bq, D],
    # lse [1, 1, bq]; with use_segs also segq [1, 1, bq], segk [1, 1, S]
    # (int32 packed-sequence ids — tokens attend within their segment).
    # Row-vectors ride a leading singleton so their last two block dims
    # satisfy Mosaic's (8, 128)-or-full tiling rule.
    if use_segs:
        segq_ref, segk_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    bq = q_ref.shape[1]
    s = k_ref.shape[1]
    d = q_ref.shape[2]
    qi = pl.program_id(1)  # Q-block index

    # Matmul operands stay in the input dtype (bf16 hits the MXU at full
    # rate; f32 would run it 8x slower); accumulation and the softmax
    # statistics are f32. The scale is folded into the f32 scores.
    q = q_ref[0]                                         # [bq, D]

    m = jnp.full((bq, 1), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((bq, 1), dtype=jnp.float32)
    acc = jnp.zeros((bq, d), dtype=jnp.float32)

    num_kb = s // block_k

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                        # [bq, bk] f32
        scores += bias_ref[0, 0, pl.ds(kb * block_k, block_k)][None, :]
        if use_segs:
            segq = segq_ref[0, 0][:, None]               # [bq, 1]
            segk = segk_ref[0, 0, pl.ds(kb * block_k, block_k)][None, :]
            scores = jnp.where(segq == segk, scores, NEG_INF)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    if causal:
        # K blocks fully in the future of this Q block are skipped entirely.
        last_kb = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, num_kb)
    else:
        last_kb = num_kb
    m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m, l, acc))

    valid = m > NEG_INF / 2                              # rows with >=1 unmasked key
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.where(valid, acc / l, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)
    # Logsumexp residual for the backward kernels; +inf on fully-masked
    # rows makes their recomputed P exactly 0.
    lse_ref[0, 0] = jnp.where(valid, m + jnp.log(l), jnp.inf)[:, 0]


def _flash_fwd_bh(q, k, v, bias, segs=None, *, causal: bool, block_q: int,
                  block_k: int, interpret: bool):
    """q,k,v: [BH, S, D]; bias: [BH, 1, S] additive (0 / NEG_INF);
    segs: optional [BH, 1, S] int32 packed-sequence ids.
    Returns (out [BH, S, D], lse [BH, 1, S])."""
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must be divisible by blocks ({block_q},{block_k})")
    scale = d ** -0.5

    kernel = functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                               scale=scale, use_segs=segs is not None)
    mem = {"memory_space": pltpu.VMEM}
    grid = (bh, s // block_q)
    qblock = pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j), **mem)
    full_row = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0), **mem)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0), **mem),
        pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0), **mem),
        pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0), **mem),
        full_row,
    ]
    args = [q, k, v, bias]
    if segs is not None:
        in_specs += [qblock, full_row]   # segq view (q rows), segk view (all keys)
        args += [segs, segs]
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0), **mem),
            qblock,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=interpret,
    )
    with kernel_scope("flash_fwd"):
        return call(*args)


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, lse_ref, do_ref, delta_ref,
               *rest, block_k: int, causal: bool, scale: float,
               use_segs: bool):
    # Shapes: q/do/dq [1, bq, D], k/v [1, S, D], bias [1, 1, S],
    # lse/delta [1, 1, bq]. One Q block per grid step, walking K blocks.
    if use_segs:
        segq_ref, segk_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    bq = q_ref.shape[1]
    s = k_ref.shape[1]
    qi = pl.program_id(1)

    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]                         # [bq, 1]
    delta = delta_ref[0, 0][:, None]                     # [bq, 1]
    acc = jnp.zeros((bq, q_ref.shape[2]), dtype=jnp.float32)

    num_kb = s // block_k

    def body(kb, acc):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        scores += bias_ref[0, 0, pl.ds(kb * block_k, block_k)][None, :]
        if use_segs:
            segq = segq_ref[0, 0][:, None]
            segk = segk_ref[0, 0, pl.ds(kb * block_k, block_k)][None, :]
            scores = jnp.where(segq == segk, scores, NEG_INF)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
        p = jnp.exp(scores - lse)                        # exact probs via saved lse
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(k_blk.dtype)
        return acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    last_kb = (
        jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, num_kb)
        if causal else num_kb
    )
    acc = jax.lax.fori_loop(0, last_kb, body, acc)
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, lse_ref, do_ref, delta_ref,
                *rest, block_q: int, causal: bool, scale: float,
                use_segs: bool):
    # Shapes: k/v/dk/dv [1, bk, D], q/do [1, S, D], bias [1, 1, bk],
    # lse/delta [1, 1, S]. One K block per grid step, walking Q blocks.
    if use_segs:
        segq_ref, segk_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    bk = k_ref.shape[1]
    s = q_ref.shape[1]
    ki = pl.program_id(1)

    k_blk = k_ref[0]
    v_blk = v_ref[0]
    bias = bias_ref[0, 0][None, :]                       # [1, bk]
    dk = jnp.zeros(k_blk.shape, dtype=jnp.float32)
    dv = jnp.zeros(v_blk.shape, dtype=jnp.float32)

    num_qb = s // block_q

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        scores = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + bias
        if use_segs:
            segq = segq_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
            segk = segk_ref[0, 0][None, :]               # [1, bk]
            scores = jnp.where(segq == segk, scores, NEG_INF)
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
        p = jnp.exp(scores - lse)                        # [bq, bk] f32
        dv = dv + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # d(scale·q·kᵀ)/dk = scale·q; fold the scale into ds.
        ds = (p * (dp - delta) * scale).astype(q_blk.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    # Causal: Q blocks strictly before this K block never attend to it.
    first_qb = (ki * bk) // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(first_qb, num_qb, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_bh(q, k, v, bias, lse, out, do, segs=None, *, causal, block_q,
                  block_k, interpret, delta_shift=None):
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    scale = d ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta[:, None, :]                            # [BH, 1, S]
    if delta_shift is not None:
        # lse cotangent from _flash_bh_lse: ds = p*(dp - delta + g_lse).
        delta = delta - delta_shift.astype(jnp.float32)
    use_segs = segs is not None

    mem = {"memory_space": pltpu.VMEM}
    full = lambda last: pl.BlockSpec((1, s, last), lambda i, j: (i, 0, 0), **mem)
    full_row = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0), **mem)
    qrow = pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j), **mem)
    krow = pl.BlockSpec((1, 1, block_k), lambda i, j: (i, 0, j), **mem)

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0), **mem),
        full(d), full(d), full_row, qrow,
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0), **mem),
        qrow,
    ]
    dq_args = [q, k, v, bias, lse, do, delta]
    if use_segs:
        dq_specs += [qrow, full_row]
        dq_args += [segs, segs]
    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal,
                          scale=scale, use_segs=use_segs),
        grid=(bh, s // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
    )
    with kernel_scope("flash_dq"):
        dq = dq_call(*dq_args)

    dkv_specs = [
        full(d),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0), **mem),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0), **mem),
        krow, full_row, full(d), full_row,
    ]
    dkv_args = [q, k, v, bias, lse, do, delta]
    if use_segs:
        dkv_specs += [full_row, krow]
        dkv_args += [segs, segs]
    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, causal=causal,
                          scale=scale, use_segs=use_segs),
        grid=(bh, s // block_k),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0), **mem),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
    )
    with kernel_scope("flash_dkv"):
        dk, dv = dkv_call(*dkv_args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_bh(q, k, v, bias, segs, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd_bh(q, k, v, bias, segs, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)
    return out


def _flash_bh_fwd(q, k, v, bias, segs, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_bh(q, k, v, bias, segs, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return out, (q, k, v, bias, segs, lse, out)


def _flash_bh_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, bias, segs, lse, out = residuals
    dq, dk, dv = _flash_bwd_bh(q, k, v, bias, lse, out, g, segs, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return dq, dk, dv, None, None


_flash_bh.defvjp(_flash_bh_fwd, _flash_bh_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_bh_lse(q, k, v, bias, segs, causal, block_q, block_k, interpret):
    """Flash attention that also returns the per-row logsumexp — the
    building block for cross-device merging (ring attention combines
    per-ring-step partial outputs by their lse)."""
    return _flash_fwd_bh(q, k, v, bias, segs, causal=causal, block_q=block_q,
                         block_k=block_k, interpret=interpret)


def _flash_bh_lse_fwd(q, k, v, bias, segs, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_bh(q, k, v, bias, segs, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return (out, lse), (q, k, v, bias, segs, lse, out)


def _flash_bh_lse_bwd(causal, block_q, block_k, interpret, residuals, gs):
    """dlse/dscores is exactly the softmax probs, so the lse cotangent
    folds into the delta term the kernels already subtract:
    ds = p*(dp - delta + g_lse) — pass (delta - g_lse) and the unchanged
    backward kernels produce the combined gradient."""
    g_out, g_lse = gs
    q, k, v, bias, segs, lse, out = residuals
    dq, dk, dv = _flash_bwd_bh(q, k, v, bias, lse, out, g_out, segs,
                               causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               delta_shift=g_lse)
    return dq, dk, dv, None, None


_flash_bh_lse.defvjp(_flash_bh_lse_fwd, _flash_bh_lse_bwd)


def _pick_seq_block(s: int, desired: int) -> int:
    """Largest Mosaic-valid sequence block: the [.., 1, S] row-vectors
    make S a lane dim, so blocks must be multiples of 128 (or full S)."""
    from pyspark_tf_gke_tpu.ops.pallas.common import pick_block

    return pick_block(s, desired, 128)


def _prep_bh(q, k, v, kv_mask, segment_ids, block_q, block_k, interpret):
    b, s, h, d = q.shape
    if interpret is None:
        from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu

        interpret = not on_tpu()
    if block_q is None:
        block_q = _pick_seq_block(s, DEFAULT_BLOCK_Q)
    if block_k is None:
        block_k = _pick_seq_block(s, DEFAULT_BLOCK_K)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    if kv_mask is None:
        bias = jnp.zeros((b, s), dtype=jnp.float32)
    else:
        bias = jnp.where(kv_mask.astype(bool), 0.0, NEG_INF).astype(jnp.float32)
    bias = jnp.repeat(bias, h, axis=0)[:, None, :]  # [BH, 1, S]
    segs = None
    if segment_ids is not None:
        segs = jnp.repeat(segment_ids.astype(jnp.int32), h, axis=0)[:, None, :]
    return to_bh(q), to_bh(k), to_bh(v), bias, segs, block_q, block_k, interpret


def flash_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: Optional[jnp.ndarray] = None,  # [B, S] bool
    causal: bool = False,
    segment_ids: Optional[jnp.ndarray] = None,  # [B, S] int — packed sequences
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused attention; drop-in for ``dot_product_attention`` on TPU.
    ``segment_ids`` confines attention within matching ids (packed
    sequences / block-diagonal masking), composable with ``kv_mask``
    and ``causal``."""
    b, s, h, d = q.shape
    qb, kb, vb, bias, segs, block_q, block_k, interpret = _prep_bh(
        q, k, v, kv_mask, segment_ids, block_q, block_k, interpret
    )
    out = _flash_bh(qb, kb, vb, bias, segs, causal, block_q, block_k, interpret)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_attention_block(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: Optional[jnp.ndarray] = None,  # [B, S] bool
    segment_ids: Optional[jnp.ndarray] = None,  # [B, S] int
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """One attention *block*: returns ``(out [B,S,H,D], lse [B,S,H])``
    so a caller can combine partial attentions over K/V blocks held
    elsewhere (ring attention merges per-ring-step results by lse).
    Rows with no unmasked key get lse = NEG_INF (no mass) and out = 0 —
    finite, so the logsumexp merge stays NaN-free."""
    b, s, h, d = q.shape
    qb, kb, vb, bias, segs, block_q, block_k, interpret = _prep_bh(
        q, k, v, kv_mask, segment_ids, block_q, block_k, interpret
    )
    out, lse = _flash_bh_lse(qb, kb, vb, bias, segs, False, block_q, block_k,
                             interpret)
    out = out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    lse = lse[:, 0, :].reshape(b, h, s).transpose(0, 2, 1)  # [B, S, H]
    lse = jnp.where(jnp.isposinf(lse), NEG_INF, lse)
    return out, lse
