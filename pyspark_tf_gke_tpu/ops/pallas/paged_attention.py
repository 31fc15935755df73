"""Paged-attention decode kernel: ragged block-table reads over a
global KV page pool (the TPU analog of vLLM's PagedAttention, Kwon et
al., SOSP'23).

The continuous-batching engine (``train/continuous.py``) stores K/V in
a single page pool per layer — ``k_pages [N, P, H_kv, D]`` — and each
slot owns an int32 row of a block table ``[num_slots, max_pages]``
naming its pages in order. Decode attention for slot ``i`` must read
only the pages that hold its ``fills[i]`` live tokens; everything else
in the pool belongs to other requests.

Kernel layout (``pltpu.PrefetchScalarGridSpec``): grid ``(slot,
page)``; the block table and fill levels ride as scalar-prefetch
operands so the K/V page ``BlockSpec`` index maps can *gather through
the table* — block ``(i, j)`` fetches pool page ``block_table[i, j]``.
Ragged early-stop: for ``j`` past the slot's last live page the index
map CLAMPS to that last live page — Mosaic's pipeline skips the DMA
when the block index repeats, so HBM traffic is proportional to each
slot's *filled* tokens, not ``max_pages`` — and ``pl.when`` skips the
compute. Online softmax (running max / normalizer / f32 accumulator in
VMEM scratch, carried across the sequential page grid dim) produces
the output at the last page step, exactly the flash-attention
recurrence over table-gathered blocks.

int8 KV rides along: when the pool is int8, per-(position, head) f32
scale pages are gathered through the same table and the dequant
(convert * scale) happens in-kernel on the VMEM-resident page.

``paged_attention_reference`` is the pure-JAX oracle (gather + masked
dot, the same math as the dense slot-decode path in
``models/causal_lm.py``): the non-TPU fallback and the numerics
reference the interpret-mode kernel is tested against, mirroring
``flash_attention.py``'s ``interpret=`` pattern so CPU CI exercises
the identical code path.

Multi-query chunks (``paged_attention_chunk``): chunked prefill writes
a prompt piece of ``S`` tokens straight into a slot's pages and then
needs attention FOR those S queries over the slot's prior pages plus
the piece itself — the same block-table gather with an in-chunk causal
mask (query ``i`` at absolute position ``fill - S + i`` sees keys at
positions ``<= fill - S + i``). The single-query decode kernel is the
``S = 1`` instance of the same program; both share one kernel body, so
the sweep in ``tools/smoke_check.py --kernels-only`` covers both.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.pallas.scope import kernel_scope

NEG_INF = -1e30


def paged_attention_chunk_reference(
    q: jnp.ndarray,            # [B, S, H, D] chunk of query tokens
    k_pages: jnp.ndarray,      # [N, P, H_kv, D] (dtype or int8)
    v_pages: jnp.ndarray,      # [N, P, H_kv, D]
    block_table: jnp.ndarray,  # [B, max_pages] int32; >= N = unallocated
    fills: jnp.ndarray,        # [B] int32 live tokens INCLUDING the chunk
    k_scales: Optional[jnp.ndarray] = None,  # [N, P, H_kv] f32 (int8 pool)
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Pure-JAX oracle for the multi-query chunk: gather every table
    page densely, mask causally per query (query ``i`` sits at absolute
    position ``fills - S + i`` and sees keys at positions ``<= fills -
    S + i``), softmax in f32 — mathematically identical to the dense
    slot-decode chunk attention in ``models/causal_lm.py`` (masked
    scores contribute exactly 0 mass). The chunk's own K/V must already
    be IN the pages (the caller writes before attending — in-chunk
    causality then falls out of the same position mask). Query rows
    with no valid key (``fills - S + i < 0``, incl. ``fills <= 0``
    empty slots) return zeros. Sentinel (out-of-range) table entries
    are clamped; whatever page they read is masked."""
    n, p_sz, hkv, d = k_pages.shape
    b, s, h, _ = q.shape
    mp = block_table.shape[1]
    g = h // hkv
    safe = jnp.minimum(block_table, n - 1)
    k = k_pages[safe].reshape(b, mp * p_sz, hkv, d)
    v = v_pages[safe].reshape(b, mp * p_sz, hkv, d)
    if k_scales is not None:
        ks = k_scales[safe].reshape(b, mp * p_sz, hkv)
        vs = v_scales[safe].reshape(b, mp * p_sz, hkv)
        k = (k.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
    q5 = q.reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    q_abs = fills[:, None] - s + jnp.arange(s)[None, :]          # [B, S]
    valid = (jnp.arange(mp * p_sz)[None, None, :]
             <= q_abs[:, :, None])                               # [B, S, K]
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, h, d)
    return jnp.where(q_abs[:, :, None, None] >= 0, out, 0).astype(q.dtype)


def paged_attention_reference(
    q: jnp.ndarray,            # [B, H, D]
    k_pages: jnp.ndarray,      # [N, P, H_kv, D] (dtype or int8)
    v_pages: jnp.ndarray,      # [N, P, H_kv, D]
    block_table: jnp.ndarray,  # [B, max_pages] int32; >= N = unallocated
    fills: jnp.ndarray,        # [B] int32 live tokens per slot
    k_scales: Optional[jnp.ndarray] = None,  # [N, P, H_kv] f32 (int8 pool)
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-query decode oracle: the ``S = 1`` case of the chunk
    reference (query at position ``fill - 1`` masks ``k_pos < fill``).
    Rows with ``fills <= 0`` return zeros."""
    return paged_attention_chunk_reference(
        q[:, None], k_pages, v_pages, block_table, fills,
        k_scales=k_scales, v_scales=v_scales)[:, 0]


def _paged_kernel(bt_ref, fills_ref, q_ref, kp_ref, vp_ref, *rest,
                  page_size: int, hkv: int, scale: float, quant: bool,
                  s_q: int):
    # Shapes: q [1, S, H, D] (S = s_q query tokens — 1 on the decode
    # path); kp/vp [1, P, Hkv, D] (the table-gathered page); with quant
    # also ks/vs [1, P, Hkv] f32; o [1, S, H, D]; scratch m/l
    # [H*S, 1] f32, acc [H*S, D] f32, rows laid out head-major:
    # row = h * S + s (a KV head's G query heads are adjacent, so its
    # group is the contiguous row block [hk*G*S, (hk+1)*G*S)).
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    fill = fills_ref[i]
    live_pages = (fill + page_size - 1) // page_size  # ceil

    @pl.when(j < live_pages)
    def _accumulate():
        q = q_ref[0]                                 # [S, H, D]
        s, h, d = q.shape
        g = h // hkv
        k = kp_ref[0]                                # [P, Hkv, D]
        v = vp_ref[0]
        if quant:
            k = (k.astype(jnp.float32) * ks_ref[0][..., None]).astype(q.dtype)
            v = (v.astype(jnp.float32) * vs_ref[0][..., None]).astype(q.dtype)
        # Per-KV-head 2D dots (Mosaic wants plain matmuls): each cached
        # KV head is read ONCE for its whole query group x chunk — the
        # GQA bandwidth win survives paging and chunking alike. The
        # group's query rows are stacked one head at a time: Mosaic
        # has no layout for collapsing [S, G, D] to [S*G, D] when G is
        # not a sublane-tile multiple (12 heads over 4 KV heads: G=3).
        def group_rows(hk):
            if s == 1:
                return q[0, hk * g:(hk + 1) * g]             # [G, D]
            return jnp.concatenate(
                [q[:, hk * g + gi, :] for gi in range(g)], axis=0)

        rows = []
        for hk in range(hkv):
            rows.append(jax.lax.dot_general(
                group_rows(hk), k[:, hk, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        scores = jnp.concatenate(rows, axis=0) * scale   # [H*S, P] f32
        # Causal mask per query row: row r holds query s_idx = r mod S
        # at absolute position fill - S + s_idx; it sees keys at
        # positions <= that. S = 1 degenerates to k_pos < fill (the
        # decode mask).
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (s * h, page_size), 1)
        q_abs = fill - 1
        if s > 1:
            r = jax.lax.broadcasted_iota(jnp.int32, (s * h, page_size), 0)
            q_abs = fill - s + r % s
        scores = jnp.where(k_pos <= q_abs, scores, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        outs = []
        for hk in range(hkv):
            outs.append(jax.lax.dot_general(
                p[hk * (s * g):(hk + 1) * (s * g)].astype(v.dtype),
                v[:, hk, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.concatenate(outs, axis=0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        m = m_ref[:]
        l = l_ref[:]
        valid = m > NEG_INF / 2      # query rows with >= 1 live key
        l = jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(valid, acc_ref[:] / l, 0.0)      # [H*S, D]
        s, h, d = o_ref.shape[1:]
        if s_q == 1:
            # head-major rows ARE head order when S = 1 — keep the
            # decode path free of the transpose below
            o_ref[0] = out.reshape(1, h, d).astype(o_ref.dtype)
        else:
            o_ref[0] = out.reshape(h, s, d).transpose(1, 0, 2).astype(
                o_ref.dtype)


def _paged_pallas(q, k_pages, v_pages, block_table, fills, k_scales,
                  v_scales, interpret: bool):
    # q arrives [B, S, H, D]; S is static (one compiled program per
    # chunk width — the engine uses exactly one width plus S=1 decode).
    n, p_sz, hkv, d = k_pages.shape
    b, s_q, h, _ = q.shape
    mp = block_table.shape[1]
    quant = k_scales is not None

    def page_map(i, j, bt, f):
        # Clamp dead iterations to the slot's LAST LIVE page: a
        # repeated block index skips the DMA, so pages past the fill
        # level are never re-fetched (ragged bandwidth). Sentinel
        # (unallocated) entries clamp into the pool; their compute is
        # pl.when-skipped anyway.
        last = jnp.maximum((f[i] - 1) // p_sz, 0)
        page = bt[i, jnp.minimum(j, last)]
        return jnp.minimum(page, n - 1), 0, 0, 0

    q_spec = pl.BlockSpec((1, s_q, h, d), lambda i, j, bt, f: (i, 0, 0, 0))
    page_spec = pl.BlockSpec((1, p_sz, hkv, d), page_map)
    in_specs = [q_spec, page_spec, page_spec]
    args = [q, k_pages, v_pages]
    if quant:
        def scale_map(i, j, bt, f):
            return page_map(i, j, bt, f)[:3]

        scale_spec = pl.BlockSpec((1, p_sz, hkv), scale_map)
        in_specs += [scale_spec, scale_spec]
        args += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, s_q, h, d),
                               lambda i, j, bt, f: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s_q * h, 1), jnp.float32),
            pltpu.VMEM((s_q * h, 1), jnp.float32),
            pltpu.VMEM((s_q * h, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=p_sz, hkv=hkv,
                               scale=d ** -0.5, quant=quant, s_q=s_q)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_q, h, d), q.dtype),
        interpret=interpret,
    )
    with kernel_scope("paged_attention_decode"):
        return call(block_table.astype(jnp.int32), fills.astype(jnp.int32), *args)


def paged_attention(
    q: jnp.ndarray,            # [B, H, D] one decode token per slot
    k_pages: jnp.ndarray,      # [N, P, H_kv, D]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32
    fills: jnp.ndarray,        # [B] int32 (valid tokens incl. the one
    #                            just written; 0 = empty slot -> zeros)
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode attention through a block table over a KV page pool.
    Returns ``[B, H, D]``. On non-TPU backends (``interpret=None``)
    falls back to the pure-JAX reference — the same dispatch contract
    as ``flash_attention``; ``interpret=True`` forces the kernel in
    interpret mode (tests / numerics oracle)."""
    return paged_attention_chunk(
        q[:, None], k_pages, v_pages, block_table, fills,
        k_scales=k_scales, v_scales=v_scales, interpret=interpret)[:, 0]


def paged_attention_chunk(
    q: jnp.ndarray,            # [B, S, H, D] chunk of query tokens
    k_pages: jnp.ndarray,      # [N, P, H_kv, D]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32
    fills: jnp.ndarray,        # [B] int32 live tokens INCLUDING the
    #                            chunk's S (query i sits at fill-S+i;
    #                            0 = empty slot -> zeros)
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Multi-query chunk attention through a block table (chunked
    prefill: the chunk's K/V are already in the pages; each query masks
    causally at its own absolute position). Returns ``[B, S, H, D]``.
    ``S`` is static — one compiled program per chunk width. Dispatch
    contract matches :func:`paged_attention`."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    h, hkv = q.shape[2], k_pages.shape[2]
    if h % hkv:
        raise ValueError(f"num_kv_heads {hkv} must divide num_heads {h}")
    if interpret is None:
        from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu

        if not on_tpu():
            return paged_attention_chunk_reference(
                q, k_pages, v_pages, block_table, fills,
                k_scales=k_scales, v_scales=v_scales)
        interpret = False
    return _paged_pallas(q, k_pages, v_pages, block_table, fills,
                         k_scales, v_scales, interpret)
