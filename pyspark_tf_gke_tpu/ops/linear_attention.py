"""Kimi Delta Attention (KDA): a gated delta-rule linear attention, chunked.

Per head, with keys and queries of width ``Dk`` and values of width ``Dv``,
a log-decay ``g_t <= 0`` per key channel and a step size ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                   S in R^{Dk x Dv}, S_0 = 0

One token at a time that is ``S`` steps of rank-1 updates. The chunked form
takes ``CHUNK`` = 64 tokens at once. With ``G_r`` the log-decay summed from
the chunk's first token to ``r``, ``u_r = beta_r (v_r - S_{r-1}^T exp(g_r) k_r)``
the value each token really writes, and ``S`` the state at the chunk's start:

    A_ri   = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])      (i < r)
    U      = (I + A)^-1 (beta V - (beta K exp(G)) S)
    O      = (Q exp(G)) S + tril(Q K^T exp(G_r - G_i)) U          (i <= r)
    S_next = diag(exp(G_C)) S + (K exp(G_C - G))^T U

so a chunk is a dozen small matmuls and only the ``[Dk, Dv]`` state passes
from chunk to chunk. ``exp(G_r - G_i)`` is never factored over a whole chunk
(``exp(-G_i)`` overflows float32 once the summed decay passes -88, and 64
tokens reach -100 here): rows are taken in sub-blocks of ``SUB`` = 16 and each
sub-block's decays relative to its own first row, so every exponent is at most
15 tokens' worth (up to ``_EXP_CAP`` = 80 is exact; a mean decay past 5.3 a
token a channel over 15 tokens is not supported). ``(I + A)^-1`` of the
strictly lower-triangular ``A`` is the product ``(I - A)(I + A^2)(I + A^4)...``,
which ends because ``A^64 = 0``; it is computed in float32.

:func:`kda` is one ``jax.custom_vjp``: the forward keeps the state at the start
of every block of ``BLOCK_CHUNKS`` chunks (float32) and the backward walks the
blocks from the last to the first, recomputes each block from its kept state
and pulls the cotangents back through it. On the TPU both walks are Pallas
kernels (``ops/pallas/kda.py``: the state rides in VMEM scratch across a
sequential grid axis); elsewhere the same algebra (:func:`block_step`) runs
under ``lax.scan``. A per-token scan is the reference's
(``benchmark/reference/kimi_linear.py``), not the program's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu

CHUNK = 64
SUB = 16
BLOCK_CHUNKS = 4          # chunks a grid step (or a scan step) takes
_EXP_CAP = 80.0

_NT = (((1,), (1,)), ((), ()))   # a [m, d] · b [n, d]^T -> [m, n]
_NN = (((1,), (0,)), ((), ()))   # a [m, n] · b [n, d]   -> [m, d]
_TN = (((0,), (0,)), ((), ()))   # a [n, m]^T · b [n, d] -> [m, d]


def _dot(a, b, dims, mxu):
    # the precision is said, not taken from ``jax_default_matmul_precision``:
    # Mosaic refuses bf16 operands at "highest"
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), dims,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _dot32(a, b):
    return jax.lax.dot_general(a, b, _NN, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a [C, C]``, float32."""
    c = a.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = jnp.where(r == col, 1.0, 0.0) - a        # holds the powers below 2
    p, span = a, 2
    while span < c:
        p = _dot32(p, p)                         # a^span
        t = t + _dot32(t, p)
        span *= 2
    return t


def _chunk(q, k, kb, vb, gc, state, mxu):
    """One chunk. ``q, k, kb, vb, gc``: lists of the chunk's ``SUB``-row
    sub-blocks ``[SUB, D]`` (``kb = beta k``, ``vb = beta v``, ``gc`` the
    log-decay summed from the chunk's first row, float32); ``state [Dv, Dk]``
    float32, the transpose of ``S``. Returns ``(o [CHUNK, Dv], next state)``.
    Matmul operands are cast to ``mxu``; sums, decays and the state are
    float32."""
    f32 = jnp.float32
    q, k, kb, vb = ([x.astype(f32) for x in xs] for xs in (q, k, kb, vb))
    n = len(q)
    last = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0) == SUB - 1
    g_end = jnp.sum(jnp.where(last, gc[-1], 0.0), axis=0, keepdims=True)   # [1, Dk]
    zeros = jnp.zeros_like(k[0])
    akk, aqk = [], []
    for a in range(n):
        # decays of sub-block a's rows and of the columns up to it, both
        # relative to the sub-block's first row: the reference cancels in
        # every product, so no gradient flows through it
        ref = jax.lax.stop_gradient(gc[a][:1])
        lift = jnp.exp(gc[a] - ref)
        cols = jnp.concatenate(
            [k[b] * jnp.exp(jnp.minimum(ref - gc[b], _EXP_CAP)) if b <= a else zeros
             for b in range(n)], axis=0)                                    # [C, Dk]
        akk.append(_dot(kb[a] * lift, cols, _NT, mxu))
        aqk.append(_dot(q[a] * lift, cols, _NT, mxu))
    akk, aqk = jnp.concatenate(akk, axis=0), jnp.concatenate(aqk, axis=0)   # [C, C]
    c = akk.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = _unit_lower_inverse(jnp.where(r > col, akk, 0.0))
    aqk = jnp.where(r >= col, aqk, 0.0)

    g_all = jnp.concatenate(gc, axis=0)
    decay = jnp.exp(g_all)
    kbg = jnp.concatenate(kb, axis=0) * decay
    qg = jnp.concatenate(q, axis=0) * decay
    kd = jnp.concatenate(k, axis=0) * jnp.exp(g_end - g_all)
    u = _dot(t, jnp.concatenate(vb, axis=0) - _dot(kbg, state, _NT, mxu), _NN, mxu)
    o = _dot(qg, state, _NT, mxu) + _dot(aqk, u, _NN, mxu)
    return o, state * jnp.exp(g_end) + _dot(u, kd, _TN, mxu)


def block_step(subs, state, mxu):
    """A block of chunks, one after another. ``subs = (q, k, kb, vb, gc)``,
    each the tuple of the block's ``SUB``-row sub-blocks in order. Returns
    ``(tuple of o [CHUNK, Dv] per chunk, state after the block)``."""
    per = CHUNK // SUB
    outs = []
    for c in range(len(subs[0]) // per):
        o, state = _chunk(*(list(x[c * per:(c + 1) * per]) for x in subs),
                          state, mxu)
        outs.append(o)
    return tuple(outs), state


def block_rows(s: int) -> int:
    """Rows a block takes: the most chunks up to ``BLOCK_CHUNKS`` that divide
    the sequence."""
    if s % CHUNK:
        raise ValueError(
            f"kda: sequence length {s} is not a multiple of the chunk ({CHUNK}); "
            "pad the rows")
    chunks = s // CHUNK
    return CHUNK * max(c for c in range(1, BLOCK_CHUNKS + 1) if chunks % c == 0)


# -- the same walk in jax.numpy (off the TPU) ------------------------------------

def _split(x):
    return tuple(x[i:i + SUB] for i in range(0, x.shape[0], SUB))


def _block_arrays(q, k, kb, vb, gc, state, mxu):
    outs, state = block_step(tuple(_split(x) for x in (q, k, kb, vb, gc)), state, mxu)
    return jnp.concatenate(outs, axis=0), state


def _to_blocks(x, heads, rows):
    b, s, hd = x.shape
    x = x.reshape(b, s // rows, rows, heads, hd // heads)
    return x.transpose(1, 0, 3, 2, 4)                       # [NB, B, H, rows, D]


def _from_blocks(x):
    nb, b, h, rows, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(b, nb * rows, h * d)


def _fwd_scan(q, k, kb, vb, gc, heads, mxu):
    b, s, hd = q.shape
    rows, d = block_rows(s), hd // heads
    dv = vb.shape[-1] // heads
    step = jax.vmap(jax.vmap(functools.partial(_block_arrays, mxu=mxu)))

    def body(state, xs):
        o, new = step(*xs, state)
        return new, (o, state)

    xs = tuple(_to_blocks(x, heads, rows) for x in (q, k, kb, vb, gc))
    _, (o, states) = jax.lax.scan(body, jnp.zeros((b, heads, dv, d), jnp.float32), xs)
    return _from_blocks(o).astype(vb.dtype), states.transpose(1, 2, 0, 3, 4)


def _bwd_scan(q, k, kb, vb, gc, states, do, heads, mxu):
    rows = block_rows(q.shape[1])
    step = jax.vmap(jax.vmap(functools.partial(_block_arrays, mxu=mxu)))

    def body(dstate, xs):
        *ins, state, g_o = xs
        _, pull = jax.vjp(step, *ins, state)
        *d_ins, d_prev = pull((g_o.astype(jnp.float32), dstate))
        return d_prev, tuple(d_ins)

    ins = tuple(_to_blocks(x, heads, rows) for x in (q, k, kb, vb, gc))
    xs = ins + (states.transpose(2, 0, 1, 3, 4), _to_blocks(do, heads, rows))
    _, grads = jax.lax.scan(body, jnp.zeros_like(states[:, :, 0]), xs, reverse=True)
    return tuple(_from_blocks(g).astype(x.dtype)
                 for g, x in zip(grads, (q, k, kb, vb, gc)))


# -- one custom_vjp over either walk -----------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _kda_core(q, k, kb, vb, gc, heads, mxu, pallas, interpret):
    return _core_fwd(q, k, kb, vb, gc, heads, mxu, pallas, interpret)[0]


def _core_fwd(q, k, kb, vb, gc, heads, mxu, pallas, interpret):
    if pallas:
        from pyspark_tf_gke_tpu.ops.pallas import kda as kernels

        o, states = kernels.forward(q, k, kb, vb, gc, heads=heads, mxu=mxu,
                                    interpret=interpret)
    else:
        o, states = _fwd_scan(q, k, kb, vb, gc, heads, mxu)
    return o, (q, k, kb, vb, gc, states)


def _core_bwd(heads, mxu, pallas, interpret, residuals, do):
    q, k, kb, vb, gc, states = residuals
    if pallas:
        from pyspark_tf_gke_tpu.ops.pallas import kda as kernels

        return kernels.backward(q, k, kb, vb, gc, states, do, heads=heads,
                                mxu=mxu, interpret=interpret)
    return _bwd_scan(q, k, kb, vb, gc, states, do, heads, mxu)


_kda_core.defvjp(_core_fwd, _core_bwd)


def kda(q: jnp.ndarray,                # [B, S, H, Dk], normalised and scaled
        k: jnp.ndarray,                # [B, S, H, Dk], normalised
        v: jnp.ndarray,                # [B, S, H, Dv]
        g: jnp.ndarray,                # [B, S, H, Dk] log-decay <= 0
        beta: jnp.ndarray,             # [B, S, H] in (0, 1)
        *, pallas: Optional[bool] = None,
        interpret: bool = False) -> jnp.ndarray:
    """Chunked KDA, forward and backward (module docstring). Returns
    ``o [B, S, H, Dv]`` in ``v``'s dtype. The state starts at 0 in every row.
    ``pallas=None`` takes the kernels on the TPU and ``lax.scan`` elsewhere;
    ``interpret`` runs the kernels in the Pallas interpreter (tests).
    The chunk's matmuls take their operands in ``q``'s dtype; decays,
    ``beta`` and the state are float32."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    block_rows(s)                                           # refuses a ragged sequence
    if pallas is None:
        pallas = on_tpu() or interpret
    mxu = jnp.dtype(q.dtype)
    beta = beta.astype(jnp.float32)[..., None]
    kb = (k.astype(jnp.float32) * beta).astype(k.dtype)
    vb = (v.astype(jnp.float32) * beta).astype(v.dtype)
    gc = jnp.cumsum(g.astype(jnp.float32).reshape(b, s // CHUNK, CHUNK, h * dk),
                    axis=2).reshape(b, s, h * dk)
    o = _kda_core(q.reshape(b, s, h * dk), k.reshape(b, s, h * dk),
                  kb.reshape(b, s, h * dk), vb.reshape(b, s, h * dv), gc,
                  h, mxu, bool(pallas), bool(interpret))
    return o.reshape(b, s, h, dv)
