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
which ends because ``A^64 = 0``. Its factors are polynomials in ``A`` and
commute, so each is multiplied on from the left, where it shares its left
operand with the squaring that makes the next one: ``P [P | T]`` is the next
power and the update in one 64 x 64 x 128 product, and six such float32
products make ``T``, those of a block's chunks issued side by side. Backward
it is differentiated in closed form, ``dA = -T^T dT T^T``, two more
(:func:`_unit_lower_inverse`).

The per-head element-wise work around the recurrence is part of the chunk
(:func:`block_step`), done in float32 on the rows a step holds: the short
convolution over time of ``q``, ``k`` and ``v`` with the SiLU after it where
the caller gives its taps (:func:`_mixed`: a token's row and the ``taps - 1``
before it, which for a block's first rows are the last of the block before,
its halo; the result is not rounded before the norm), the L2 norm of
``q`` and ``k`` (``q`` also scaled by ``Dk^-1/2``), ``beta k`` and ``beta v``
(not rounded before use), the log-decay summed from each chunk's first row
(:func:`_running_sum` of each sub-block, a ``[SUB, SUB]`` triangle of ones
times it, exact to float32, plus the sum of the sub-blocks before it) and,
after the chunk, the output divided by the RMS of its head's columns. So the
caller hands over what its projections write, ``[B, S, H*D]`` with a head a
128-lane column slab, and never views it as ``[B, S, H, D]``.

:func:`kda` is one ``jax.custom_vjp``: the forward keeps the state at the start
of every block of ``BLOCK_CHUNKS`` chunks (float32) and the backward walks the
blocks from the last to the first, recomputes each block from its kept state
and pulls the cotangents back through it; the residuals are the operands
and those states. Walking back, the cotangent of a block's halo is added to
the last rows of the block walked next, and the taps' gradients sum up over
the blocks. On the TPU both walks are Pallas kernels
(``ops/pallas/kda.py``: the state rides in VMEM scratch across a sequential
grid axis); elsewhere the same algebra (:func:`block_step`) runs under
``lax.scan``. A per-token scan is the reference's
(``benchmark/reference/kimi_linear.py``), not the program's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu
from pyspark_tf_gke_tpu.ops.pallas.scope import name_results

CHUNK = 64
SUB = 16
BLOCK_CHUNKS = 4          # chunks a grid step (or a scan step) takes
L2_EPS = 1e-6             # under the root of q's and k's L2 norm
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


def _dot32(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _running_sum(g, reverse=False):
    """Row ``r`` of ``g [n, D]`` (float32) summed over the rows up to and with
    ``r`` (from ``r`` on when reversed, which is the transpose and so the
    backward). A triangle of ones times ``g`` on the MXU, exact to float32 in
    three bf16 passes: the ones are exact in bf16, and ``g`` is the sum of
    three bf16 parts of 8 bits each (``HIGHEST`` would split the ones too,
    and take six)."""
    bf16, n = jnp.bfloat16, g.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    ones = jnp.where((r <= col) if reverse else (r >= col), 1.0, 0.0)
    hi = g.astype(bf16)
    rest = g - hi.astype(g.dtype)
    mid = rest.astype(bf16)
    low = (rest - mid.astype(g.dtype)).astype(bf16)
    return sum(_dot(ones, part, _NN, bf16) for part in (hi, mid, low))


_running_sum.defvjp(lambda g, reverse: (_running_sum(g, reverse), None),
                    lambda reverse, _, ct: (_running_sum(ct, not reverse),))


@jax.custom_vjp
def _unit_lower_inverse(chunks):
    """``T = (I + a)^-1`` of every ``a [C, C]`` of the tuple ``chunks``, float32,
    each of which has to be strictly lower-triangular (what is on or above the
    diagonal is not masked here: :func:`_scores`'s ``where`` does it, and masks
    the cotangent through its own transpose). ``a^C = 0``, so ``T = (I - a)
    (I + a^2)(I + a^4)...`` ends with the factor of ``a^(C/2)``. Every factor
    is a polynomial in ``a`` and they commute, so a round's update is taken
    from the left, ``t + p t``, where the round's squaring ``p p`` has the
    same left operand: the two are one product ``p [p | t]``, ``[C, C] x
    [C, 2C]``, the MXU's 128 columns for ``C`` = 64. ``[p | t]`` stays one
    array from round to round (laying two side by side on the lanes every
    round costs more than the product saves): ``p`` is its left half, read in
    place, and ``t`` comes out of its right half once, at the end. That is
    ``a [a | I]``, four rounds and a last ``t + p t``: six products, all
    float32 at ``HIGHEST``, each a link of one dependent chain. The chunks'
    chains are issued side by side, link by link: a block's chunks do not wait
    for one another here as they do for the state, and the compiler keeps the
    order it is given. Backward it is no transpose of that series: ``dT = -T
    da T`` gives the cotangent ``-T^T ct T^T`` in two products of the one
    residual ``T``."""
    c = chunks[0].shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    right, eye = col >= c, jnp.where(col == r + c, 1.0, 0.0)                 # [0 | I]
    ws = [_dot32(a, jnp.concatenate([a, jnp.zeros_like(a)], axis=1) + eye)
          for a in chunks]                                                   # [a a | a]
    # [p | t]: p = a^span, t the series' powers below span
    ws, span = [jnp.where(right, eye - w, w) for w in ws], 2
    while 2 * span < c:
        ws = [_dot32(w[:, :c], w) + jnp.where(right, w, 0.0) for w in ws]    # [p p | t + p t]
        span *= 2
    # the last product's left half, p p, is not used: the MXU's columns are there either way
    return tuple((w + _dot32(w[:, :c], w))[:, c:] for w in ws)


def _unit_lower_inverse_fwd(chunks):
    ts = _unit_lower_inverse(chunks)
    return ts, ts


def _unit_lower_inverse_bwd(ts, cts):
    return (tuple(-_dot32(t, _dot32(ct, t, _NT), _TN) for t, ct in zip(ts, cts)),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _scores(q, k, kb, gc, mxu):
    """The half of a chunk that needs no state: ``(A, tril(Q K^T exp(G_r -
    G_i)))`` of the module docstring, ``[CHUNK, CHUNK]`` each, the first
    strictly lower-triangular. ``q, k, kb, gc``: lists of the chunk's
    ``SUB``-row sub-blocks ``[SUB, Dk]``, float32 (``kb = beta k``, ``gc`` the
    log-decay summed from the chunk's first row). Matmul operands are cast to
    ``mxu``."""
    n = len(q)
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
    return jnp.where(r > col, akk, 0.0), jnp.where(r >= col, aqk, 0.0)


def _chunk(t, aqk, q, k, kb, vb, gc, state, mxu):
    """The half of a chunk that waits for the state: ``t = (I + A)^-1`` and
    ``aqk`` from :func:`_scores`, the sub-blocks as there with ``vb = beta v
    [SUB, Dv]``; ``state [Dv, Dk]`` float32, the transpose of ``S``. Returns
    ``(o [CHUNK, Dv], next state)``. Matmul operands are cast to ``mxu``;
    sums, decays and the state are float32."""
    last = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0) == SUB - 1
    g_end = jnp.sum(jnp.where(last, gc[-1], 0.0), axis=0, keepdims=True)   # [1, Dk]
    g_all = jnp.concatenate(gc, axis=0)
    decay = jnp.exp(g_all)
    kbg = jnp.concatenate(kb, axis=0) * decay
    qg = jnp.concatenate(q, axis=0) * decay
    kd = jnp.concatenate(k, axis=0) * jnp.exp(g_end - g_all)
    u = _dot(t, jnp.concatenate(vb, axis=0) - _dot(kbg, state, _NT, mxu), _NN, mxu)
    o = _dot(qg, state, _NT, mxu) + _dot(aqk, u, _NN, mxu)
    return o, state * jnp.exp(g_end) + _dot(u, kd, _TN, mxu)


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


@jax.custom_vjp
def _sub_blocks(x):
    """``x [n SUB, D]`` as its ``SUB``-row sub-blocks; backward they are laid
    end to end again (a slice's own transpose would pad each to the whole)."""
    return tuple(x[i:i + SUB] for i in range(0, x.shape[0], SUB))


_sub_blocks.defvjp(lambda x: (_sub_blocks(x), None),
                   lambda _, ct: (jnp.concatenate(ct, axis=0),))


def _mixed(x, taps, halo):
    """``silu(conv(x))`` of one operand's sub-blocks ``x`` (``[SUB, D]`` each, in
    order), as sub-blocks: the depthwise causal convolution over time with
    ``taps`` (a row of weights ``[1, D]`` or ``[D]`` a tap, the last the current
    token's) on the block's rows laid end to end behind ``halo [SUB, D]``, the
    rows before the block, so that a token's row ``j`` back is a slice ``j``
    rows up. float32, not rounded before the L2 norm."""
    f32 = jnp.float32
    rows = jnp.concatenate([halo.astype(f32)] + [sub.astype(f32) for sub in x], axis=0)
    n = rows.shape[0] - SUB
    y = sum(taps[-1 - j] * rows[SUB - j:SUB - j + n] for j in range(len(taps)))
    return _sub_blocks(jax.nn.silu(y))


def block_step(subs, state, head, *, mxu, eps, conv=None):
    """A block of chunks of head ``head``: first of every chunk what needs no
    state (:func:`_scores`) and the triangles' inverses, all chunks' at once
    (:func:`_unit_lower_inverse`), then the chunks one after another through
    the state (:func:`_chunk`).
    ``subs = (q, k, v, g, beta)``, each the tuple of the block's ``SUB``-row
    sub-blocks in order, as :func:`kda` takes them: ``q, k [SUB, Dk]`` and
    ``v [SUB, Dv]`` not normalised, ``g [SUB, Dk]`` the log-decay a token,
    ``beta [SUB, H]`` with all the heads' step sizes (column ``head`` is
    picked here, so that its gradient comes out of the same ``vjp``).
    ``conv = (taps, halos)``, one of each for ``q``, ``k`` and ``v`` as
    :func:`_mixed` takes them, says that the three are a projection's output
    still to be convolved over time and passed through SiLU, which is then
    done here on the block's rows (``halos`` being the ``SUB`` rows before
    them, zeros at a row's first block).
    Returns ``(tuple of o [CHUNK, Dv] per chunk, each row over its RMS,
    state after the block)``. All of it float32 but the matmul operands."""
    f32 = jnp.float32
    q, k, v, g, beta = subs
    if conv is not None:
        q, k, v = (_mixed(x, *each) for x, each in zip((q, k, v), zip(*conv)))
    dk = q[0].shape[-1]
    mine = jax.lax.broadcasted_iota(jnp.int32, beta[0].shape, 1) == head
    per = CHUNK // SUB
    triangles, rest = [], []          # what needs no state, of every chunk first
    for c in range(len(q) // per):
        qs, ks, kb, vb, gc = [], [], [], [], []
        before = 0.0                  # the chunk's log-decay before this sub-block
        for i in range(c * per, (c + 1) * per):
            b_i = jnp.sum(jnp.where(mine, beta[i], 0.0), axis=1, keepdims=True)
            k_i = _l2_norm(k[i].astype(f32))
            qs.append(_l2_norm(q[i].astype(f32)) * dk ** -0.5)
            ks.append(k_i)
            kb.append(b_i * k_i)
            vb.append(b_i * v[i].astype(f32))
            gc.append(_running_sum(g[i]) + before)
            before = before + jnp.sum(g[i], axis=0, keepdims=True)
        akk, aqk = _scores(qs, ks, kb, gc, mxu)
        triangles.append(akk)
        rest.append((aqk, qs, ks, kb, vb, gc))
    outs = []
    for t, operands in zip(_unit_lower_inverse(tuple(triangles)), rest):
        o, state = _chunk(t, *operands, state, mxu)
        outs.append(o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps))
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


def _block_arrays(q, k, v, g, beta, state, head, taps, halos, mxu, eps):
    outs, state = block_step(tuple(_split(x) for x in (q, k, v, g, beta)), state, head,
                             mxu=mxu, eps=eps, conv=taps and (taps, halos))
    return jnp.concatenate(outs, axis=0), state


def _scan_step(mxu, eps):
    """:func:`_block_arrays` over rows and heads: ``q, k, v, g [B, H, rows, D]``,
    ``beta [B, rows, H]`` (every head reads the whole block), ``state
    [B, H, Dv, Dk]``, ``head [H]``, and for ``q``, ``k`` and ``v`` each ``taps
    [H, taps, D]`` and ``halos [B, H, SUB, D]``, or ``None`` twice."""
    step = functools.partial(_block_arrays, mxu=mxu, eps=eps)
    return jax.vmap(jax.vmap(step, in_axes=(0, 0, 0, 0, None, 0, 0, 0, 0)),
                    in_axes=(0, 0, 0, 0, 0, 0, None, None, 0))


def _to_blocks(x, heads, rows):
    b, s, hd = x.shape
    x = x.reshape(b, s // rows, rows, heads, hd // heads)
    return x.transpose(1, 0, 3, 2, 4)                       # [NB, B, H, rows, D]


def _from_blocks(x):
    nb, b, h, rows, d = x.shape
    return x.transpose(1, 0, 3, 2, 4).reshape(b, nb * rows, h * d)


def _scan_operands(q, k, v, g, beta, conv, heads, rows):
    """``(the five a block, the halos a block, the taps a head)``, the last two
    ``None`` without ``conv``. A block's halo is the last ``SUB`` rows of the
    block before it, zeros at the first; what is convolved is float32 from
    here on, so that a halo's cotangent meets its rows' before either is
    rounded."""
    b, s, _ = q.shape
    if conv is not None:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    five = tuple(_to_blocks(x, heads, rows) for x in (q, k, v, g)) + (
        beta.reshape(b, s // rows, rows, heads).transpose(1, 0, 2, 3),)  # [NB, B, rows, H]
    if conv is None:
        return five, None, None
    halos = tuple(jnp.concatenate([jnp.zeros_like(x[:1, ..., -SUB:, :]),
                                   x[:-1, ..., -SUB:, :]]) for x in five[:3])
    taps = tuple(w.reshape(w.shape[0], heads, -1).transpose(1, 0, 2) for w in conv)
    return five, halos, taps


def _fwd_scan(q, k, v, g, beta, conv, heads, eps, mxu):
    b, s, hd = q.shape
    rows, d, dv = block_rows(s), hd // heads, v.shape[-1] // heads
    step, head = _scan_step(mxu, eps), jnp.arange(heads)
    five, halos, taps = _scan_operands(q, k, v, g, beta, conv, heads, rows)

    def body(state, xs):
        *ins, halo = xs
        o, new = step(*ins, state, head, taps, halo)
        return new, (o, state)

    _, (o, states) = jax.lax.scan(body, jnp.zeros((b, heads, dv, d), jnp.float32),
                                  five + (halos,))
    return _from_blocks(o).astype(v.dtype), states.transpose(1, 2, 0, 3, 4)


def _bwd_scan(q, k, v, g, beta, conv, states, do, heads, eps, mxu):
    b, s, _ = q.shape
    rows = block_rows(s)
    step, head = _scan_step(mxu, eps), jnp.arange(heads)
    five, halos, taps = _scan_operands(q, k, v, g, beta, conv, heads, rows)

    def body(carry, xs):
        # beside the state's cotangent, the taps' summed over the blocks walked
        # and the halo's of the block walked last: that of this block's last rows
        dstate, dtaps, late = carry
        *ins, halo, state, g_o = xs
        _, pull = jax.vjp(lambda ins, state, taps, halo: step(*ins, state, head, taps, halo),
                          ins, state, taps, halo)
        d_ins, d_prev, d_taps, d_halo = pull((g_o.astype(jnp.float32), dstate))
        if conv is not None:
            d_ins = [d.at[..., -SUB:, :].add(ct) for d, ct in zip(d_ins, late)] + d_ins[3:]
        return (d_prev, jax.tree.map(jnp.add, dtaps, d_taps), d_halo), tuple(d_ins)

    xs = five + (halos, states.transpose(2, 0, 1, 3, 4), _to_blocks(do, heads, rows))
    zeros = functools.partial(jax.tree.map, jnp.zeros_like)       # ``None`` stays ``None``
    (_, dtaps, _), (*grads, dbeta) = jax.lax.scan(
        body, (jnp.zeros_like(states[:, :, 0]), zeros(taps),
               zeros(jax.tree.map(lambda h: h[0], halos))), xs, reverse=True)
    return tuple(_from_blocks(d).astype(x.dtype) for d, x in zip(grads, (q, k, v, g))) + (
        dbeta.transpose(1, 0, 2, 3).reshape(b, s, heads),
        conv and tuple(d.transpose(1, 0, 2).reshape(w.shape) for d, w in zip(dtaps, conv)))


# -- one custom_vjp over either walk -----------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _kda_core(q, k, v, g, beta, conv, heads, eps, mxu, pallas, interpret):
    return _core_fwd(q, k, v, g, beta, conv, heads, eps, mxu, pallas, interpret)[0]


def _core_fwd(q, k, v, g, beta, conv, heads, eps, mxu, pallas, interpret):
    """``o`` and ``states`` are named for a remat policy (``scope.KERNEL_RESULTS``):
    a block rematted with it keeps them, and its backward runs no forward again."""
    if pallas:
        from pyspark_tf_gke_tpu.ops.pallas import kda as kernels

        o, states = kernels.forward(q, k, v, g, beta, conv, heads=heads, eps=eps, mxu=mxu,
                                    interpret=interpret)
    else:
        o, states = _fwd_scan(q, k, v, g, beta, conv, heads, eps, mxu)
    o, states = name_results("kda", o, states)
    return o, (q, k, v, g, beta, conv, states)


def _core_bwd(heads, eps, mxu, pallas, interpret, residuals, do):
    if pallas:
        from pyspark_tf_gke_tpu.ops.pallas import kda as kernels

        return kernels.backward(*residuals, do, heads=heads, eps=eps, mxu=mxu,
                                interpret=interpret)
    return _bwd_scan(*residuals, do, heads, eps, mxu)


_kda_core.defvjp(_core_fwd, _core_bwd)


def kda(q: jnp.ndarray,                # [B, S, H*Dk] not normalised
        k: jnp.ndarray,                # [B, S, H*Dk] not normalised
        v: jnp.ndarray,                # [B, S, H*Dv]
        g: jnp.ndarray,                # [B, S, H*Dk] log-decay a token, <= 0
        beta: jnp.ndarray,             # [B, S, H] in (0, 1)
        *, heads: int, eps: float, conv=None, pallas: Optional[bool] = None,
        interpret: bool = False) -> jnp.ndarray:
    """Chunked KDA, forward and backward (module docstring), of operands as the
    projections write them: head ``h`` is columns ``[h D, (h + 1) D)``.
    With ``conv = (wq, wk, wv)``, each ``[taps, H*D]`` float32, ``q``, ``k`` and
    ``v`` are the projections' own outputs and each is first convolved over
    time with its taps (depthwise and causal, the last tap on the current
    token, zeros before a row's first) and passed through SiLU, in float32;
    without it they come already mixed.
    Per head ``q`` and ``k`` are L2-normalised (``q`` also times ``Dk^-1/2``)
    and the recurrence's output is divided by the RMS of its ``Dv`` columns
    (``eps`` under the root); the caller's norm scale and gate come after.
    Returns ``o [B, S, H*Dv]`` in ``v``'s dtype. The state starts at 0 in
    every row. ``pallas=None`` takes the kernels on the TPU and ``lax.scan``
    elsewhere; ``interpret`` runs the kernels in the Pallas interpreter
    (tests). The chunk's matmuls take their operands in ``q``'s dtype; the
    convolution, the norms' statistics, ``beta``, decays, their sums and the
    state are float32."""
    b, s, wide = q.shape
    if wide % heads or v.shape[-1] % heads or beta.shape[-1] != heads:
        raise ValueError(
            f"kda: {heads} heads do not divide q's {wide} and v's {v.shape[-1]} "
            f"columns, or are not beta's {beta.shape[-1]}")
    block_rows(s)                                           # refuses a ragged sequence
    if conv is not None:
        conv = tuple(w.astype(jnp.float32) for w in conv)
        if any(w.shape != (conv[0].shape[0], x.shape[-1]) for w, x in zip(conv, (q, k, v))) or (
                conv[0].shape[0] > SUB):
            raise ValueError(
                f"kda: taps {[w.shape for w in conv]} are not [taps, columns] of q, k and v "
                f"with at most {SUB} taps (a halo is one sub-block)")
    if pallas is None:
        pallas = on_tpu() or interpret
    return _kda_core(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32), conv,
                     int(heads), float(eps), jnp.dtype(q.dtype), bool(pallas),
                     bool(interpret))
