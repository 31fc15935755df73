"""Flash attention as Pallas TPU kernels, forward and backward.

The S×S score matrix never touches HBM: each grid step owns one Q block in
VMEM, walks the K/V axis with the online-softmax recurrence (running max
``m``, normalizer ``l``, accumulator in f32), and writes one O block.
Q·Kᵀ and P·V hit the MXU with f32 accumulation.

Layout: inputs are ``[BH, S, D]`` (batch×heads collapsed — each grid row
is independent); ``v`` (and with it ``o``, ``dO`` and ``dV``) may be narrower
or wider than ``q`` and ``k`` (MLA: keys of 192, values of 128), and the
scale is that of the keys' width. An optional additive bias ``[BH, 1, S]`` implements
padding masks (0 for keep, NEG_INF for drop); without a ``kv_mask`` there
is no bias operand at all. Optional ``[BH, 1, S]`` segment ids confine
attention within a packed sequence.

**The schedule** (``_schedule``, a pure function of ``(S, block_q, block_k,
causal, window)``; the three kernels take every loop bound from it through
``_sweep``). The grid block is ``block_q`` rows (``block_k`` keys in dK/dV).
Seen from a grid block, the other axis falls into three kinds of *tile*:

* *skipped*: wholly above the diagonal. Never computed.
* *full*: wholly on or under it. No iota, no compare, no select. The whole
  block takes them ``walk`` positions a *full step*.
* *diagonal*: crossed by the diagonal. These lie in the block's *diagonal
  square* (the block against its own positions on the other axis), which
  is cut into ``tile``-sided tiles and taken one *strip* at a time: a row
  sub-block of ``tile`` rows against the keys of the square up to its own
  tile (forward, dQ), or a key tile against the rows of the square from its
  own tile on (dK/dV). Only the one diagonal tile of a strip passes
  through the mask, a triangle that is the same for every diagonal tile and
  is built once a grid step; the strip's other tiles are full, and the
  tiles above the diagonal inside the square are skipped.

With a **window** (``window`` keys a row: its own position and the
``window - 1`` before it; causal only) the walk starts at the window's far
edge instead of the sequence's start::

    keys ->      far edge                     diagonal
    . . . . . | % # # # | # # # # | # # # # | % . . . |      . skipped
    . . . . . | . % # # | # # # # | # # # # | # % . . |      # full
    . . . . . | . . % # | # # # # | # # # # | # # % . |      % masked tile
    . . . . . | . . . % | # # # # | # # # # | # # # % |
      skipped   edge        full steps          diagonal
                strips                          strips

Tiles wholly behind the window are skipped like those above the diagonal,
tiles wholly inside it are full, and where the window is a multiple of the
grid block the far edge gets the mirror of the diagonal's treatment: the
*edge square* (the block against the positions ``window`` before its own, or
after them in dK/dV) is taken in strips of which only the edge tile is
masked, by the complement of the diagonal's triangle. A grid block whose
edge square lies off the sequence (the first ``window / block`` blocks of
rows, the last ones of keys) has none: one ``lax.cond`` a grid step. A
window that is no multiple of the block (``_Schedule.mirrored`` false)
still skips what lies wholly behind it, and passes every tile it computes
through a mask of positions (``band``): right for any window, and slower.
That path is kept for windows shorter than a block (windows of 128 keys are
published, a quarter of the default block of 512); no cell runs it yet: it
has run in interpret mode and compiled for a v5e (the tests), not on a chip.
A window of the sequence's length or more is no window.

Not causal, every tile is full and there is no square. Where a block has
no 128-multiple divisor (the tests' 16- and 32-wide blocks, a sequence
taken as one block) the tile is the whole block: one masked strip.

Strips are static Python loops, and the full steps are unrolled where
their number is static (not causal) and at most ``UNROLL``. On the v5e a
step inside a dynamic ``fori_loop`` costs about twice what the same step
costs in straight-line code, where the scheduler overlaps one strip's
matmuls with the next one's vector work (PERF.md §6, PR 26); hence a
sequence of up to ``WHOLE_SEQ`` positions is one grid block: a head is one
grid step with no dynamic loop at all. Longer sequences keep the 512-row
grid block, and causal ones walk their full tiles in a ``fori_loop`` whose
bounds depend on the grid block. Inside a ``mirrored`` window every block
with an edge square takes the same number of full steps, ``window / walk -
block / walk`` from a start that moves with the block: they stand unrolled
(up to ``UNROLL``) in the ``lax.cond`` branch that holds the edge strips,
and the loop is left to the first blocks of rows (the last of keys), whose
edge square lies off the sequence (``_Schedule.looped_steps``).

Backward: ``jax.custom_vjp`` with **Pallas backward kernels** — the
forward additionally emits the per-row logsumexp ``L = m + log(l)``, and
two kernels recompute P from (q, k, bias, L): one walks keys to produce
dQ, the other walks rows to produce dK/dV (the standard flash-attention
backward split). The dK/dV kernel computes the *transposed* tile (keys on
sublanes, rows on lanes): ``L`` and ``delta`` are then read as the row
vectors they are stored as, and every matmul contracts over a last or a
first-of-rhs dimension. No S×S tensor ever exists in either pass;
residuals are (q, k, v, bias, L, D=rowsum(dO·O)).

The public entry ``flash_attention`` takes ``[B, S, H, D]`` like
``ops.attention.dot_product_attention`` and reshapes. Falls back to the
dense path on non-TPU backends unless ``interpret=True`` (used in tests).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pyspark_tf_gke_tpu.ops.pallas.common import on_tpu, pick_block
from pyspark_tf_gke_tpu.ops.pallas.scope import caller_scope, kernel_scope, name_results

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# Settled on the v5e (PERF.md §6, PR 26): the longest sequence taken as one
# grid block, the side of a tile of the diagonal square, the width of a full
# step, and the most full steps that are unrolled (each holds its tiles in
# VMEM: 16 of them at head_dim 128 pass the 16 MiB a kernel may take).
WHOLE_SEQ = 1024
# whole-sequence operands a launch may hold under the default VMEM limit, and
# the room the tiles and the blocks of a grid step take besides (``_vmem``)
VMEM_ASK = 8 * 2 ** 20
VMEM_TILES = 16 * 2 ** 20
TILE = 128
WALK = 512
UNROLL = 8

_NT = (((1,), (1,)), ((), ()))   # a [m, D] · b [n, D]ᵀ -> [m, n]
_NN = (((1,), (0,)), ((), ()))   # a [m, n] · b [n, D]  -> [m, D]


class _Schedule(NamedTuple):
    """What one head's kernels compute of the [S, S] scores (module
    docstring). Positions are along the grid axis (rows of q in forward and
    dQ, keys in dK/dV) and the walk axis (the other one)."""

    s: int
    block: int        # grid block
    walk: int         # width of one step over full tiles
    tile: int         # side of a tile; a sub-block's extent along the grid axis
    causal: bool
    walks_rows: bool  # dK/dV: the visible side of the walk axis is the far one
    window: Optional[int] = None  # keys a row sees, its own among them; under s

    @property
    def mirrored(self) -> bool:
        """The window's far edge is taken as the diagonal is, in strips."""
        return self.window is not None and self.window % self.block == 0

    def full_steps(self, i):
        """Half-open range of the walk steps grid block ``i`` (a Python or a
        traced integer) takes whole: wholly visible, or with a window that is
        not ``mirrored`` not wholly behind it."""
        per_block, steps = self.block // self.walk, self.s // self.walk
        if not self.causal:
            return 0, steps
        if self.block == self.s:
            return 0, 0
        lo, hi = ((i + 1) * per_block, steps) if self.walks_rows else (0, i * per_block)
        if self.window is None:
            return lo, hi
        if self.mirrored:
            reach = self.window // self.walk
            if self.walks_rows:
                return lo, jnp.minimum(hi, i * per_block + reach)
            return jnp.maximum(lo, (i + 1) * per_block - reach), hi
        if self.walks_rows:       # the last row that sees the block's last key
            return lo, jnp.minimum(
                hi, (i * self.block + self.block + self.window - 2) // self.walk + 1)
        return jnp.maximum(i * self.block - self.window + 1, 0) // self.walk, hi

    def sub_blocks(self):
        """``(first, size)`` of the block's sub-blocks along the grid axis."""
        return [(i * self.tile, self.tile)
                for i in range(self.block // self.tile)]

    def strips(self):
        """The diagonal square's strips, one a sub-block and in their order,
        as ``(start, width)`` along the walk axis from the square's corner.
        The diagonal tile is the strip's last (its first, where the kernel
        walks rows)."""
        if not self.causal:
            return []
        if self.walks_rows:
            return [(first, self.block - first)
                    for first, _ in self.sub_blocks()]
        return [(0, first + size) for first, size in self.sub_blocks()]

    def edge_strips(self):
        """The edge square's strips (``mirrored``), as :meth:`strips` gives the
        diagonal square's, from the edge square's corner: the mirror image,
        the edge tile the strip's first (its last, where the kernel walks
        rows)."""
        if self.walks_rows:
            return [(0, first + size) for first, size in self.sub_blocks()]
        return [(first, self.block - first) for first, _ in self.sub_blocks()]

    def has_edge(self, i):
        """Whether grid block ``i``'s edge square lies on the sequence."""
        reach = self.window // self.block
        return i < self.s // self.block - reach if self.walks_rows else i >= reach

    def edge_steps(self) -> int:
        """The full steps of every block with an edge square (``mirrored``):
        the window's steps less the block's own, from a start that moves with
        the block. Checked against :meth:`full_steps` for each such block."""
        n = self.window // self.walk - self.block // self.walk
        with jax.ensure_compile_time_eval():   # on the host, inside a kernel's trace too
            for i in range(self.s // self.block):
                lo, hi = self.full_steps(i)
                if self.has_edge(i) and int(hi) - int(lo) != n:
                    raise ValueError(f"block {i} of {self} takes {int(hi) - int(lo)} "
                                     f"full steps beside its edge square, not {n}")
        return n

    def looped_steps(self):
        """(unrolled, looped) full steps a head: those :func:`_sweep` lays out
        as straight-line code beside the edge strips, and those it walks in a
        ``fori_loop`` whose bounds are traced."""
        blocks = range(self.s // self.block)
        total = sum(int(hi) - int(lo) for lo, hi in map(self.full_steps, blocks))
        if not (self.mirrored and self.edge_steps() <= UNROLL):
            return 0, total
        unrolled = self.edge_steps() * sum(bool(self.has_edge(i)) for i in blocks)
        return unrolled, total - unrolled

    def counts(self):
        """(computed, through the mask, thrown away) score elements a head."""
        if not self.causal:
            return self.s * self.s, 0, 0
        blocks, t = self.s // self.block, self.tile
        diagonal_tiles = blocks * len(self.sub_blocks())
        square = t * sum(width for _, width in self.strips())
        if self.window is None:
            full = self.block ** 2 * blocks * (blocks - 1) // 2
            return (full + blocks * square, diagonal_tiles * t * t,
                    diagonal_tiles * t * (t - 1) // 2)
        steps = [int(hi) - int(lo) for lo, hi in map(self.full_steps, range(blocks))]
        full = self.block * self.walk * sum(n for n in steps if n > 0)
        if self.mirrored:
            edges = sum(bool(self.has_edge(i)) for i in range(blocks))
            masked = (diagonal_tiles + edges * len(self.sub_blocks())) * t * t
            # an edge tile keeps what the diagonal tile throws away
            return (full + (blocks + edges) * square, masked,
                    diagonal_tiles * t * (t - 1) // 2
                    + edges * len(self.sub_blocks()) * t * (t + 1) // 2)
        computed = full + blocks * square
        rows = np.arange(self.s)
        visible = int(np.sum(np.minimum(rows + 1, self.window)))
        return computed, computed, computed - visible


def _schedule(s: int, block_q: int, block_k: int, causal: bool,
              walks_rows: bool = False, window: Optional[int] = None) -> _Schedule:
    block, other = (block_k, block_q) if walks_rows else (block_q, block_k)
    walk = pick_block(math.gcd(block, other) if causal else other, WALK, 128)
    return _Schedule(s, block, walk, pick_block(block, TILE, 128), causal,
                     walks_rows, window)


def _sweep(sched: _Schedule, i, carry, piece):
    """Everything grid block ``i`` computes: ``piece(carry, sub, start,
    width, mask, band)`` updates the carry (a tuple of arrays whose first axis
    is the block's) of the block's positions ``sub`` from ``width`` positions
    of the walk axis at ``start``. The full steps take the whole block; then
    each strip of the edge square (a ``mirrored`` window's) and of the
    diagonal square takes its sub-block's slice. A block with an edge square
    takes its full steps, a static count, unrolled in the same ``lax.cond``
    branch as the edge strips; one without walks them in the loop. ``mask``
    is ``None`` or the strip's one masked tile, ``(triangle, whether it is
    the strip's last)``; ``band`` is ``None`` or, for a window that is not
    ``mirrored``, what :func:`_scores` masks every tile by."""
    whole = slice(0, sched.block)
    banded = sched.window is not None and not sched.mirrored

    def band(sub, start):
        # row - key < window, with ``d`` rows and ``e`` keys past the piece's
        # first: d - e < window - (first row - first key)
        if not banded:
            return None
        ahead = i * sched.block + sub.start - start
        return sched.window + ahead if sched.walks_rows else sched.window - ahead

    def step(j, carry):
        start = pl.multiple_of(j * sched.walk, sched.walk)
        return piece(carry, whole, start, sched.walk, None, band(whole, start))

    lo, hi = sched.full_steps(i)
    # a few steps with static bounds unroll into one basic block, where the
    # scheduler overlaps a step's matmuls with its neighbour's vector work
    few = isinstance(lo, int) and isinstance(hi, int) and hi - lo <= UNROLL

    def walk(carry, n=None):
        # the full steps: ``n`` of them from ``lo`` unrolled, or the loop
        if n is None:
            return jax.lax.fori_loop(lo, hi, step, carry, unroll=few or None)
        for j in range(n):
            carry = step(lo + j, carry)
        return carry

    if not sched.mirrored:
        carry = walk(carry)
    if not sched.causal:
        return carry
    tri = _triangle(sched.tile, keys_first=sched.walks_rows)
    corner = pl.multiple_of(i * sched.block, sched.block)

    def square(carry, origin, strips, mask):
        done = []
        for (first, size), (start, width) in zip(sched.sub_blocks(), strips):
            sub = slice(first, first + size)
            mine, at = tuple(c[sub] for c in carry), origin + start
            done.append(piece(mine, sub, at, width, mask, band(sub, at)))
        return tuple(jnp.concatenate(c) for c in zip(*done))

    if sched.mirrored:
        reach = sched.window if sched.walks_rows else -sched.window
        n = sched.edge_steps()
        carry = jax.lax.cond(
            sched.has_edge(i),
            lambda c: square(walk(c, n if n <= UNROLL else None), corner + reach,
                             sched.edge_strips(), (~tri, sched.walks_rows)),
            walk, carry)
    return square(carry, corner, sched.strips(), (tri, not sched.walks_rows))


def _scores(a, b, scale, bias, seg_a, seg_b, mask, band=None, keys_first=False):
    """f32 scores of ``a`` [m, D] against ``b`` [n, D], masked: [m, n].
    ``bias`` and the segment ids broadcast against [m, n]; ``mask`` is
    ``(tri [t, t], last)``: ``tri`` masks one tile alone, the last t columns
    or the first. ``band`` masks the whole piece by position: kept where
    the row is under ``band`` positions past the key, counted from the
    piece's first row and key (``a`` holds the keys where ``keys_first``)."""
    s = jax.lax.dot_general(a, b, _NT,
                            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if seg_a is not None:
        s = jnp.where(seg_a == seg_b, s, NEG_INF)
    if mask is not None:
        tri, last = mask
        t = tri.shape[0]
        if s.shape[1] == t:
            s = jnp.where(tri, s, NEG_INF)
        elif last:
            s = jnp.concatenate(
                [s[:, :-t], jnp.where(tri, s[:, -t:], NEG_INF)], axis=1)
        else:
            s = jnp.concatenate(
                [jnp.where(tri, s[:, :t], NEG_INF), s[:, t:]], axis=1)
    if band is not None:
        d = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((-d if keys_first else d) < band, s, NEG_INF)
    return s


def _triangle(t: int, keys_first: bool):
    """The diagonal tile's mask: row >= key, rows on sublanes (or on lanes,
    for the transposed tile of dK/dV)."""
    a = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return a <= b if keys_first else a >= b


def _row(ref, start, width):
    """[1, width] of a ``[1, 1, S]`` row-vector ref."""
    return ref[0, 0, pl.ds(start, width)][None, :]


def _split_refs(refs, n_in, use_bias, use_segs):
    """(inputs, bias, segq, segk, outputs) of a kernel's positional refs."""
    refs = list(refs)
    ins, rest = refs[:n_in], refs[n_in:]
    bias = rest.pop(0) if use_bias else None
    segq, segk = (rest.pop(0), rest.pop(0)) if use_segs else (None, None)
    return ins, bias, segq, segk, rest


def _vmem(s: int, d: int, dv: int, dtype) -> dict:
    """``compiler_params`` for a launch whose whole-sequence operands pass
    what a kernel may take by default. A kernel holds two whole ``[S, D]`` /
    ``[S, Dv]`` operands (k and v, or q and dO), twice each (the pipeline's
    two buffers), lane-padded to 128; at ``VMEM_ASK`` and under the launch is
    as it always was (PERF.md §6, PR 26: ``D`` 128 at ``S`` 8192 asks 11-12
    MiB of the v5e's default 16), above it the limit is raised to hold them
    with the tiles' room on top."""
    lanes = lambda w: -(-w // 128) * 128
    held = 2 * s * (lanes(d) + lanes(dv)) * jnp.dtype(dtype).itemsize
    if held <= VMEM_ASK:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=int(held + VMEM_TILES))}


def _fwd_kernel(*refs, sched: _Schedule, scale: float, use_bias: bool,
                use_segs: bool):
    # Shapes: q [1, bq, D], k [1, S, D], v [1, S, Dv], bias [1, 1, S], o [1, bq, Dv],
    # lse [1, 1, bq]; with use_segs also segq [1, 1, bq], segk [1, 1, S]
    # (int32 packed-sequence ids — tokens attend within their segment).
    # Row-vectors ride a leading singleton so their last two block dims
    # satisfy Mosaic's (8, 128)-or-full tiling rule.
    (q_ref, k_ref, v_ref), bias_ref, segq_ref, segk_ref, (o_ref, lse_ref) = (
        _split_refs(refs, 3, use_bias, use_segs))
    bq, dv = q_ref.shape[1], v_ref.shape[2]
    qi = pl.program_id(1)  # Q-block index

    # Matmul operands stay in the input dtype (bf16 hits the MXU at full
    # rate; f32 would run it 8x slower); accumulation and the softmax
    # statistics are f32.
    q = q_ref[0]                                         # [bq, D]
    segq = segq_ref[0, 0][:, None] if use_segs else None  # [bq, 1]

    def attend(carry, rows, start, width, mask, band):
        # online-softmax update of the block's ``rows`` with keys
        # [start, start + width)
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(start, width), :]
        v_blk = v_ref[0, pl.ds(start, width), :]
        s = _scores(
            q[rows], k_blk, scale,
            _row(bias_ref, start, width) if use_bias else None,
            segq[rows] if use_segs else None,
            _row(segk_ref, start, width) if use_segs else None,
            mask, band)                                  # [rows, width] f32
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, _NN,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = _sweep(
        sched, qi,
        (jnp.full((bq, 1), NEG_INF, dtype=jnp.float32),
         jnp.zeros((bq, 1), dtype=jnp.float32),
         jnp.zeros((bq, dv), dtype=jnp.float32)),
        attend)

    valid = m > NEG_INF / 2                              # rows with >=1 unmasked key
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.where(valid, acc / l, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)
    # Logsumexp residual for the backward kernels; +inf on fully-masked
    # rows makes their recomputed P exactly 0.
    lse_ref[0, 0] = jnp.where(valid, m + jnp.log(l), jnp.inf)[:, 0]


def _flash_fwd_bh(q, k, v, bias=None, segs=None, *, causal: bool,
                  block_q: int, block_k: int, interpret: bool,
                  window: Optional[int] = None):
    """q,k: [BH, S, D]; v: [BH, S, Dv]; bias: optional [BH, 1, S] additive
    (0 / NEG_INF); segs: optional [BH, 1, S] int32 packed-sequence ids.
    Returns (out [BH, S, Dv], lse [BH, 1, S])."""
    s = q.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must be divisible by blocks ({block_q},{block_k})")
    return _fwd_call(q, k, v, bias, segs, causal=causal, block_q=block_q,
                     block_k=block_k, interpret=interpret, caller=caller_scope(),
                     window=window)


# The launches are jitted so that a model's layers, which call with the same
# shapes, share one trace and one lowering of a kernel's body: its strips are
# static Python loops, and 72 traces of them a step program cost the set-up
# 20 s (PERF.md §6, PR 26).
_LAUNCH_STATICS = ("causal", "block_q", "block_k", "interpret", "caller", "window")


def _launch_name(kernel: str, window: Optional[int]) -> str:
    """A windowed launch has a name of its own in the trace, so that a reader
    tells it from a global layer's launch at the same shapes."""
    return kernel if window is None else f"window_{kernel}"


@functools.partial(jax.jit, static_argnames=_LAUNCH_STATICS)
def _fwd_call(q, k, v, bias, segs, *, causal, block_q, block_k, interpret,
              caller, window=None):
    bh, s, d = q.shape
    dv = v.shape[2]
    kernel = functools.partial(
        _fwd_kernel, sched=_schedule(s, block_q, block_k, causal, window=window),
        scale=d ** -0.5, use_bias=bias is not None, use_segs=segs is not None)
    mem = {"memory_space": pltpu.VMEM}
    grid = (bh, s // block_q)
    qblock = pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j), **mem)
    full_row = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0), **mem)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0), **mem),
        pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0), **mem),
        pl.BlockSpec((1, s, dv), lambda i, j: (i, 0, 0), **mem),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs += [full_row]
        args += [bias]
    if segs is not None:
        in_specs += [qblock, full_row]   # segq view (q rows), segk view (all keys)
        args += [segs, segs]
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j: (i, j, 0), **mem),
            qblock,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=interpret,
        **_vmem(s, d, dv, q.dtype),
    )
    with kernel_scope(_launch_name("flash_fwd", window), caller):
        return call(*args)


def _dq_kernel(*refs, sched: _Schedule, scale: float, use_bias: bool,
               use_segs: bool):
    # Shapes: q/dq [1, bq, D], do [1, bq, Dv], k [1, S, D], v [1, S, Dv],
    # bias [1, 1, S], lse/delta [1, 1, bq]. One Q block per grid step,
    # walking keys.
    ((q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref), bias_ref, segq_ref,
     segk_ref, (dq_ref,)) = _split_refs(refs, 6, use_bias, use_segs)
    bq = q_ref.shape[1]
    qi = pl.program_id(1)

    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]                         # [bq, 1]
    delta = delta_ref[0, 0][:, None]                     # [bq, 1]
    segq = segq_ref[0, 0][:, None] if use_segs else None

    def grad(carry, rows, start, width, mask, band):
        # dq of the block's ``rows`` from keys [start, start + width)
        (acc,) = carry
        k_blk = k_ref[0, pl.ds(start, width), :]
        v_blk = v_ref[0, pl.ds(start, width), :]
        s = _scores(
            q[rows], k_blk, scale,
            _row(bias_ref, start, width) if use_bias else None,
            segq[rows] if use_segs else None,
            _row(segk_ref, start, width) if use_segs else None,
            mask, band)
        p = jnp.exp(s - lse[rows])                       # exact probs via saved lse
        dp = jax.lax.dot_general(do[rows], v_blk, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[rows]) * scale
        return (acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, _NN,
            preferred_element_type=jnp.float32),)

    (acc,) = _sweep(
        sched, qi, (jnp.zeros((bq, q_ref.shape[2]), dtype=jnp.float32),), grad)
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _dkv_kernel(*refs, sched: _Schedule, scale: float, use_bias: bool,
                use_segs: bool):
    # Shapes: k/dk [1, bk, D], v/dv [1, bk, Dv], q [1, S, D], do [1, S, Dv],
    # bias [1, 1, bk], lse/delta [1, 1, S]. One K block per grid step, walking rows; the
    # tile is transposed, [keys, rows], so lse and delta stay row vectors.
    ((q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref), bias_ref, segq_ref,
     segk_ref, (dk_ref, dv_ref)) = _split_refs(refs, 6, use_bias, use_segs)
    bk = k_ref.shape[1]
    ki = pl.program_id(1)

    k_blk = k_ref[0]
    v_blk = v_ref[0]
    bias = bias_ref[0, 0][:, None] if use_bias else None  # [bk, 1]
    segk = segk_ref[0, 0][:, None] if use_segs else None  # [bk, 1]

    def grad(carry, keys, start, width, mask, band):
        # dk, dv of the block's ``keys`` from rows [start, start + width)
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(start, width), :]
        do_blk = do_ref[0, pl.ds(start, width), :]
        s = _scores(
            k_blk[keys], q_blk, scale,
            bias[keys] if use_bias else None,
            segk[keys] if use_segs else None,
            _row(segq_ref, start, width) if use_segs else None,
            mask, band, keys_first=True)                 # [keys, width] f32
        p = jnp.exp(s - _row(lse_ref, start, width))
        dv = dv + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, _NN,
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_blk[keys], do_blk, _NT,
                                 preferred_element_type=jnp.float32)
        # d(scale·q·kᵀ)/dk = scale·q; fold the scale into ds.
        ds = p * (dp - _row(delta_ref, start, width)) * scale
        dk = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, _NN,
            preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = _sweep(
        sched, ki,
        (jnp.zeros(k_blk.shape, dtype=jnp.float32),
         jnp.zeros(v_blk.shape, dtype=jnp.float32)),
        grad)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_bh(q, k, v, bias, lse, out, do, segs=None, *, causal, block_q,
                  block_k, interpret, delta_shift=None, window=None):
    s = q.shape[1]
    return _bwd_call(q, k, v, bias, lse, out, do, segs, delta_shift,
                     causal=causal, block_q=min(block_q, s),
                     block_k=min(block_k, s), interpret=interpret,
                     caller=caller_scope(), window=window)


@functools.partial(jax.jit, static_argnames=_LAUNCH_STATICS)
def _bwd_call(q, k, v, bias, lse, out, do, segs, delta_shift, *, causal,
              block_q, block_k, interpret, caller, window=None):
    bh, s, d = q.shape
    dv = v.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta[:, None, :]                            # [BH, 1, S]
    if delta_shift is not None:
        # lse cotangent from _flash_bh_lse: ds = p*(dp - delta + g_lse).
        delta = delta - delta_shift.astype(jnp.float32)
    static = dict(scale=d ** -0.5, use_bias=bias is not None,
                  use_segs=segs is not None)

    mem = {"memory_space": pltpu.VMEM}
    # a block of q's or k's width, and one of v's (the same spec when equal)
    def wide(rows, index, width):
        return pl.BlockSpec((1, rows, width), index, **mem)

    whole, by_block = (lambda i, j: (i, 0, 0)), (lambda i, j: (i, j, 0))
    full, vfull = wide(s, whole, d), wide(s, whole, dv)
    full_row = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0), **mem)
    qblock, doblock = wide(block_q, by_block, d), wide(block_q, by_block, dv)
    kblock, vblock = wide(block_k, by_block, d), wide(block_k, by_block, dv)
    qrow = pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j), **mem)
    krow = pl.BlockSpec((1, 1, block_k), lambda i, j: (i, 0, j), **mem)
    args = [q, k, v, lse, do, delta]

    dq_specs = [qblock, full, vfull, qrow, doblock, qrow]
    dkv_specs = [full, kblock, vblock, full_row, vfull, full_row]
    if bias is not None:
        args += [bias]
        dq_specs += [full_row]
        dkv_specs += [krow]
    if segs is not None:
        args += [segs, segs]
        dq_specs += [qrow, full_row]
        dkv_specs += [full_row, krow]

    dq_call = pl.pallas_call(
        functools.partial(
            _dq_kernel, sched=_schedule(s, block_q, block_k, causal, window=window),
            **static),
        grid=(bh, s // block_q),
        in_specs=dq_specs,
        out_specs=qblock,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        **_vmem(s, d, dv, q.dtype),
    )
    with kernel_scope(_launch_name("flash_dq", window), caller):
        dq = dq_call(*args)

    dkv_call = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            sched=_schedule(s, block_q, block_k, causal, walks_rows=True,
                            window=window),
            **static),
        grid=(bh, s // block_k),
        in_specs=dkv_specs,
        out_specs=[kblock, vblock],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
        ],
        interpret=interpret,
        **_vmem(s, d, dv, q.dtype),
    )
    with kernel_scope(_launch_name("flash_dkv", window), caller):
        dk, dv = dkv_call(*args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_bh(q, k, v, bias, segs, causal, block_q, block_k, interpret,
              window=None):
    out, _ = _flash_fwd_bh(q, k, v, bias, segs, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret, window=window)
    return out


def _flash_bh_fwd(q, k, v, bias, segs, causal, block_q, block_k, interpret,
                  window):
    """The launch's ``out`` and ``lse`` are named for a remat policy
    (``scope.KERNEL_RESULTS``): a block rematted with it keeps them, and its
    backward does not launch the forward again."""
    out, lse = name_results("flash", *_flash_fwd_bh(
        q, k, v, bias, segs, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window))
    return out, (q, k, v, bias, segs, lse, out)


def _flash_bh_bwd(causal, block_q, block_k, interpret, window, residuals, g):
    q, k, v, bias, segs, lse, out = residuals
    dq, dk, dv = _flash_bwd_bh(q, k, v, bias, lse, out, g, segs, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret, window=window)
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
    """``out`` and ``lse`` named for a remat policy, as in ``_flash_bh_fwd``."""
    out, lse = name_results("flash", *_flash_fwd_bh(
        q, k, v, bias, segs, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret))
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
    make S a lane dim, so blocks must be multiples of 128 (or full S). A
    sequence of up to ``WHOLE_SEQ`` is one block (module docstring)."""
    return s if s <= WHOLE_SEQ else pick_block(s, desired, 128)


def _prep_bh(q, k, v, kv_mask, segment_ids, block_q, block_k, interpret):
    b, s, h, d = q.shape
    if interpret is None:
        interpret = not on_tpu()
    if block_q is None:
        block_q = _pick_seq_block(s, DEFAULT_BLOCK_Q)
    if block_k is None:
        block_k = _pick_seq_block(s, DEFAULT_BLOCK_K)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    bias = segs = None
    if kv_mask is not None:
        bias = jnp.where(kv_mask.astype(bool), 0.0, NEG_INF).astype(jnp.float32)
        bias = jnp.repeat(bias, h, axis=0)[:, None, :]  # [BH, 1, S]
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
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused attention; drop-in for ``dot_product_attention`` on TPU.
    ``segment_ids`` confines attention within matching ids (packed
    sequences / block-diagonal masking), composable with ``kv_mask``
    and ``causal``. ``window`` (a static integer, causal only) confines a
    row to its own position and the ``window - 1`` before it: the kernels
    skip what lies behind the window (module docstring), and their launches
    are named ``window_flash_*``. ``None``, or the sequence's length or
    more, is plain causal attention."""
    b, s, h, d = q.shape
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"a window ({window}) is of at least one key and causal")
        window = None if window >= s else int(window)
    qb, kb, vb, bias, segs, block_q, block_k, interpret = _prep_bh(
        q, k, v, kv_mask, segment_ids, block_q, block_k, interpret
    )
    out = _flash_bh(qb, kb, vb, bias, segs, causal, block_q, block_k, interpret,
                    window)
    return out.reshape(b, h, s, v.shape[-1]).transpose(0, 2, 1, 3)


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
    out = out.reshape(b, h, s, v.shape[-1]).transpose(0, 2, 1, 3)
    lse = lse[:, 0, :].reshape(b, h, s).transpose(0, 2, 1)  # [B, S, H]
    lse = jnp.where(jnp.isposinf(lse), NEG_INF, lse)
    return out, lse
