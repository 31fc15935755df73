"""Attention ops.

The reference has no attention anywhere (its largest model is a 43M-param
CNN — SURVEY §2b), but long-context support is first-class in this
framework, so two implementations live here:

* ``dot_product_attention`` — plain batched attention; XLA fuses it well
  on the MXU for moderate sequence lengths.
* ``ring_attention`` — sequence-parallel attention over the ``sp`` mesh
  axis: each device holds one sequence block of Q/K/V, K/V blocks rotate
  around the ring via ``lax.ppermute`` over ICI, and softmax is
  accumulated online (flash-style running max / normalizer), so the full
  S×S score matrix never materializes and sequence length scales with the
  number of devices. Pattern follows the public ring-attention recipe
  (blockwise attention + ring P2P), re-derived for shard_map.
* ``ulysses_attention`` — the all-to-all alternative (DeepSpeed-Ulysses
  pattern): two ``lax.all_to_all``s swap the sequence sharding for a
  *head* sharding, full attention runs locally on ``H/sp`` heads, and a
  final all-to-all restores sequence sharding. Cheaper than the ring when
  ``sp`` ≤ num_heads and the interconnect does fast all-to-all (ICI);
  the ring wins when S is huge (it never holds the full S per device).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def dot_product_attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, H, D]
    v: jnp.ndarray,  # [B, Sk, H, D]
    mask: Optional[jnp.ndarray] = None,  # broadcastable to [B, H, Sq, Sk]
    causal: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Standard attention in float32 accumulation, bf16-friendly inputs.
    ``window`` (causal only) confines a row to its own position and the
    ``window - 1`` before it, as ``flash_attention``'s does."""
    if window is not None and not causal:
        raise ValueError("a window is causal")
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        cm = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window is not None and window < sk:
            cm &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq - window)
        scores = jnp.where(cm[None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if mask is not None:
        # Rows with no valid key (all-padding queries) output 0, not mean(V).
        valid = jnp.broadcast_to(mask, scores.shape).any(axis=-1)  # [B,H,Sq]
        out = jnp.where(valid.transpose(0, 2, 1)[..., None], out, 0)
    return out


def _ring_block(q, k, v, kv_mask, axis_name: str, axis_size: int, causal: bool):
    """Per-device body: local Q block attends to all K/V blocks as they
    rotate around the ring. Shapes: q [B,Sq,H,D]; k,v [B,Sk,H,D];
    kv_mask [B,Sk] bool or None."""
    scale = q.shape[-1] ** -0.5
    b, sq, h, d = q.shape
    sk = k.shape[1]
    my_index = lax.axis_index(axis_name)

    o = jnp.zeros((b, sq, h, d), dtype=jnp.float32)
    m = jnp.full((b, h, sq), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((b, h, sq), dtype=jnp.float32)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(i, carry):
        o, m, l, k, v, kv_mask = carry
        # Which global block this K/V came from: after i rotations we hold
        # the block originally on device (my_index - i) mod axis_size.
        src = (my_index - i) % axis_size
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = my_index * sq + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
            k_pos = src * sk + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
        if kv_mask is not None:
            s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
        )
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if kv_mask is not None:
            kv_mask = lax.ppermute(kv_mask, axis_name, perm)
        return o, m_new, l, k, v, kv_mask

    o, m, l, *_ = lax.fori_loop(0, axis_size, body, (o, m, l, k, v, kv_mask))
    # Rows with no valid key anywhere keep m == NEG_INF (every score was
    # masked); their p/l accumulations are exp(0)=1 garbage — zero them out,
    # matching dot_product_attention's all-padding behavior.
    valid = m > NEG_INF / 2  # [B,H,Sq]
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l.transpose(0, 2, 1)[..., None]
    out = jnp.where(valid.transpose(0, 2, 1)[..., None], out, 0)
    return out.astype(q.dtype)


def _merge_partial(o, lse, o_i, lse_i):
    """Combine two partial attentions (outputs + logsumexps) over
    disjoint key sets — the flash-style merge. NEG_INF (not -inf) marks
    empty rows, so the -inf-minus--inf NaN case never arises; merged
    garbage rows are 0*w + 0*w = 0."""
    lse_new = jnp.logaddexp(lse, lse_i)
    w = jnp.exp(lse - lse_new)[..., None]
    w_i = jnp.exp(lse_i - lse_new)[..., None]
    return o * w + o_i.astype(jnp.float32) * w_i, lse_new


def _ring_block_flash(q, k, v, kv_mask, axis_name: str, axis_size: int):
    """Ring attention with the Pallas flash kernel as the per-step block
    engine: each ring step runs one fused blockwise attention on the
    resident K/V block (returning out + lse), and partial results merge
    by logsumexp. ``lax.scan`` (not fori_loop) so the ring is
    reverse-mode differentiable; K/V/mask rotate via ppermute inside the
    scan, and their cotangents ride the reversed ring on the way back."""
    from pyspark_tf_gke_tpu.ops.pallas.flash_attention import (
        flash_attention_block,
    )

    b, sq, h, d = q.shape
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    o0 = jnp.zeros((b, sq, h, d), dtype=jnp.float32)
    lse0 = jnp.full((b, sq, h), NEG_INF, dtype=jnp.float32)
    have_mask = kv_mask is not None
    mask0 = kv_mask if have_mask else jnp.zeros((), dtype=bool)

    def body(carry, _):
        o, lse, k, v, mask = carry
        o_i, lse_i = flash_attention_block(
            q, k, v, kv_mask=mask if have_mask else None
        )
        o, lse = _merge_partial(o, lse, o_i, lse_i)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if have_mask:
            mask = lax.ppermute(mask, axis_name, perm)
        return (o, lse, k, v, mask), None

    (o, lse, *_), _ = lax.scan(body, (o0, lse0, k, v, mask0), None,
                               length=axis_size)
    return o.astype(q.dtype)


def _sp_shard_map(body, mesh: Mesh, axis: str, kv_mask):
    """Shared shard_map scaffolding for the sequence-parallel attention
    variants: Q/K/V sharded [data, axis, tp, -] with an optional [data,
    axis] mask (a scalar sentinel stands in when there is none — shard_map
    needs a concrete operand either way)."""
    data_spec = ("dp", "fsdp")
    qkv_spec = P(data_spec, axis, "tp", None)
    mask_spec = P(data_spec, axis) if kv_mask is not None else P()
    if kv_mask is None:
        fn = lambda q, k, v, _: body(q, k, v, None)
        kv_mask_arg = jnp.zeros((), dtype=bool)
    else:
        fn = body
        kv_mask_arg = kv_mask
    wrapped = shard_map(
        fn, mesh=mesh, in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
        out_specs=qkv_spec, check_vma=False,
    )
    return lambda q, k, v: wrapped(q, k, v, kv_mask_arg)


def ring_attention(
    q: jnp.ndarray,  # [B, S, H, D] — S sharded over `axis` outside
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    kv_mask: Optional[jnp.ndarray] = None,  # [B, S] bool, S sharded likewise
    axis: str = "sp",
    causal: bool = False,
    use_flash: Optional[bool] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention over mesh axis ``axis``.

    Inputs carry the *global* sequence dimension; shard_map splits it over
    the ring. Batch stays sharded over the data axes, heads over ``tp``.

    ``use_flash`` selects the per-step block engine: the Pallas flash
    kernel with lse-merging (None = auto: TPU backend, per-shard sequence
    >= 512, non-causal — the measured kernel crossover), else the dense
    online-softmax block. Causal ring flash is unsupported (the kernel's
    causal mask is block-local); auto falls back to dense for it.
    """
    axis_size = mesh.shape[axis]
    if axis_size == 1:
        return dot_product_attention(q, k, v,
                                     mask=None if kv_mask is None else kv_mask[:, None, None, :],
                                     causal=causal)
    if use_flash is None:
        from pyspark_tf_gke_tpu.ops.pallas.common import FLASH_MIN_SEQ, on_tpu

        use_flash = (
            not causal and on_tpu()
            and q.shape[1] // axis_size >= FLASH_MIN_SEQ
        )
    if use_flash:
        if causal:
            raise ValueError("ring flash attention does not support causal=True")
        fn = functools.partial(_ring_block_flash, axis_name=axis,
                               axis_size=axis_size)
    else:
        fn = functools.partial(_ring_block, axis_name=axis,
                               axis_size=axis_size, causal=causal)
    return _sp_shard_map(fn, mesh, axis, kv_mask)(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,  # [B, S, H, D] — S sharded over `axis` outside
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    kv_mask: Optional[jnp.ndarray] = None,  # [B, S] bool, S sharded likewise
    axis: str = "sp",
    causal: bool = False,
    use_flash: Optional[bool] = None,
) -> jnp.ndarray:
    """All-to-all sequence parallelism over mesh axis ``axis``.

    Each device starts with a sequence block of all heads; one
    ``all_to_all`` re-shards to all of the sequence for ``H/sp`` heads,
    attention runs locally (exact, not blockwise), and the inverse
    ``all_to_all`` restores the sequence sharding. Head count (after any
    ``tp`` split) must divide by the axis size.

    ``use_flash`` (None = auto: TPU and global seq >= 512) runs the
    local attention through the Pallas flash kernel — the device sees
    the FULL sequence here, so unlike the ring, even ``causal`` works
    (the kernel's positions are global).
    """
    axis_size = mesh.shape[axis]
    if use_flash is None:
        from pyspark_tf_gke_tpu.ops.pallas.common import FLASH_MIN_SEQ, on_tpu

        use_flash = on_tpu() and q.shape[1] >= FLASH_MIN_SEQ
    if axis_size == 1:
        if use_flash:
            from pyspark_tf_gke_tpu.ops.pallas.flash_attention import (
                flash_attention,
            )

            return flash_attention(q, k, v, kv_mask=kv_mask, causal=causal)
        return dot_product_attention(
            q, k, v,
            mask=None if kv_mask is None else kv_mask[:, None, None, :],
            causal=causal,
        )
    from pyspark_tf_gke_tpu.parallel.sharding import mesh_extent_for

    tp = mesh_extent_for("heads", mesh)  # rule-derived, not literal "tp"
    local_heads = q.shape[2] // tp
    if local_heads % axis_size:
        raise ValueError(
            f"ulysses needs per-device head count {local_heads} divisible by "
            f"{axis}={axis_size}; use ring_attention instead"
        )

    def body(q, k, v, mask):
        # [B, S/sp, h, D] -> [B, S, h/sp, D]: split heads, gather sequence.
        q, k, v = (
            lax.all_to_all(t, axis, split_axis=2, concat_axis=1, tiled=True)
            for t in (q, k, v)
        )
        full_mask = (
            None if mask is None
            else lax.all_gather(mask, axis, axis=1, tiled=True)
        )
        if use_flash:
            from pyspark_tf_gke_tpu.ops.pallas.flash_attention import (
                flash_attention,
            )

            out = flash_attention(q, k, v, kv_mask=full_mask, causal=causal)
        else:
            out = dot_product_attention(
                q, k, v,
                mask=None if full_mask is None else full_mask[:, None, None, :],
                causal=causal,
            )
        # [B, S, h/sp, D] -> [B, S/sp, h, D]
        return lax.all_to_all(out, axis, split_axis=1, concat_axis=2, tiled=True)

    return _sp_shard_map(body, mesh, axis, kv_mask)(q, k, v)
