"""Decoder-only causal language model + KV-cache autoregressive decoding.

No counterpart in the reference (its only models are an MLP and CNNs —
SURVEY §2b); this completes the transformer family with the *serving*
path a framework needs: train with next-token loss, then generate with
a static-shape KV cache under ``lax.scan`` — no retracing per token, no
dynamic shapes, XLA-friendly throughout.

TPU-first design notes:

* pre-LN blocks sharing the encoder's building blocks
  (``_dense`` / ``_layernorm`` / logical axis annotations from
  ``models/bert.py``) so the same LOGICAL_RULES place it on any mesh;
* training attention goes through the same dispatch as BERT: Pallas
  flash (``causal=True`` with block-level skipping) on TPU at
  seq >= FLASH_MIN_SEQ, dense otherwise, shard_map-wrapped on sharded
  meshes;
* decoding keeps a ``[B, S_max, H_kv, D]`` K/V cache per layer as flax
  "cache" variables (``H_kv < H`` under grouped-query attention — the
  cache, and with it per-step HBM traffic, shrinks by ``H/H_kv``); each
  step attends over the cache prefix with a position mask (static
  shapes — the mask, not the shapes, moves);
* ``generate`` = one jitted prefill + one jitted ``lax.scan`` over
  decode steps (greedy or temperature sampling).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh

from pyspark_tf_gke_tpu.models.bert import _data_shards, _dense
from pyspark_tf_gke_tpu.models.embedding import TokenEmbed
from pyspark_tf_gke_tpu.parallel.sharding import mesh_extent_for
from pyspark_tf_gke_tpu.ops.attention import dot_product_attention
from pyspark_tf_gke_tpu.ops.pallas.scope import part_scope

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class CausalLMConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_seq_len: int = 1024
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    use_flash: Optional[bool] = None  # None = auto (TPU, seq >= FLASH_MIN_SEQ)
    # Grouped-query attention: K/V get this many heads (must divide
    # num_heads); None = num_heads (standard MHA), 1 = MQA. The KV cache
    # shrinks by num_heads/num_kv_heads — the decode path is HBM-bound on
    # cache reads, so this is a direct serving-throughput lever.
    num_kv_heads: Optional[int] = None
    # "learned" = absolute wpe table (GPT-2 style); "rope" = rotary
    # embeddings applied to q/k (no position table, better length
    # extrapolation, the modern default for long-context decoders).
    pos_embedding: str = "learned"
    rope_theta: float = 10000.0
    # "layernorm" (GPT-2 style, the Pallas-fused LN) or "rmsnorm"
    # (Llama style: no mean subtraction, no bias — one less HBM pass).
    norm: str = "layernorm"
    # "gelu" (hidden = W2 gelu(W1 x)) or "swiglu" (Llama style:
    # hidden = W2 (silu(Wg x) * W1 x); intermediate_size is the gated
    # width as given — no 2/3 rescaling is applied implicitly).
    ffn: str = "gelu"
    # int8 KV cache: store K/V as int8 with one float32 scale per
    # (batch, position, kv_head) — symmetric over head_dim, quantized at
    # write time. Decode streams the whole cache every step, so this
    # cuts that traffic 4x vs f32 (2x vs bf16) ON TOP of GQA's
    # num_heads/kv_heads shrink; the dequant (convert+scale) fuses into
    # the attention einsums. Composes with beam search and tp sharding.
    kv_cache_quant: bool = False
    # Paged KV cache (slot-decode / continuous batching only): when
    # kv_num_pages is set, slot mode stores K/V in ONE global page pool
    # per layer — (kv_num_pages, kv_page_size, kv_heads, head_dim) —
    # plus an int32 block table (num_slots, max_pages_per_slot) naming
    # each slot's pages. Cache memory then tracks tokens actually
    # allocated by the engine (train/continuous.py manages page
    # alloc/free on admit/free), not num_slots x max_seq_len, and the
    # decode read is the ragged ops/pallas/paged_attention kernel whose
    # HBM traffic stops at each slot's last live page. The non-slot
    # paths (training, prefill, whole-batch generate) are unaffected —
    # they keep the dense layouts.
    kv_page_size: int = 64
    kv_num_pages: Optional[int] = None  # None = dense slot cache

    @property
    def paged_kv(self) -> bool:
        return self.kv_num_pages is not None

    @property
    def max_pages_per_slot(self) -> int:
        return -(-self.max_seq_len // self.kv_page_size)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        kv = self.num_kv_heads if self.num_kv_heads is not None else self.num_heads
        if self.num_heads % kv:
            raise ValueError(
                f"num_kv_heads {kv} must divide num_heads {self.num_heads}")
        return kv


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """Rotary position embedding on ``x [B, S, H, D]`` at integer
    ``positions [B, S]`` (rotate-half formulation, fp32 angles). The
    same code serves training (positions = arange) and decode
    (positions = the single cache index), because rotation is purely
    per-position — nothing is cached or retrained for new lengths."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]                       # [B,S,1,half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def llama_like(**overrides) -> "CausalLMConfig":
    """Llama-architecture preset: RoPE + RMSNorm + SwiGLU. Combine with
    ``num_kv_heads`` for GQA. Any field can be overridden."""
    defaults = dict(pos_embedding="rope", norm="rmsnorm", ffn="swiglu")
    return CausalLMConfig(**{**defaults, **overrides})


class RMSNorm(nn.Module):
    """Llama-style norm: ``x * scale / rms(x)`` — no mean subtraction,
    no bias. fp32 statistics regardless of compute dtype."""

    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(),
                                         ("embed",)),
            (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.epsilon)
        return (xf / rms * scale).astype(self.dtype)


def _ln(cfg: CausalLMConfig, mesh: Optional[Mesh] = None, name=None):
    if cfg.norm == "rmsnorm":
        return RMSNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name=name)
    if cfg.norm != "layernorm":
        raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', "
                         f"got {cfg.norm!r}")
    from pyspark_tf_gke_tpu.models.bert import FusedLayerNorm

    return FusedLayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                          mesh=mesh, name=name)


class CausalSelfAttention(nn.Module):
    cfg: CausalLMConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, hidden, *, decode: bool = False, prefill: bool = False,
                 positions: Optional[jnp.ndarray] = None,
                 segment_ids: Optional[jnp.ndarray] = None,
                 slot_decode: bool = False):
        cfg = self.cfg
        b, s, _ = hidden.shape
        h, hkv, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim

        q = _dense(cfg.hidden_size, ("embed", "mlp"), cfg, name="query")(hidden)
        k = _dense(hkv * d, ("embed", "mlp"), cfg, name="key")(hidden)
        v = _dense(hkv * d, ("embed", "mlp"), cfg, name="value")(hidden)
        q = q.reshape(b, s, h, d)
        k = k.reshape(b, s, hkv, d)
        v = v.reshape(b, s, hkv, d)
        if cfg.pos_embedding == "rope":
            if d % 2:
                raise ValueError(f"rope needs an even head_dim, got {d}")
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            # rotate q and k (the cache then holds rotated keys, so the
            # decode einsum needs no further position handling)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
        # K/V carry only kv_heads here; with more head-shards than
        # kv_heads (e.g. MQA on a tp=2 mesh) a 'heads' constraint on
        # that axis is non-divisible and the trace fails. Keep the
        # constraint whenever the head-shard extent divides kv_heads
        # (so divisible GQA, e.g. kv=4/tp=2, stays explicitly sharded
        # through the cache write) and only drop it — re-constraining
        # after the repeat below — when it cannot divide. The extent is
        # derived from LOGICAL_RULES ("heads" → whatever axis the rules
        # map), not a hardcoded "tp".
        tp = mesh_extent_for("heads", self.mesh)
        kv_axes = ("batch", "seq", "heads" if hkv % tp == 0 else None,
                   "head_dim")
        k = nn.with_logical_constraint(k, kv_axes)
        v = nn.with_logical_constraint(v, kv_axes)

        if decode:
            out = self._decode_attend(
                q, k, v,
                row_positions=(positions if slot_decode else None))
        else:
            if prefill:
                # One full forward fills the whole cache prefix — no
                # per-token replay; attention below is the normal causal
                # pass over the prompt. The cache stores kv_heads only.
                self._write_cache_prefix(k, v)
            if hkv != h:
                # Training/prefill compute path: broadcast K/V to the full
                # head count so the shared flash/dense engines apply. The
                # GQA memory win is in the cache, not the training pass.
                k = jnp.repeat(k, h // hkv, axis=2)
                v = jnp.repeat(v, h // hkv, axis=2)
                k = nn.with_logical_constraint(
                    k, ("batch", "seq", "heads", "head_dim"))
                v = nn.with_logical_constraint(
                    v, ("batch", "seq", "heads", "head_dim"))
            out = self._causal_attend(q, k, v, segment_ids=segment_ids)
        out = out.reshape(b, s, cfg.hidden_size)
        return _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="out")(out)

    def _causal_attend(self, q, k, v, segment_ids=None):
        from pyspark_tf_gke_tpu.models.bert import resolve_use_flash

        cfg = self.cfg
        s = q.shape[1]
        if resolve_use_flash(cfg, s):
            from pyspark_tf_gke_tpu.ops.pallas.flash_attention import (
                flash_attention,
            )

            if _data_shards(self.mesh, "dp", "fsdp", "tp") > 1:
                # Same rationale as BertSelfAttention: the partitioner
                # can't split an opaque Pallas call — run it per shard.
                from jax.sharding import PartitionSpec as P

                from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES

                qkv_spec = P(DATA_AXES, None, "tp", None)
                # one shard_map either way: the optional segment operand
                # rides as *rest so the dispatch can't diverge between
                # the masked and unmasked paths
                operands = (q, k, v)
                in_specs = (qkv_spec,) * 3
                if segment_ids is not None:
                    operands += (segment_ids,)
                    in_specs += (P(DATA_AXES, None),)
                fn = shard_map(
                    lambda qq, kk, vv, *rest: flash_attention(
                        qq, kk, vv, causal=True,
                        segment_ids=rest[0] if rest else None),
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=qkv_spec,
                    check_vma=False,
                )
                return fn(*operands)
            return flash_attention(q, k, v, causal=True,
                                   segment_ids=segment_ids)
        mask = None
        if segment_ids is not None:
            # block-diagonal: query attends only within its document
            mask = (segment_ids[:, None, :, None] ==
                    segment_ids[:, None, None, :])
        return dot_product_attention(q, k, v, mask=mask, causal=True)

    def _paged_cache_vars(self, b, h, d, dtype):
        """Paged slot-cache variables: the global page pool (shared by
        every slot), the per-slot block table, and the conservative
        fill counter. The block table initializes to the OUT-OF-RANGE
        sentinel ``kv_num_pages`` — a row with no pages writes nowhere
        (scatter mode="drop") and reads only masked garbage — so a
        freed slot's rows can never touch pages reallocated to another
        request."""
        cfg = self.cfg
        store = jnp.int8 if cfg.kv_cache_quant else dtype
        n, ps = cfg.kv_num_pages, cfg.kv_page_size
        if cfg.max_seq_len % ps:
            raise ValueError(
                f"kv_page_size {ps} must divide max_seq_len "
                f"{cfg.max_seq_len}")
        mp = cfg.max_pages_per_slot
        kp = self.variable("cache", "k_pages", jnp.zeros, (n, ps, h, d),
                           store)
        vp = self.variable("cache", "v_pages", jnp.zeros, (n, ps, h, d),
                           store)
        bt = self.variable("cache", "block_table",
                           lambda: jnp.full((b, mp), n, jnp.int32))
        idx = self.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
        if not cfg.kv_cache_quant:
            return kp, vp, bt, None, None, idx
        ks = self.variable("cache", "k_scale_pages", jnp.zeros,
                           (n, ps, h), jnp.float32)
        vs = self.variable("cache", "v_scale_pages", jnp.zeros,
                           (n, ps, h), jnp.float32)
        return kp, vp, bt, ks, vs, idx

    def _paged_decode_attend(self, q, k, v, row_positions):
        """Slot-decode step against the paged pool: write each row's
        new K/V at (block_table[row, pos // P], pos % P) — one token
        per row on the decode path, or a CHUNK of s consecutive tokens
        (chunked prefill writes a prompt piece straight into the slot's
        pages; ``row_positions[b]`` must then be ``fill + arange(s)``)
        — then attend through the block table with the ragged
        ``paged_attention`` / ``paged_attention_chunk`` kernel
        (pure-JAX reference off-TPU). Writing BEFORE attending makes
        in-chunk causality fall out of the position mask: each chunk
        query sees exactly the keys at positions <= its own."""
        cfg = self.cfg
        b, s, h, d = q.shape
        from pyspark_tf_gke_tpu.ops.pallas.paged_attention import (
            paged_attention,
            paged_attention_chunk,
        )

        hkv = k.shape[2]
        kp, vp, bt, ks, vs, idx = self._paged_cache_vars(b, hkv, d, k.dtype)
        pos = row_positions                                      # [B, s]
        ps = cfg.kv_page_size
        # take_along_axis clips an over-long dead row's page index into
        # the table; a sentinel entry there makes the write a no-op.
        page = jnp.take_along_axis(
            bt.value, jnp.minimum(pos // ps, bt.value.shape[1] - 1),
            axis=1)                                              # [B, s]
        off = pos % ps
        krows, vrows = k, v                                  # [B,s,Hkv,D]
        if ks is not None:
            krows, k_scale = self._quantize_kv(krows)
            vrows, v_scale = self._quantize_kv(vrows)
            ks.value = ks.value.at[page, off].set(k_scale, mode="drop")
            vs.value = vs.value.at[page, off].set(v_scale, mode="drop")
        kp.value = kp.value.at[page, off].set(
            krows.astype(kp.value.dtype), mode="drop")
        vp.value = vp.value.at[page, off].set(
            vrows.astype(vp.value.dtype), mode="drop")
        idx.value = jnp.maximum(idx.value, jnp.max(pos) + 1)
        # fills = live tokens INCLUDING the chunk (positions must be
        # consecutive per row — the chunked-prefill contract)
        fills = pos[:, -1] + 1
        pool = [kp.value, vp.value]
        if ks is not None:
            pool += [ks.value, vs.value]

        def attend(qq, tbl, fl, kpg, vpg, *sc):
            sc = dict(zip(("k_scales", "v_scales"), sc))
            if s == 1:
                return paged_attention(qq[:, 0], kpg, vpg, tbl, fl,
                                       **sc)[:, None]        # [B,1,H,D]
            return paged_attention_chunk(qq, kpg, vpg, tbl, fl, **sc)

        if self.mesh is not None and self.mesh.size > 1:
            # Same rationale as _causal_attend: the partitioner can't
            # split an opaque Pallas call — run it per shard. Heads are
            # independent, so each tp shard attends its own query heads
            # against its own slice of the page pool; the block table
            # and fill levels are replicated. KV heads that tp does not
            # divide (MQA on tp=2) stay whole on every shard.
            from jax.sharding import PartitionSpec as P

            ax = "tp" if hkv % self.mesh.shape["tp"] == 0 else None
            heads, rep = P(None, None, ax, None), P()
            attend = shard_map(
                attend, mesh=self.mesh,
                in_specs=(heads, rep, rep, heads, heads)
                + (P(None, None, ax),) * (len(pool) - 2),
                out_specs=heads, check_vma=False)
        return attend(q, bt.value, fills, *pool)

    def _cache_vars(self, b, h, d, dtype):
        cfg = self.cfg
        store = jnp.int8 if cfg.kv_cache_quant else dtype
        ck = self.variable("cache", "k", jnp.zeros,
                           (b, cfg.max_seq_len, h, d), store)
        cv = self.variable("cache", "v", jnp.zeros,
                           (b, cfg.max_seq_len, h, d), store)
        idx = self.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
        if not cfg.kv_cache_quant:
            return ck, cv, None, None, idx
        ks = self.variable("cache", "k_scale", jnp.zeros,
                           (b, cfg.max_seq_len, h), jnp.float32)
        vs = self.variable("cache", "v_scale", jnp.zeros,
                           (b, cfg.max_seq_len, h), jnp.float32)
        return ck, cv, ks, vs, idx

    @staticmethod
    def _quantize_kv(x):
        """[B,S,H,D] -> (int8 [B,S,H,D], f32 scale [B,S,H]): symmetric
        per-(position, head) quantization over head_dim — each cached
        row keeps its own scale, so magnitude outliers stay local."""
        xf = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
        q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
        return q.astype(jnp.int8), scale

    @staticmethod
    def _cache_write(cache, pos, k, v):
        """Write k/v [B,s,H,D] at position ``pos`` (prefix fill or one
        decode token) into the cache vars, quantizing when int8."""
        ck, cv, ks, vs, _ = cache
        if ks is not None:
            k, k_scale = CausalSelfAttention._quantize_kv(k)
            v, v_scale = CausalSelfAttention._quantize_kv(v)
            ks.value = jax.lax.dynamic_update_slice(
                ks.value, k_scale, (0, pos, 0))
            vs.value = jax.lax.dynamic_update_slice(
                vs.value, v_scale, (0, pos, 0))
        ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, pos, 0, 0))
        cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, pos, 0, 0))

    @staticmethod
    def _cache_write_rows(cache, pos_b, k, v):
        """Slot-mode write: k/v [B,s,H,D] land at a DIFFERENT position
        per row (``pos_b`` [B] int32) — each batch row is an independent
        request at its own fill level (train/continuous.py). A vmapped
        per-row dynamic_update_slice costs a scatter instead of the
        uniform path's one contiguous slice write, which is why the
        whole-batch path above stays separate."""
        ck, cv, ks, vs, _ = cache
        row3 = jax.vmap(
            lambda buf, val, p: jax.lax.dynamic_update_slice(
                buf, val, (p, 0, 0)))
        if ks is not None:
            k, k_scale = CausalSelfAttention._quantize_kv(k)
            v, v_scale = CausalSelfAttention._quantize_kv(v)
            row2 = jax.vmap(
                lambda buf, val, p: jax.lax.dynamic_update_slice(
                    buf, val, (p, 0)))
            ks.value = row2(ks.value, k_scale, pos_b)
            vs.value = row2(vs.value, v_scale, pos_b)
        ck.value = row3(ck.value, k, pos_b)
        cv.value = row3(cv.value, v, pos_b)

    def _write_cache_prefix(self, k, v):
        b, s, h, d = k.shape
        cache = self._cache_vars(b, h, d, k.dtype)
        self._cache_write(cache, 0, k, v)
        cache[-1].value = jnp.asarray(s, jnp.int32)

    def _decode_attend(self, q, k, v, row_positions=None):
        """A decode step against the static-shape KV cache: one token,
        or a CHUNK of s tokens (speculative decoding scores a whole
        draft proposal in one forward). The cache is a flax "cache"
        variable [B, S_max, H_kv, D]; ``cache_index`` tracks the fill
        level, and a position mask (not a dynamic slice shape) hides the
        unwritten suffix — chunk queries get the causal offset mask
        ``k_pos <= pos + q_idx``. With GQA the grouped einsum reads each
        cached KV head once for its whole query group — the HBM traffic
        drops by num_heads/kv_heads.

        ``row_positions`` [B, s] switches to slot mode (continuous
        batching): each row writes at ITS OWN fill level and masks
        against it; the shared ``cache_index`` advances to the max fill
        so non-slot readers of the var stay conservative."""
        cfg = self.cfg
        b, s, h, d = q.shape
        hkv = k.shape[2]
        if row_positions is not None and cfg.paged_kv:
            return self._paged_decode_attend(q, k, v, row_positions)
        cache = self._cache_vars(b, hkv, d, k.dtype)
        ck, cv, ks, vs, idx = cache
        if row_positions is not None:
            pos_b = row_positions[:, 0]                       # [B]
            self._cache_write_rows(cache, pos_b, k, v)
            idx.value = jnp.maximum(idx.value, jnp.max(pos_b) + s)
        else:
            pos = idx.value
            self._cache_write(cache, pos, k, v)
            idx.value = pos + s

        # int8 cache: dequantize in-einsum — XLA streams int8 + the tiny
        # [B,S,H] scales from HBM and fuses convert*scale into the
        # contraction, so the wide bf16/f32 cache never exists in HBM.
        if ks is not None:
            kf = (ck.value.astype(jnp.float32)
                  * ks.value[..., None]).astype(q.dtype)
            vf = (cv.value.astype(jnp.float32)
                  * vs.value[..., None]).astype(q.dtype)
        else:
            kf, vf = ck.value, cv.value

        # [B,s,Hkv,G,D] x [B,S_max,Hkv,D] -> [B,Hkv,G,s,S_max], masked
        # causally past each query's own position (G = query heads per
        # KV head; G=1 is plain MHA).
        g = h // hkv
        q5 = q.reshape(b, s, hkv, g, d)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q5, kf,
                            preferred_element_type=jnp.float32) * (d ** -0.5)
        k_pos = jnp.arange(cfg.max_seq_len)
        if row_positions is not None:
            q_abs = pos_b[:, None] + jnp.arange(s)[None, :]   # [B, s]
            valid = k_pos[None, None, :] <= q_abs[..., None]  # [B, s, S_max]
            vmask = valid[:, None, None, :, :]
        else:
            valid = k_pos[None, :] <= pos + jnp.arange(s)[:, None]
            vmask = valid[None, None, None, :, :]             # [s, S_max]
        scores = jnp.where(vmask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
        return out.reshape(b, s, h, d)


class CausalLMBlock(nn.Module):
    cfg: CausalLMConfig
    mesh: Optional[Mesh] = None
    # Static mode flags live on the MODULE (not call kwargs): nn.remat
    # forwards call kwargs as traced values, and `if decode:` on a
    # tracer crashes. Module attributes stay Python bools under remat.
    decode: bool = False
    prefill: bool = False
    slot_decode: bool = False

    @nn.compact
    def __call__(self, hidden, positions=None, segment_ids=None):
        cfg = self.cfg
        with part_scope("mixer"):
            attn_in = _ln(cfg, self.mesh, name="ln_attn")(hidden)
            hidden = hidden + CausalSelfAttention(cfg, self.mesh, name="attention")(
                attn_in, decode=self.decode, prefill=self.prefill,
                positions=positions, segment_ids=segment_ids,
                slot_decode=self.slot_decode,
            )
        with part_scope("ffn"):
            mlp_in = _ln(cfg, self.mesh, name="ln_mlp")(hidden)
            if cfg.ffn == "swiglu":
                gate = _dense(cfg.intermediate_size, ("embed", "mlp"), cfg,
                              name="mlp_gate")(mlp_in)
                up = _dense(cfg.intermediate_size, ("embed", "mlp"), cfg,
                            name="mlp_in")(mlp_in)
                mlp = nn.silu(gate) * up
            elif cfg.ffn == "gelu":
                mlp = _dense(cfg.intermediate_size, ("embed", "mlp"), cfg,
                             name="mlp_in")(mlp_in)
                mlp = nn.gelu(mlp, approximate=True)
            else:
                raise ValueError(f"ffn must be 'gelu' or 'swiglu', got {cfg.ffn!r}")
            mlp = _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="mlp_out")(mlp)
            return hidden + mlp


class CausalLM(nn.Module):
    """Pre-LN decoder stack with tied-untied LM head (untied: its own
    ("embed", "vocab") projection, tensor-parallel over tp)."""

    cfg: CausalLMConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, input_ids, *, decode: bool = False,
                 prefill: bool = False,
                 positions: Optional[jnp.ndarray] = None,
                 segment_ids: Optional[jnp.ndarray] = None,
                 return_hidden: bool = False,
                 train: bool = True,
                 slot_decode: bool = False):
        cfg = self.cfg
        if cfg.pos_embedding not in ("learned", "rope"):
            raise ValueError(f"pos_embedding must be 'learned' or 'rope', "
                             f"got {cfg.pos_embedding!r}")
        b, s = input_ids.shape
        if slot_decode and (not decode or positions is None
                            or positions.ndim != 2):
            # slot mode (continuous batching, train/continuous.py): each
            # batch row is an independent request at its own cache fill
            # level; positions [B, s] are the per-row authority for the
            # cache write offset, the attention validity mask AND
            # wpe/RoPE — an implicit default would desync them.
            raise ValueError(
                "slot_decode requires decode=True and explicit "
                "positions of shape [batch, s]")
        if decode and s > 1 and positions is None:
            # a decode CHUNK (speculative verify) embeds at absolute
            # positions cache_fill..cache_fill+s-1, which only the
            # caller knows — defaulting to arange(s) would silently
            # misplace wpe/RoPE while the attention mask stays right
            raise ValueError(
                "multi-token decode requires explicit positions "
                "(cache_fill + arange(s)); see models/speculative._extend")
        # One-hot matmul embed on the training path (models/embedding.py:
        # nn.Embed's gather backward triggers involuntary full remat on
        # dp×fsdp×tp meshes). The matmul only pays for itself when a
        # gradient will flow — decode/prefill have no backward, and
        # pure-inference full forwards (scoring/eval) pass train=False
        # to keep the cheap gather too.
        one_hot = train and not (decode or prefill)
        embed = TokenEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
            name="wte",
        )
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        with part_scope("embed"):
            if cfg.pos_embedding == "rope":
                hidden = embed(input_ids, one_hot=one_hot)
            else:
                pos_embed = TokenEmbed(
                    cfg.max_seq_len, cfg.hidden_size, dtype=cfg.dtype,
                    embedding_init=nn.with_logical_partitioning(
                        nn.initializers.normal(stddev=0.02), (None, "embed")),
                    name="wpe",
                )
                hidden = (embed(input_ids, one_hot=one_hot)
                          + pos_embed(positions, one_hot=one_hot))

        block_cls = CausalLMBlock
        if cfg.remat and not (decode or prefill):
            block_cls = nn.remat(CausalLMBlock, static_argnums=())
        # slot mode needs positions in the attention layer even for
        # learned-pos models: they are the per-row cache write offset,
        # not just a RoPE input.
        rope_pos = (positions if cfg.pos_embedding == "rope" or slot_decode
                    else None)
        for i in range(cfg.num_layers):
            hidden = block_cls(cfg, self.mesh, decode=decode, prefill=prefill,
                               slot_decode=slot_decode,
                               name=f"layer_{i}")(hidden, rope_pos,
                                                  segment_ids)
        with part_scope("head_loss"):
            hidden = _ln(cfg, self.mesh, name="ln_final")(hidden)
            head = _dense(cfg.vocab_size, ("embed", "vocab"), cfg, name="lm_head")
            if return_hidden:
                # Chunked-CE training path (ops/chunked_ce.py): the caller
                # applies the head weight chunk-by-chunk inside the loss, so
                # full [B,S,V] logits never materialize. Touch the head on a
                # single position so its params exist under init.
                head(hidden[:, :1])
                return hidden
            return head(hidden).astype(jnp.float32)


# ---------------------------------------------------------------------------
# generation
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("model",))
def _prefill(model: CausalLM, params, prompt_ids):
    """ONE full causal forward over the prompt: computes the last-token
    logits AND writes every layer's K/V into the cache prefix
    (prefill=True) — no per-token replay. ``params`` may be an int8
    weight-only quantized tree (``ops/quant.py``); dequant happens here,
    inside the jit, so XLA fuses it into the matmuls."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    logits, mutated = model.apply(
        {"params": dequantize_tree(params)}, prompt_ids, prefill=True,
        mutable=["cache"]
    )
    return mutated["cache"], logits[:, -1]


def _filter_logits(logits, top_k: Optional[int], top_p):
    """Mask logits outside the top-k set and/or the top-p (nucleus) mass
    to NEG_INF. Static-shape friendly: thresholds, not gathers.
    ``top_k`` is static (lax.top_k needs a static k); ``top_p`` may be a
    traced scalar — only its presence is a trace key, so per-request
    sampling settings don't recompile the decode program."""
    if top_k is not None and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep tokens whose *exclusive* cumulative mass is < top_p — the
        # top token always survives (and top_p >= 1 keeps everything).
        # Threshold = smallest kept logit.
        keep = (cum - probs) < top_p
        thresh = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, NEG_INF, logits)
    return logits


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "greedy", "eos_token_id",
                     "s_prompt", "top_k"),
)
def _decode(model: CausalLM, params, cache, last_logits, rng, temperature,
            top_p, repetition_penalty, seen0, *, max_new_tokens: int,
            greedy: bool, eos_token_id: Optional[int], s_prompt: int,
            top_k: Optional[int] = None):
    from pyspark_tf_gke_tpu.ops.quant import dequantize_embeddings, is_quantized

    quantized = is_quantized(params)
    if quantized:
        # Embedding tables dequant ONCE here (hoisted out of the scan):
        # decode gathers single rows from them, so an in-loop barrier
        # would stream the whole table every step for nothing.
        params = dequantize_embeddings(params)
    b = last_logits.shape[0]

    def penalize(logits, seen):
        """CTRL-style repetition penalty: already-seen tokens become
        less likely — positive logits divide by the penalty, negative
        ones multiply by it (both directions REDUCE the logit; this is
        the CTRL/HF formulation). seen is a [B, V] presence bitmap
        carried through the scan; repetition_penalty rides as a traced
        scalar (1.0 = off) or None (compiled out)."""
        if repetition_penalty is None:
            return logits
        adj = jnp.where(logits > 0, logits / repetition_penalty,
                        logits * repetition_penalty)
        return jnp.where(seen, adj, logits)

    def step_params(p):
        """Weight-only int8: in-loop barriered dequant (ops/quant.py)."""
        if not quantized:
            return p
        from pyspark_tf_gke_tpu.ops.quant import inloop_dequantize

        return inloop_dequantize(p)

    def sample(logits, rng, seen):
        logits = penalize(logits, seen)
        if greedy:
            return jnp.argmax(logits, axis=-1)
        logits = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(rng, logits, axis=-1)

    def emit(logits, rng, done, seen):
        """Sample one token, fold in the eos latch, mark it seen."""
        tok = sample(logits, rng, seen).astype(jnp.int32)    # [B]
        if eos_token_id is not None:
            tok = jnp.where(done, eos_token_id, tok)
            done = done | (tok == eos_token_id)
        if repetition_penalty is not None:
            seen = seen.at[jnp.arange(b), tok].set(True)
        return tok, done, seen

    def step(carry, t):
        cache, logits, rng, done, seen = carry
        rng, sub = jax.random.split(rng)
        tok, done, seen = emit(logits, sub, done, seen)
        logits, mutated = model.apply(
            {"params": step_params(params), "cache": cache}, tok[:, None],
            decode=True,
            positions=jnp.full((b, 1), t, jnp.int32),
            mutable=["cache"],
        )
        return (mutated["cache"], logits[:, 0], rng, done, seen), tok

    # Scan max_new_tokens - 1 steps; the final token is sampled from the
    # carried logits directly — the last model forward (whose logits
    # nobody reads) never runs.
    done0 = jnp.zeros((b,), bool)
    (_, last, rng, done, seen), tokens = jax.lax.scan(
        step, (cache, last_logits, rng, done0, seen0),
        s_prompt + jnp.arange(max_new_tokens - 1),
    )
    rng, sub = jax.random.split(rng)
    final, _, _ = emit(last, sub, done, seen)
    tokens = jnp.concatenate([tokens, final[None]], axis=0)
    return tokens.T  # [B, max_new_tokens]


def generate(
    model: CausalLM,
    params,
    prompt_ids: jnp.ndarray,       # [B, S_prompt] int32
    max_new_tokens: int,
    temperature: float = 0.0,      # 0 → greedy
    rng: Optional[jax.Array] = None,
    eos_token_id: Optional[int] = None,
    top_k: Optional[int] = None,   # sample from the k highest logits
    top_p: Optional[float] = None,  # nucleus sampling mass (0, 1]
    repetition_penalty: Optional[float] = None,  # >1 discourages repeats
) -> jnp.ndarray:
    """Autoregressive decoding: one jitted prefill forward (fills the KV
    cache in a single pass) + one jitted ``lax.scan`` over single-token
    cache steps. The jits are module-level with the model/config static,
    so repeat serving calls with the same shapes hit the compile cache.
    ``top_k``/``top_p`` filter the sampling distribution (ignored when
    greedy). Returns ``[B, S_prompt + max_new_tokens]``; after
    ``eos_token_id`` (if given) positions are padded with eos."""
    cfg = model.cfg
    _, s_prompt = prompt_ids.shape
    if max_new_tokens < 1:
        # the decode scan runs max_new_tokens - 1 steps and then emits one
        # final token from the carried logits, so 0 would silently return
        # 1 generated token (beam_search already validates this)
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if s_prompt + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt {s_prompt} + {max_new_tokens} new tokens exceeds "
            f"max_seq_len {cfg.max_seq_len}"
        )
    if repetition_penalty is not None and repetition_penalty <= 0:
        # 0 would map seen logits to +inf/0 (deterministic repeat loop),
        # negative sign-flips them — both silently corrupt decoding
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache, last_logits = _prefill(model, params, prompt_ids)
    # temperature / top_p / repetition_penalty ride as traced scalars:
    # changing them per call (per request, on a server) reuses the
    # compiled decode program. The repetition presence bitmap [B, V] is
    # seeded from the prompt; a [B, 1] dummy keeps the scan carry
    # structure when the penalty is off.
    b = prompt_ids.shape[0]
    if repetition_penalty is not None:
        seen0 = jnp.zeros((b, cfg.vocab_size), bool)
        seen0 = seen0.at[jnp.arange(b)[:, None], prompt_ids].set(True)
        rp = jnp.float32(repetition_penalty)
    else:
        seen0 = jnp.zeros((b, 1), bool)
        rp = None
    new_tokens = _decode(
        model, params, cache, last_logits, rng,
        jnp.float32(temperature if temperature > 0 else 1.0),
        jnp.float32(top_p) if top_p is not None else None,
        rp, seen0,
        max_new_tokens=max_new_tokens, greedy=temperature <= 0,
        eos_token_id=eos_token_id, s_prompt=s_prompt, top_k=top_k,
    )
    return jnp.concatenate([prompt_ids, new_tokens], axis=1)
