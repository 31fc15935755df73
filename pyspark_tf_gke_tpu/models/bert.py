"""BERT-base encoder for the BASELINE.json config-5 workload
("BERT-base fine-tune fed by PySpark-preprocessed TFRecord shards").

Absent from the reference (no attention model exists there — SURVEY §2b);
designed TPU-first:

* every parameter carries **logical axis annotations**
  (``nn.with_logical_partitioning``) so one set of rules
  (``parallel.sharding.LOGICAL_RULES``) places the model on any mesh:
  ``tp`` shards heads and MLP width, ``fsdp`` shards the embed dim,
  ``sp`` shards the sequence dimension of activations;
* attention dispatches to ``ops.ring_attention`` (default) or
  ``ops.ulysses_attention`` (``sp_impl="ulysses"``) when the mesh has an
  ``sp`` axis > 1 — long-context sequence parallelism over ICI — on TPU
  with sp=1 it defaults to the **Pallas flash-attention kernel**
  (``ops.pallas.flash_attention``), and to plain MXU attention otherwise;
* LayerNorms default to the **fused Pallas kernel**
  (``ops.pallas.layernorm``) on TPU, plain XLA-fused math elsewhere;
* Pallas calls are wrapped in ``jax.shard_map`` whenever the mesh shards
  the batch/heads axes — the SPMD partitioner cannot split an opaque
  custom call, so without this a dp>1 mesh would replicate the kernel;
* bfloat16 compute, float32 params and softmax accumulation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pyspark_tf_gke_tpu.ops.attention import (
    dot_product_attention,
    ring_attention,
    ulysses_attention,
)
from pyspark_tf_gke_tpu.models.embedding import TokenEmbed
from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES, ambient_mesh


# Shared flash-vs-dense dispatch constants (ops/pallas/common.py) —
# re-exported here for callers that think in model terms.
from pyspark_tf_gke_tpu.ops.pallas.common import FLASH_MIN_SEQ, on_tpu  # noqa: E402


def resolve_use_flash(cfg: "BertConfig", seq_len: int) -> bool:
    """The model's flash-vs-dense dispatch, resolved for a sequence
    length. Single source of truth: the decoders import it too."""
    if cfg.use_flash is not None:
        return cfg.use_flash
    return on_tpu() and seq_len >= FLASH_MIN_SEQ


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # Pallas flash attention. None = auto (per path: sp=1 uses the plain
    # kernel at seq >= FLASH_MIN_SEQ on TPU; sp>1 ring/Ulysses apply their
    # own thresholds). Explicit True/False forces the kernel on/off on
    # every path; tests force True with the interpret-mode kernel.
    use_flash: Optional[bool] = None
    # Pallas fused LayerNorm. None = auto: on for TPU backends.
    use_fused_ln: Optional[bool] = None
    # Sequence-parallel implementation when the mesh has sp>1:
    # "ring" (ppermute ring, unbounded S) or "ulysses" (all-to-all,
    # needs heads divisible by sp; cheaper at moderate S).
    sp_impl: str = "ring"
    # Mixture-of-Experts: num_experts > 0 replaces the dense FFN of every
    # ``moe_every``-th layer with an expert-parallel MoELayer (models/moe.py).
    num_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    capacity_factor: float = 1.25

    def __post_init__(self):
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_impl must be 'ring' or 'ulysses', got {self.sp_impl!r}"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _dense(features, kernel_axes, cfg: BertConfig, name=None,
           use_bias: bool = True):
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=cfg.dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), kernel_axes
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (kernel_axes[-1],)
        ),
        name=name,
    )


def _data_shards(mesh: Optional[Mesh], *axes: str) -> int:
    if mesh is None:
        return 1
    out = 1
    for a in axes:
        out *= mesh.shape.get(a, 1)
    return out


class FusedLayerNorm(nn.Module):
    """LayerNorm on the Pallas fused kernel (``ops.pallas.layernorm``) —
    one VMEM pass instead of several HBM round-trips. Same param names
    ("scale"/"bias") and init as ``nn.LayerNorm``, so checkpoints are
    interchangeable. Falls back to plain jnp math (identical closed form,
    f32 statistics) off-TPU or when ``use_fused=False``."""

    epsilon: float = 1e-12
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    use_fused: Optional[bool] = None

    @nn.compact
    def __call__(self, x, residual=None):
        """``residual`` is summed into ``x`` *inside* the fused kernel
        (``y = LN(x + residual)``) — the transformer-block pattern; the
        unfused path adds it in-graph (XLA fuses that itself)."""
        d = x.shape[-1]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("norm",)),
            (d,), jnp.float32,
        )
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("norm",)),
            (d,), jnp.float32,
        )
        fused = self.use_fused if self.use_fused is not None else on_tpu()
        if fused:
            from pyspark_tf_gke_tpu.ops.pallas.layernorm import fused_layernorm

            if self.mesh is not None and self.mesh.size > 1:
                # Any multi-device jit: Mosaic kernels are never
                # partitioned automatically. LN is row-wise: shard rows
                # (batch and, if 3D, seq) and run the kernel per shard
                # (on a tp-only mesh every shard runs all rows).
                # Scale/bias replicated; the optional residual shards
                # like x.
                row_spec = (
                    P(DATA_AXES, "sp", None) if x.ndim == 3 else P(DATA_AXES, None)
                )
                has_res = residual is not None
                args = (x, residual, scale, bias) if has_res else (x, scale, bias)
                specs = ((row_spec,) * (2 if has_res else 1)) + (P(None), P(None))

                def ln_shard(*a):
                    xx, rr = (a[0], a[1]) if has_res else (a[0], None)
                    return fused_layernorm(xx, a[-2], a[-1], eps=self.epsilon,
                                           residual=rr)

                y = shard_map(ln_shard, mesh=self.mesh, in_specs=specs,
                                  out_specs=row_spec, check_vma=False)(*args)
            else:
                y = fused_layernorm(x, scale, bias, eps=self.epsilon,
                                    residual=residual)
            return y.astype(self.dtype)
        if residual is not None:
            x = x + residual
        # Row-wise math stays on the BATCH sharding end to end: the
        # mean/variance broadcasts back to x's shape would otherwise
        # inherit the consumer matmul's contracting-dim (embed over
        # fsdp, transposed device order) sharding through propagation,
        # a reshard current XLA can only do by involuntary full
        # rematerialization (the regression oracle in
        # tests/test_embedding.py). Pinning the broadcast results makes
        # the one reshard happen on the LN OUTPUT, an ordinary tensor.
        def pin(t):
            from jax.sharding import NamedSharding

            mesh = ambient_mesh()
            if mesh is None:
                return t
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(
                    mesh, P(DATA_AXES, *([None] * (t.ndim - 1)))))

        xf = pin(x.astype(jnp.float32))
        mean = xf.mean(-1, keepdims=True)
        xc = pin(xf - mean)
        var = (xc * xc).mean(-1, keepdims=True)
        y = pin(xc * jax.lax.rsqrt(var + self.epsilon)) * scale[None, :] \
            + bias[None, :]
        return y.astype(self.dtype)


def _layernorm(cfg: BertConfig, mesh: Optional[Mesh] = None, name=None):
    return FusedLayerNorm(
        epsilon=cfg.layer_norm_eps,
        dtype=cfg.dtype,
        mesh=mesh,
        use_fused=cfg.use_fused_ln,
        name=name,
    )


class BertSelfAttention(nn.Module):
    cfg: BertConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, hidden, mask):
        cfg = self.cfg
        b, s, _ = hidden.shape
        h, d = cfg.num_heads, cfg.head_dim

        q = _dense(cfg.hidden_size, ("embed", "mlp"), cfg, name="query")(hidden)
        k = _dense(cfg.hidden_size, ("embed", "mlp"), cfg, name="key")(hidden)
        v = _dense(cfg.hidden_size, ("embed", "mlp"), cfg, name="value")(hidden)
        q = q.reshape(b, s, h, d)
        k = k.reshape(b, s, h, d)
        v = v.reshape(b, s, h, d)
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
        k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "head_dim"))
        v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "head_dim"))

        use_sp = self.mesh is not None and self.mesh.shape.get("sp", 1) > 1
        use_flash = resolve_use_flash(cfg, s)
        if use_sp:
            sp_fn = ulysses_attention if cfg.sp_impl == "ulysses" else ring_attention
            # Pass the raw tri-state: explicit True/False wins; None lets
            # each sp impl auto-decide with its own (per-shard vs global)
            # sequence-length knowledge.
            out = sp_fn(q, k, v, self.mesh, kv_mask=mask, axis="sp",
                        use_flash=cfg.use_flash)
        elif use_flash:
            from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention

            if _data_shards(self.mesh, "dp", "fsdp", "tp") > 1:
                # Kernel per shard: batch over the data axes, heads over
                # tp. Without this the partitioner replicates the opaque
                # Pallas custom call on every chip.
                qkv_spec = P(DATA_AXES, None, "tp", None)
                fn = shard_map(
                    lambda qq, kk, vv, mm: flash_attention(qq, kk, vv, kv_mask=mm),
                    mesh=self.mesh,
                    in_specs=(qkv_spec,) * 3 + (P(DATA_AXES, None),),
                    out_specs=qkv_spec,
                    check_vma=False,
                )
                out = fn(q, k, v, mask)
            else:
                out = flash_attention(q, k, v, kv_mask=mask)
        else:
            out = dot_product_attention(q, k, v, mask=mask[:, None, None, :])
        out = out.reshape(b, s, cfg.hidden_size)
        out = _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="out")(out)
        return out


class BertLayer(nn.Module):
    cfg: BertConfig
    mesh: Optional[Mesh] = None
    use_moe: bool = False

    @nn.compact
    def __call__(self, hidden, mask):
        cfg = self.cfg
        attn_out = BertSelfAttention(cfg, self.mesh, name="attention")(hidden, mask)
        hidden = _layernorm(cfg, self.mesh, name="ln_attn")(attn_out, residual=hidden)
        if self.use_moe:
            from pyspark_tf_gke_tpu.models.moe import MoELayer

            mlp, aux = MoELayer(
                num_experts=cfg.num_experts,
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.intermediate_size,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.capacity_factor,
                dtype=cfg.dtype,
                name="moe",
            )(hidden)
        else:
            mlp = _dense(cfg.intermediate_size, ("embed", "mlp"), cfg, name="mlp_in")(hidden)
            mlp = nn.gelu(mlp, approximate=True)
            mlp = _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="mlp_out")(mlp)
            aux = jnp.zeros((), jnp.float32)
        hidden = _layernorm(cfg, self.mesh, name="ln_mlp")(mlp, residual=hidden)
        return nn.with_logical_constraint(hidden, ("batch", "seq", "embed")), aux


class BertEncoder(nn.Module):
    cfg: BertConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 train: bool = True):
        cfg = self.cfg
        b, s = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((b, s), dtype=bool)
        else:
            attention_mask = attention_mask.astype(bool)
        if token_type_ids is None:
            token_type_ids = jnp.zeros((b, s), dtype=jnp.int32)

        # One-hot matmul embeds (models/embedding.py): nn.Embed's gather
        # backward forces an involuntary full remat on dp×fsdp×tp meshes.
        embed = TokenEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
            name="word_embeddings",
        )
        pos_embed = TokenEmbed(
            cfg.max_position_embeddings, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (None, "embed")),
            name="position_embeddings",
        )
        type_embed = TokenEmbed(
            cfg.type_vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), (None, "embed")),
            name="token_type_embeddings",
        )
        positions = jnp.arange(s)[None, :]
        # one_hot only when a gradient will flow (see models/embedding.py);
        # eval-only forwards keep the cheap gather.
        hidden = (embed(input_ids, one_hot=train)
                  + pos_embed(positions, one_hot=train)
                  + type_embed(token_type_ids, one_hot=train))
        hidden = _layernorm(cfg, self.mesh, name="ln_embed")(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "seq", "embed"))

        layer_cls = BertLayer
        if cfg.remat:
            layer_cls = nn.remat(BertLayer, static_argnums=())
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            use_moe = cfg.num_experts > 0 and (i + 1) % cfg.moe_every == 0
            hidden, aux = layer_cls(cfg, self.mesh, use_moe, name=f"layer_{i}")(
                hidden, attention_mask
            )
            aux_total = aux_total + aux
        return hidden, aux_total


class BertForPretraining(nn.Module):
    """Encoder + MLM head + sequence-level classifier (doubles as the
    fine-tune head for config 5)."""

    cfg: BertConfig
    mesh: Optional[Mesh] = None
    num_labels: int = 2

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 train: bool = True):
        cfg = self.cfg
        hidden, aux_loss = BertEncoder(cfg, self.mesh, name="encoder")(
            input_ids, token_type_ids, attention_mask, train=train
        )
        mlm = _dense(cfg.hidden_size, ("embed", "embed_out"), cfg, name="mlm_transform")(hidden)
        mlm = nn.gelu(mlm, approximate=True)
        mlm = _layernorm(cfg, self.mesh, name="mlm_ln")(mlm)
        mlm_logits = _dense(cfg.vocab_size, ("embed", "vocab"), cfg, name="mlm_head")(mlm)
        pooled = jnp.tanh(
            _dense(cfg.hidden_size, ("embed", "embed_out"), cfg, name="pooler")(hidden[:, 0])
        )
        cls_logits = _dense(self.num_labels, ("embed", None), cfg, name="classifier")(pooled)
        return {
            "mlm_logits": mlm_logits.astype(jnp.float32),
            "cls_logits": cls_logits.astype(jnp.float32),
            "aux_loss": aux_loss,
        }
