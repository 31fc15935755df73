"""A decoder whose layers differ in kind: per layer an attention kind
(``kda`` linear attention or ``mla`` latent attention without positions) and
an FFN kind (``dense`` SwiGLU or ``experts``, one chip's share of a dropless
expert layer). Written for the Kimi-Linear family
(``moonshotai/Kimi-Linear-48B-A3B-Instruct``): all norms RMSNorm, pre-norm
blocks, no biases, no embedding scale, untied head.

* **KDA** (``KDAAttention``): ``q, k = L2norm(silu(conv4(x W)))``, ``v =
  silu(conv4(x W_v))`` with a depthwise causal convolution of 4 taps; a
  per-channel log-decay ``g = -exp(A_log) * softplus(x W_fa W_fb + dt_bias)``
  and a step size ``beta = sigmoid(x W_b)``, both float32; the chunked
  recurrence of ``ops/linear_attention.py::kda`` (Pallas kernels ``kda_fwd`` /
  ``kda_bwd`` on the TPU); ``RMSNorm(o) * sigmoid(x W_ga W_gb)`` and the
  output projection. Everything here stays ``[B, S, heads * 128]``, and the
  projections' outputs go to ``kda`` as they are, with the taps: what is per
  head and per row (the convolution and its SiLU, the L2 norms, ``beta``'s
  products, the output's RMS) is ``kda``'s.
* **MLA without positions** (``MLAAttention``): queries of ``qk_nope + qk_rope``
  = 192 a head, a 512-wide normalised latent expanded to 128 of key and 128
  of value a head, 64 more key columns shared by all heads and not rotated;
  causal softmax attention through the flash kernels, whose values may be
  narrower than their keys. Training uses this expanded form.
* **Experts**: ``models/moe.py::HeldExpertsLayer``; its counters are sown
  into the ``counters`` collection and summed here
  (:meth:`HybridLM.step_counters`).

The training path only: ``decode=True`` / ``prefill=True`` raise (the
engine's cache has neither latent pages nor a per-slot recurrent state yet:
ROADMAP Reach 3 and 4), and so do ``segment_ids`` (packed documents would
have to reset KDA's state inside a row). The call contract is ``CausalLM``'s,
so ``causal_lm_task`` and ``Trainer`` take the model as they take that one.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pyspark_tf_gke_tpu.models.bert import (_data_shards, _dense,
                                            resolve_use_flash)
from pyspark_tf_gke_tpu.models.causal_lm import RMSNorm
from pyspark_tf_gke_tpu.models.embedding import TokenEmbed
from pyspark_tf_gke_tpu.models.moe import HeldExpertsLayer, SwiGLU
from pyspark_tf_gke_tpu.ops.attention import dot_product_attention
from pyspark_tf_gke_tpu.ops.linear_attention import kda

NOT_SERVED = ("HybridLM has no decode or prefill path: the engine's cache holds "
              "neither latent KV pages nor a per-slot recurrent state "
              "(ROADMAP Reach 3 and 4)")


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    vocab_size: int
    hidden_size: int
    attention: Tuple[str, ...]            # per layer: "kda" | "mla"
    ffn: Tuple[str, ...]                  # per layer: "dense" | "experts"
    # KDA
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    gate_rank: int = 128
    # MLA
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    # FFNs
    intermediate_size: int = 9216
    expert_intermediate_size: int = 1024
    num_experts: int = 256                # the router's width
    experts_held: Tuple[int, int] = (0, 8)   # (first, count) this chip holds
    experts_per_token: int = 8
    shared_experts: int = 1
    route_scale: float = 2.446
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    use_flash: Optional[bool] = None      # None = auto (TPU, seq >= FLASH_MIN_SEQ)

    def __post_init__(self):
        if len(self.attention) != len(self.ffn):
            raise ValueError("attention and ffn name one kind per layer each")
        for kinds, known in ((self.attention, ("kda", "mla")),
                             (self.ffn, ("dense", "experts"))):
            bad = set(kinds) - set(known)
            if bad:
                raise ValueError(f"unknown layer kind {sorted(bad)}; know {known}")

    @property
    def num_layers(self) -> int:
        return len(self.attention)


def config_from_file(path_or_dict, dtype=jnp.bfloat16,
                     remat: bool = False) -> HybridLMConfig:
    """A :class:`HybridLMConfig` from a configuration file with the family's
    published keys (``benchmark/configs/kimi-linear-48b-a3b.json``):
    ``num_experts`` there counts the experts held on this chip, and
    ``published.num_experts`` is the router's width."""
    c = path_or_dict
    if not isinstance(c, dict):
        with open(c) as f:
            c = json.load(f)
    lin = c["linear_attn_config"]
    layers = range(1, c["num_hidden_layers"] + 1)
    bad = [n for n in layers if (n in lin["kda_layers"]) == (n in lin["full_attn_layers"])]
    if bad:
        raise ValueError(f"layers {bad} are not in exactly one of kda_layers, full_attn_layers")
    published = c.get("published", {})
    return HybridLMConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        attention=tuple("kda" if n in lin["kda_layers"] else "mla" for n in layers),
        ffn=tuple("dense" if n <= c["first_k_dense_replace"] else "experts"
                  for n in layers),
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        num_heads=c["num_attention_heads"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], kv_lora_rank=c["kv_lora_rank"],
        intermediate_size=c["intermediate_size"],
        expert_intermediate_size=c["moe_intermediate_size"],
        num_experts=published.get("num_experts", c["num_experts"]),
        experts_held=(c.get("deployment", {}).get("experts_held_first", 0),
                      c["num_experts"]),
        experts_per_token=c["num_experts_per_token"],
        shared_experts=c["num_shared_experts"],
        route_scale=float(c["routed_scaling_factor"]),
        layer_norm_eps=float(c["rms_norm_eps"]), dtype=dtype, remat=remat)


def _per_shard(fn, mesh: Optional[Mesh], *specs):
    """``fn`` as it is on one device; under a mesh that shards the batch or
    the heads, per shard (Mosaic kernels are never partitioned)."""
    if _data_shards(mesh, "dp", "fsdp", "tp") <= 1:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs[0],
                     check_vma=False)


class CausalConv(nn.Module):
    """The taps ``kernel [size, features]`` (float32, no bias) of a depthwise
    causal convolution over time, the last on the current token. ``kda``
    convolves with them, on the rows its kernels hold."""

    size: int
    features: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(stddev=0.02),
                          (self.size, self.features), jnp.float32)


class _Scale(nn.Module):
    """A norm's ``scale [features]`` (ones, float32) where the statistics are
    taken elsewhere: ``RMSNorm``'s leaf by name, shape and partitioning."""

    features: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("embed",)), (self.features,), jnp.float32)


class KDAAttention(nn.Module):
    cfg: HybridLMConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, hidden):
        from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES

        cfg = self.cfg
        heads, dim = cfg.kda_heads, cfg.kda_head_dim
        wide = heads * dim

        def dense(features, name):
            return _dense(features, ("embed", "mlp"), cfg, name=name, use_bias=False)

        # q, k, v as their projections write them: the short convolution and the
        # SiLU after it are ``kda``'s, with these taps
        q, k, v = (dense(wide, f"{name}_proj")(hidden) for name in "qkv")
        taps = [CausalConv(cfg.conv_size, wide, name=f"{name}_conv")() for name in "qkv"]
        # the decay, beta and the output gate in float32
        a_log = self.param("A_log", nn.initializers.zeros_init(), (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros_init(), (wide,), jnp.float32)
        f = dense(wide, "f_b")(dense(cfg.gate_rank, "f_a")(hidden)).astype(jnp.float32)
        g = jnp.repeat(-jnp.exp(a_log), dim) * jax.nn.softplus(f + dt_bias)
        beta = jax.nn.sigmoid(dense(heads, "b_proj")(hidden).astype(jnp.float32))

        def attend(q, k, v, g, beta, *taps):  # under a mesh: a shard's heads
            return kda(q, k, v, g, beta, heads=beta.shape[-1], eps=cfg.layer_norm_eps,
                       conv=taps)

        o = _per_shard(attend, self.mesh, *[P(DATA_AXES, None, "tp")] * 5,
                       *[P(None, "tp")] * 3)(q, k, v, g, beta, *taps)
        gate = dense(wide, "g_b")(dense(cfg.gate_rank, "g_a")(hidden))
        scale = jnp.tile(_Scale(dim, name="o_norm")(), heads)
        o = o.astype(jnp.float32) * scale * jax.nn.sigmoid(gate.astype(jnp.float32))
        return _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="o_proj",
                      use_bias=False)(o.astype(cfg.dtype))


class MLAAttention(nn.Module):
    cfg: HybridLMConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        b, s, _ = hidden.shape
        heads, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dv, rank = cfg.v_head_dim, cfg.kv_lora_rank

        def dense(features, name):
            return _dense(features, ("embed", "mlp"), cfg, name=name, use_bias=False)

        q = dense(heads * (nope + rope), "q_proj")(hidden).reshape(b, s, heads, nope + rope)
        kva = dense(rank + rope, "kv_a")(hidden)
        latent = RMSNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="kv_norm")(kva[..., :rank])
        k_pe = kva[..., rank:]                       # shared by all heads, not rotated
        kvb = dense(heads * (nope + dv), "kv_b")(latent).reshape(b, s, heads, nope + dv)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_pe[:, :, None, :], (b, s, heads, rope))],
            axis=-1)
        out = self._causal_attend(q, k, kvb[..., nope:])
        return _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="o_proj",
                      use_bias=False)(out.reshape(b, s, heads * dv))

    def _causal_attend(self, q, k, v):
        from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES

        if not resolve_use_flash(self.cfg, q.shape[1]):
            return dot_product_attention(q, k, v, causal=True)
        from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention

        spec = P(DATA_AXES, None, "tp", None)
        return _per_shard(lambda qq, kk, vv: flash_attention(qq, kk, vv, causal=True),
                          self.mesh, spec, spec, spec)(q, k, v)


class HybridBlock(nn.Module):
    cfg: HybridLMConfig
    mesh: Optional[Mesh]
    layer: int                                       # 0-based

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        norm = lambda name: RMSNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name=name)
        attend = KDAAttention if cfg.attention[self.layer] == "kda" else MLAAttention
        hidden = hidden + attend(cfg, self.mesh, name="attention")(norm("ln_attn")(hidden))
        m = norm("ln_mlp")(hidden)
        if cfg.ffn[self.layer] == "dense":
            return hidden + SwiGLU(cfg.hidden_size, cfg.intermediate_size, cfg.dtype,
                                   name="mlp")(m)
        out, counters = HeldExpertsLayer(
            num_experts=cfg.num_experts, held=cfg.experts_held,
            top_k=cfg.experts_per_token, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.expert_intermediate_size,
            route_scale=cfg.route_scale, shared=cfg.shared_experts,
            dtype=cfg.dtype, name="mlp")(m)
        for name, value in counters.items():
            self.sow("counters", name, value)
        return hidden + out


class HybridLM(nn.Module):
    """``input_ids [B, S]`` -> logits ``[B, S, vocab]`` (float32), or the
    final-norm hidden states with ``return_hidden`` (the chunked loss applies
    the head itself). Expert layers sow their counters into ``counters``."""

    cfg: HybridLMConfig
    mesh: Optional[Mesh] = None
    sows_counters = True

    @nn.compact
    def __call__(self, input_ids, *, decode: bool = False, prefill: bool = False,
                 positions: Optional[jnp.ndarray] = None,
                 segment_ids: Optional[jnp.ndarray] = None,
                 return_hidden: bool = False, train: bool = True,
                 slot_decode: bool = False):
        cfg = self.cfg
        if decode or prefill or slot_decode:
            raise NotImplementedError(NOT_SERVED)
        if segment_ids is not None:
            raise NotImplementedError(
                "HybridLM takes no segment_ids yet: packed documents would have "
                "to reset KDA's state inside a row (ROADMAP Reach 4)")
        hidden = TokenEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
            name="wte")(input_ids, one_hot=train)
        block_cls = nn.remat(HybridBlock) if cfg.remat else HybridBlock
        for i in range(cfg.num_layers):
            hidden = block_cls(cfg, self.mesh, i, name=f"layer_{i}")(hidden)
        hidden = RMSNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ln_final")(hidden)
        head = _dense(cfg.vocab_size, ("embed", "vocab"), cfg, name="lm_head",
                      use_bias=False)
        if return_hidden:
            head(hidden[:, :1])          # the head's params exist under init
            return hidden
        return head(hidden).astype(jnp.float32)

    @staticmethod
    def step_counters(sown) -> dict:
        """The step's counters from the ``counters`` collection: assignments
        to held experts summed over the expert layers, and the busiest held
        expert's load in any of them."""
        layers = [c for c in sown.values() if "held_assignments" in c]
        if not layers:
            return {}
        # ``sow`` keeps a tuple a name: one value a call
        return {"moe_held_assignments": sum(c["held_assignments"][0] for c in layers),
                "moe_held_load_max": jnp.max(jnp.stack(
                    [c["held_load_max"][0] for c in layers]))}
