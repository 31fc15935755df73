"""A decoder whose layers differ in kind: per layer an attention kind
(``kda`` linear attention, ``mla`` latent attention without positions,
``mamba2`` a state-space mixer, ``gqa`` grouped-query attention without
positions, ``gated_sliding`` / ``gated_full`` gated grouped-query attention
inside a window with rotary positions / global without positions, or
``none``) and an FFN kind (``dense`` SwiGLU, ``experts``, one chip's share of a
dropless expert layer, or ``none``); a layer has one of the two or both, each
behind a pre-norm of its own (and, with ``sandwich_norms``, a norm of its own
after it, before the residual sum). Written for three families: the
Kimi-Linear one (``moonshotai/Kimi-Linear-48B-A3B-Instruct``: every layer
attention then FFN), Nemotron-H (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B``,
``model_type`` ``nemotron_h``: every layer one mixer, ``mamba2``, ``gqa`` or
``experts`` by the letter of ``hybrid_override_pattern``) and AFMoE
(``arcee-ai/Trinity-Mini``, ``model_type`` ``afmoe``: every layer attention
then FFN, ``gated_sliding`` or ``gated_full`` by ``layer_types``, sandwich
norms, the embedding times ``sqrt(hidden_size)``). All norms RMSNorm, untied
head, no bias but the Mamba-2 convolution's.

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
* **Mamba-2** (``Mamba2Mixer``): ``[z | xBC | dt] = u W_in``; ``xBC =
  silu(conv4(xBC) + b)`` (depthwise, causal); ``xBC`` splits into ``x`` (heads
  of 64), ``B`` and ``C`` (groups of 128 each, a group shared by its heads);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, float32; the chunked
  scan of ``ops/state_space.py::ssd`` (Pallas kernels ``ssd_fwd`` / ``ssd_bwd``
  on the TPU); ``RMSNorm_group(y * silu(z))`` (the mean square over each
  group's channels) and the output projection. The convolution, SiLU,
  softplus and the gated norm are XLA's.
* **GQA without positions** (``GQAAttention``): 32 query heads of 128 on 2
  key-value heads, repeated to 32 before the flash kernels; no rotary
  embedding (the published ``nemotron_h`` applies none).
* **Gated GQA, in a window or global** (``GatedAttention``): 32 query heads of
  128 on 4 key-value heads; ``q`` and ``k`` RMS-normed a head (a learned scale
  of 128 each); on a ``gated_sliding`` layer rotated (``causal_lm.py::
  apply_rope``, the half-rotation convention, all 128 columns) and confined to
  ``sliding_window`` keys, the row's own among them, which the flash kernels
  take as their ``window`` (launches named ``window_flash_*``); on a
  ``gated_full`` layer neither; the heads' outputs times ``sigmoid(x W_g)``
  before the output projection.
* **Experts**: ``models/moe.py::HeldExpertsLayer`` (``swiglu`` experts for
  Kimi-Linear and AFMoE, ``relu2`` ones with a shared expert of its own width
  for Nemotron-H), which walks the assignments to the experts this chip holds
  in steps of an eighth of their mean load, as many as the load asks for; its
  counters (the held assignments, the busiest held expert's, the rows walked)
  are sown into the ``counters`` collection and summed here
  (:meth:`HybridLM.step_counters`).

The training path only, for every kind: ``decode=True`` / ``prefill=True``
raise (the engine's cache has neither latent pages nor a per-slot recurrent
state, KDA's or the scan's, yet: ROADMAP Reach 3 and 4), and so do
``segment_ids`` (packed documents would have to reset KDA's and the scan's
state inside a row). The call contract is ``CausalLM``'s, so
``causal_lm_task`` and ``Trainer`` take the model as they take that one.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pyspark_tf_gke_tpu.models.bert import (_data_shards, _dense,
                                            resolve_use_flash)
from pyspark_tf_gke_tpu.models.causal_lm import RMSNorm, apply_rope
from pyspark_tf_gke_tpu.models.embedding import TokenEmbed
from pyspark_tf_gke_tpu.models.moe import HeldExpertsLayer, SwiGLU
from pyspark_tf_gke_tpu.ops.attention import dot_product_attention
from pyspark_tf_gke_tpu.ops.linear_attention import kda
from pyspark_tf_gke_tpu.ops.pallas.scope import KERNEL_RESULT_NAMES, part_scope
from pyspark_tf_gke_tpu.ops.state_space import ssd

NOT_SERVED = ("HybridLM has no decode or prefill path, whatever its layers' kinds: the "
              "engine's cache holds neither latent KV pages nor a per-slot recurrent "
              "state, KDA's or the state-space scan's (ROADMAP Reach 3 and 4), and its "
              "page allocator knows one kind of layer, not window layers beside global "
              "ones (Reach 2)")
# kimi_linear files give kda / mla, nemotron_h files mamba2 / gqa / none, afmoe
# files gated_sliding / gated_full (``config_from_file``)
ATTENTION_KINDS = ("kda", "mla", "mamba2", "gqa", "gated_sliding", "gated_full", "none")
FFN_KINDS = ("dense", "experts", "none")


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    vocab_size: int
    hidden_size: int
    attention: Tuple[str, ...]            # per layer: one of ATTENTION_KINDS
    ffn: Tuple[str, ...]                  # per layer: one of FFN_KINDS
    # KDA
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    gate_rank: int = 128
    # Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_chunk: int = 128
    # a fresh ``dt_bias`` is ``softplus^-1(dt)``, ``dt`` log-uniform in [min, max], not under the floor
    time_step_min: float = 1e-3
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # MLA and GQA
    num_heads: int = 32
    kv_heads: int = 2                     # GQA, gated or not
    head_dim: int = 128                   # GQA, gated or not
    sliding_window: int = 2048            # gated_sliding: keys a row sees, its own among them
    rope_theta: float = 10000.0           # gated_sliding
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
    shared_intermediate_size: int = 0     # 0 = shared_experts routed widths
    expert_activation: str = "swiglu"     # "swiglu" | "relu2"
    route_scale: float = 2.446
    sandwich_norms: bool = False          # a norm after each mixer and FFN too
    scale_embedding: bool = False         # the embedding times sqrt(hidden_size)
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    use_flash: Optional[bool] = None      # None = auto (TPU, seq >= FLASH_MIN_SEQ)

    def __post_init__(self):
        if len(self.attention) != len(self.ffn):
            raise ValueError("attention and ffn name one kind per layer each")
        for kinds, known in ((self.attention, ATTENTION_KINDS), (self.ffn, FFN_KINDS)):
            bad = set(kinds) - set(known)
            if bad:
                raise ValueError(f"unknown layer kind {sorted(bad)}; know {known}")
        empty = [i for i, kinds in enumerate(zip(self.attention, self.ffn))
                 if kinds == ("none", "none")]
        if empty:
            raise ValueError(f"layers {empty} have neither an attention nor an FFN kind")

    @property
    def num_layers(self) -> int:
        return len(self.attention)


def config_from_file(path_or_dict, dtype=jnp.bfloat16,
                     remat: bool = False) -> HybridLMConfig:
    """A :class:`HybridLMConfig` from a configuration file with its family's
    published keys, the family by ``model_type``: ``kimi_linear``
    (``benchmark/configs/kimi-linear-48b-a3b.json``), ``nemotron_h``
    (``benchmark/configs/nemotron-3-nano-30b-a3b.json``) or ``afmoe``
    (``benchmark/configs/trinity-mini.json``). The key that counts
    the routed experts (``num_experts`` / ``n_routed_experts``) there counts
    those held on this chip, and ``published`` has the router's width."""
    c = path_or_dict
    if not isinstance(c, dict):
        with open(c) as f:
            c = json.load(f)
    if c.get("model_type") == "nemotron_h":
        return _nemotron_h_config(c, dtype, remat)
    if c.get("model_type") == "afmoe":
        return _afmoe_config(c, dtype, remat)
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


def _nemotron_h_config(c: dict, dtype, remat: bool) -> HybridLMConfig:
    """``hybrid_override_pattern`` names one mixer a layer: ``M`` Mamba-2, ``*``
    attention, ``E`` experts."""
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"] or set(pattern) - set("M*E"):
        raise ValueError(f"hybrid_override_pattern {pattern!r} does not name "
                         f"{c['num_hidden_layers']} layers by M, * and E")
    if c["mlp_hidden_act"] != "relu2":
        raise ValueError(f"nemotron_h experts are relu2 here, not {c['mlp_hidden_act']!r}")
    return HybridLMConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        attention=tuple({"M": "mamba2", "*": "gqa", "E": "none"}[k] for k in pattern),
        ffn=tuple("experts" if k == "E" else "none" for k in pattern),
        mamba_heads=c["mamba_num_heads"], mamba_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], ssm_groups=c["n_groups"], ssm_chunk=c["chunk_size"],
        time_step_min=float(c["time_step_min"]), time_step_max=float(c["time_step_max"]),
        time_step_floor=float(c["time_step_floor"]),
        conv_size=c["conv_kernel"],
        num_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        expert_intermediate_size=c["moe_intermediate_size"],
        num_experts=c.get("published", {}).get("n_routed_experts", c["n_routed_experts"]),
        experts_held=(c.get("deployment", {}).get("experts_held_first", 0),
                      c["n_routed_experts"]),
        experts_per_token=c["num_experts_per_tok"],
        shared_experts=c["n_shared_experts"],
        shared_intermediate_size=c["moe_shared_expert_intermediate_size"] * c["n_shared_experts"],
        expert_activation="relu2",
        route_scale=float(c["routed_scaling_factor"]),
        layer_norm_eps=float(c["layer_norm_epsilon"]), dtype=dtype, remat=remat)


def _afmoe_config(c: dict, dtype, remat: bool) -> HybridLMConfig:
    """``layer_types`` names each layer's attention (``sliding_attention`` /
    ``full_attention``); the first ``num_dense_layers`` have a dense FFN, the
    others experts."""
    kinds = {"sliding_attention": "gated_sliding", "full_attention": "gated_full"}
    types = c["layer_types"]
    if len(types) != c["num_hidden_layers"] or set(types) - set(kinds):
        raise ValueError(f"layer_types {types!r} does not name {c['num_hidden_layers']} "
                         f"layers by {sorted(kinds)}")
    if c["score_func"] != "sigmoid" or not c["route_norm"] or c["hidden_act"] != "silu":
        raise ValueError("afmoe experts are SwiGLU behind a sigmoid router with "
                         "renormalised weights here (score_func, route_norm, hidden_act)")
    if c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("afmoe routing is not grouped here (n_group, topk_group)")
    return HybridLMConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        attention=tuple(kinds[t] for t in types),
        ffn=tuple("dense" if n < c["num_dense_layers"] else "experts"
                  for n in range(len(types))),
        num_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], sliding_window=c["sliding_window"],
        rope_theta=float(c["rope_theta"]),
        intermediate_size=c["intermediate_size"],
        expert_intermediate_size=c["moe_intermediate_size"],
        num_experts=c.get("published", {}).get("num_experts", c["num_experts"]),
        experts_held=(c.get("deployment", {}).get("experts_held_first", 0),
                      c["num_experts"]),
        experts_per_token=c["num_experts_per_tok"],
        shared_experts=c["num_shared_experts"],
        route_scale=float(c["route_scale"]),
        sandwich_norms=True, scale_embedding=bool(c["mup_enabled"]),
        layer_norm_eps=float(c["rms_norm_eps"]), dtype=dtype, remat=remat)


def _per_shard(fn, mesh: Optional[Mesh], *specs):
    """``fn`` as it is on one device; under a mesh that shards the batch or
    the heads, per shard (Mosaic kernels are never partitioned)."""
    if _data_shards(mesh, "dp", "fsdp", "tp") <= 1:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs[0],
                     check_vma=False)


def _symmetric_uniform(bound: float):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


class CausalConv(nn.Module):
    """The taps ``kernel [size, features]`` (float32) of a depthwise causal
    convolution over time, the last on the current token; with ``use_bias``
    ``(kernel, bias [features])``. ``kda`` convolves with the taps on the rows
    its kernels hold; ``Mamba2Mixer`` convolves by itself, and draws taps and
    bias as a depthwise ``Conv1d`` is drawn where it was published:
    ``U(-size^-1/2, size^-1/2)`` (``published_init``)."""

    size: int
    features: int
    use_bias: bool = False
    published_init: bool = False

    @nn.compact
    def __call__(self):
        draw = _symmetric_uniform(self.size ** -0.5) if self.published_init else None
        kernel = self.param("kernel", draw or nn.initializers.normal(stddev=0.02),
                            (self.size, self.features), jnp.float32)
        if not self.use_bias:
            return kernel
        return kernel, self.param("bias", draw or nn.initializers.zeros_init(),
                                  (self.features,), jnp.float32)


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
        return _flash_or_dense(self.cfg, self.mesh, q, k, v)


def _flash_or_dense(cfg, mesh, q, k, v, window=None):
    """Causal softmax attention of ``q, k, v [B, S, H, D]``, inside ``window``
    keys where one is given: the flash kernels where ``resolve_use_flash`` says
    so (per shard under a mesh), else dense. Called from a module's
    ``_causal_attend``, whose name the launches carry."""
    from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES

    if not resolve_use_flash(cfg, q.shape[1]):
        return dot_product_attention(q, k, v, causal=True, window=window)
    from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention

    spec = P(DATA_AXES, None, "tp", None)
    return _per_shard(
        lambda qq, kk, vv: flash_attention(qq, kk, vv, causal=True, window=window),
        mesh, spec, spec, spec)(q, k, v)


class GQAAttention(nn.Module):
    cfg: HybridLMConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        b, s, _ = hidden.shape
        heads, kv_heads, dim = cfg.num_heads, cfg.kv_heads, cfg.head_dim

        def dense(features, name):
            return _dense(features, ("embed", "mlp"), cfg, name=name, use_bias=False)

        q = dense(heads * dim, "q_proj")(hidden).reshape(b, s, heads, dim)
        # the training pass: K and V repeated to the query heads, so that the
        # kernels the other decoders use apply (``models/causal_lm.py``)
        k, v = (jnp.repeat(dense(kv_heads * dim, f"{name}_proj")(hidden).reshape(
            b, s, kv_heads, dim), heads // kv_heads, axis=2) for name in "kv")
        out = self._causal_attend(q, k, v)
        return _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="o_proj",
                      use_bias=False)(out.reshape(b, s, heads * dim))

    def _causal_attend(self, q, k, v):
        return _flash_or_dense(self.cfg, self.mesh, q, k, v)


class GatedAttention(nn.Module):
    """AFMoE's attention (module docstring): ``sliding`` layers rotate ``q``
    and ``k`` and see ``cfg.sliding_window`` keys, the others have no position
    signal and see every key before them."""

    cfg: HybridLMConfig
    mesh: Optional[Mesh] = None
    sliding: bool = False

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        b, s, _ = hidden.shape
        heads, kv_heads, dim = cfg.num_heads, cfg.kv_heads, cfg.head_dim

        def dense(features, name):
            return _dense(features, ("embed", "mlp"), cfg, name=name, use_bias=False)

        def head_norm(name):
            return RMSNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name=name)

        q = head_norm("q_norm")(dense(heads * dim, "q_proj")(hidden).reshape(b, s, heads, dim))
        k = head_norm("k_norm")(dense(kv_heads * dim, "k_proj")(hidden).reshape(
            b, s, kv_heads, dim))
        v = dense(kv_heads * dim, "v_proj")(hidden).reshape(b, s, kv_heads, dim)
        gate = dense(heads * dim, "gate_proj")(hidden)
        if self.sliding:
            positions = jnp.arange(s)[None]
            q, k = (apply_rope(x, positions, cfg.rope_theta) for x in (q, k))
        # the training pass: K and V repeated to the query heads (``GQAAttention``)
        k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
        out = self._causal_attend(q, k, v).reshape(b, s, heads * dim)
        out = out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="o_proj",
                      use_bias=False)(out.astype(cfg.dtype))

    def _causal_attend(self, q, k, v):
        return _flash_or_dense(self.cfg, self.mesh, q, k, v,
                               window=self.cfg.sliding_window if self.sliding else None)


def _log_uniform_a(key, shape, dtype=jnp.float32):
    """``A_log`` with ``A = exp(A_log)`` uniform in [1, 16], as published."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _inverse_softplus_of_dt(lo: float, hi: float, floor: float):
    """``dt_bias`` with ``softplus(dt_bias)`` log-uniform in ``[lo, hi]`` and
    not under ``floor``, as published."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(lo), math.log(hi))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


class Mamba2Mixer(nn.Module):
    """A fresh mixer is drawn as the published implementation draws it
    (``A_log``, ``dt_bias``, ``D`` = 1, the convolution): with ``A_log`` and
    ``dt_bias`` at nought a state halves every token and nothing outlives a
    chunk, and with taps of 0.02 the state's part of ``y`` is a thousandth of
    the skip's ``D x``, so training from scratch would start from a mixer
    whose scan is inert."""

    cfg: HybridLMConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, hidden):
        from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES

        cfg, f32 = self.cfg, jnp.float32
        heads, groups, n = cfg.mamba_heads, cfg.ssm_groups, cfg.ssm_state
        inner, keys = heads * cfg.mamba_head_dim, groups * n
        zxbcdt = _dense(2 * inner + 2 * keys + heads, ("embed", "mlp"), cfg, name="in_proj",
                        use_bias=False)(hidden)
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * keys], axis=-1)
        # the short convolution over time and its SiLU, float32, then cfg.dtype
        taps, bias = CausalConv(cfg.conv_size, inner + 2 * keys, use_bias=True,
                                published_init=True, name="conv")()
        s = xbc.shape[1]
        padded = jnp.pad(xbc.astype(f32), ((0, 0), (cfg.conv_size - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(padded[:, j:j + s] * taps[j] for j in range(cfg.conv_size))
                          + bias).astype(cfg.dtype)
        x, b, c = jnp.split(xbc, [inner, inner + keys], axis=-1)
        a_log = self.param("A_log", _log_uniform_a, (heads,), f32)
        dt_bias = self.param("dt_bias", _inverse_softplus_of_dt(
            cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor), (heads,), f32)
        skip = self.param("D", nn.initializers.ones_init(), (heads,), f32)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)

        def scan(x, dt, b, c, a, skip):               # under a mesh: a shard's heads and groups
            return ssd(x, dt, a, b, c, skip, heads=dt.shape[-1], groups=b.shape[-1] // n,
                       chunk=cfg.ssm_chunk)

        y = _per_shard(scan, self.mesh, *[P(DATA_AXES, None, "tp")] * 4, P("tp"), P("tp"))(
            x, dt, b, c, -jnp.exp(a_log), skip)
        # the gated norm: the mean square over each group's channels, float32
        y = (y.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(*y.shape[:2], groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.layer_norm_eps)
        y = y.reshape(*y.shape[:2], inner) * _Scale(inner, name="norm")()
        return _dense(cfg.hidden_size, ("mlp", "embed"), cfg, name="out_proj",
                      use_bias=False)(y.astype(cfg.dtype))


MIXERS = {"kda": KDAAttention, "mla": MLAAttention, "mamba2": Mamba2Mixer,
          "gqa": GQAAttention, "gated_full": GatedAttention,
          "gated_sliding": functools.partial(GatedAttention, sliding=True)}


class HybridBlock(nn.Module):
    cfg: HybridLMConfig
    mesh: Optional[Mesh]
    layer: int                                       # 0-based

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        norm = lambda name: RMSNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name=name)
        kind, ffn = cfg.attention[self.layer], cfg.ffn[self.layer]

        def after(name, out):
            # with sandwich norms, what a mixer or an FFN gives is normed before the sum
            return norm(name)(out) if cfg.sandwich_norms else out

        if kind != "none":
            with part_scope("mixer"):
                hidden = hidden + after("ln_post_attn", MIXERS[kind](
                    cfg, self.mesh, name="attention")(norm("ln_attn")(hidden)))
        if ffn == "none":
            return hidden
        with part_scope("ffn"):
            m = norm("ln_mlp")(hidden)
            if ffn == "dense":
                return hidden + after("ln_post_mlp", SwiGLU(
                    cfg.hidden_size, cfg.intermediate_size, cfg.dtype, name="mlp")(m))
            out, counters = HeldExpertsLayer(
                num_experts=cfg.num_experts, held=cfg.experts_held,
                top_k=cfg.experts_per_token, hidden_size=cfg.hidden_size,
                intermediate_size=cfg.expert_intermediate_size,
                route_scale=cfg.route_scale, shared=cfg.shared_experts,
                dtype=cfg.dtype, activation=cfg.expert_activation,
                shared_width=cfg.shared_intermediate_size, name="mlp")(m)
            for name, value in counters.items():
                self.sow("counters", name, value)
            return hidden + after("ln_post_mlp", out)


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
                "to reset the recurrent state, KDA's or the state-space scan's, "
                "inside a row (ROADMAP Reach 4)")
        with part_scope("embed"):
            hidden = TokenEmbed(
                cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                embedding_init=nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
                name="wte")(input_ids, one_hot=train)
            if cfg.scale_embedding:
                hidden = (hidden.astype(jnp.float32)
                          * math.sqrt(cfg.hidden_size)).astype(cfg.dtype)
        # Under remat a block keeps what its mixer kernels' forward launches
        # gave (flash's out and lse, KDA's o and states, the scan's y and
        # kept): the backward's rerun of the block recomputes the rest, the
        # kernels' inputs among it, and launches no forward kernel again.
        block_cls = nn.remat(HybridBlock, policy=jax.checkpoint_policies.save_only_these_names(
            *KERNEL_RESULT_NAMES)) if cfg.remat else HybridBlock
        for i in range(cfg.num_layers):
            hidden = block_cls(cfg, self.mesh, i, name=f"layer_{i}")(hidden)
        with part_scope("head_loss"):
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
        to held experts and the rows their walks took, each summed over the
        expert layers, and the busiest held expert's load in any of them."""
        layers = [c for c in sown.values() if "held_assignments" in c]
        if not layers:
            return {}
        # ``sow`` keeps a tuple a name: one value a call
        with part_scope("ffn"):
            return {"moe_held_assignments": sum(c["held_assignments"][0] for c in layers),
                    "moe_held_load_max": jnp.max(jnp.stack(
                        [c["held_load_max"][0] for c in layers])),
                    "moe_held_rows_walked": sum(c["held_rows_walked"][0] for c in layers)}
