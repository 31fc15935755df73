"""Leaf shapes and leaves of the Kimi-Linear decoder (``configs/
kimi-linear-48b-a3b.json`` key names), beside ``lib/weights.py``.

Every leaf comes from ``lib.weights.make_leaf`` (a function of seed, leaf name
and shape) but two, which that function picks by name and would draw as noise
about 0: KDA's ``A_log`` and ``dt_bias``. Noise about 0 there means a decay of
a half a token; the state would forget within a chunk and a kernel that lost
it between chunks would pass. They are drawn as the family initialises them:
``A_log = log(U(1, 16))`` per head, ``dt_bias = softplus^-1(dt)`` with ``dt``
log-uniform in [0.001, 0.1], so that the log-decay lies in about
[-1.6, -0.001] a token and the state carries across the whole row.
"""

import math

import jax
import jax.numpy as jnp

from lib import weights as W

GATE_RANK = 128          # assumed: rank of the decay gate and of the output gate
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def dims(cfg: dict) -> dict:
    """The sizes a builder needs, from the configuration file's keys."""
    lin = cfg["linear_attn_config"]
    return {
        "h": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "eps": float(cfg["rms_norm_eps"]),
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"], "gate_rank": GATE_RANK,
        "heads": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
        "kv_rank": cfg["kv_lora_rank"], "ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "router": cfg["published"]["num_experts"], "held": cfg["num_experts"],
        "held_first": cfg["deployment"]["experts_held_first"],
        "top_k": cfg["num_experts_per_token"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "shared": cfg["num_shared_experts"],
    }


def attention_kind(cfg: dict, layer: int) -> str:
    """``kda`` or ``mla`` for the 1-based ``layer``, as the published lists say."""
    lin = cfg["linear_attn_config"]
    if layer in lin["kda_layers"]:
        return "kda"
    if layer in lin["full_attn_layers"]:
        return "mla"
    raise ValueError(f"layer {layer} is in neither list of linear_attn_config")


def ffn_kind(cfg: dict, layer: int) -> str:
    return "dense" if layer <= cfg["first_k_dense_replace"] else "experts"


def attention_leaf_shapes(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    h = d["h"]
    if kind == "kda":
        wide = d["kda_heads"] * d["kda_dim"]
        out = {f"{p}_proj/kernel": (h, wide) for p in "qkv"}
        out.update({f"{p}_conv/kernel": (d["conv"], wide) for p in "qkv"})
        out.update({
            "f_a/kernel": (h, d["gate_rank"]), "f_b/kernel": (d["gate_rank"], wide),
            "A_log": (d["kda_heads"],), "dt_bias": (wide,),
            "b_proj/kernel": (h, d["kda_heads"]),
            "g_a/kernel": (h, d["gate_rank"]), "g_b/kernel": (d["gate_rank"], wide),
            "o_norm/scale": (d["kda_dim"],), "o_proj/kernel": (wide, h)})
        return out
    heads = d["heads"]
    return {"q_proj/kernel": (h, heads * (d["nope"] + d["rope"])),
            "kv_a/kernel": (h, d["kv_rank"] + d["rope"]),
            "kv_norm/scale": (d["kv_rank"],),
            "kv_b/kernel": (d["kv_rank"], heads * (d["nope"] + d["v_dim"])),
            "o_proj/kernel": (heads * d["v_dim"], h)}


def ffn_leaf_shapes(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    h = d["h"]
    if kind == "dense":
        return {"gate/kernel": (h, d["ffn"]), "up/kernel": (h, d["ffn"]),
                "down/kernel": (d["ffn"], h)}
    e, w = d["held"], d["expert_ffn"]
    out = {"router/kernel": (h, d["router"]), "router_bias": (d["router"],),
           "w_gate": (e, h, w), "w_up": (e, h, w), "w_down": (e, w, h)}
    if d["shared"]:
        sw = w * d["shared"]
        out.update({"shared/gate/kernel": (h, sw), "shared/up/kernel": (h, sw),
                    "shared/down/kernel": (sw, h)})
    return out


def layer_leaf_shapes(cfg: dict, layer: int) -> dict:
    """Leaf name (inside the layer) -> shape for the 1-based ``layer``."""
    h = cfg["hidden_size"]
    out = {"ln_attn/scale": (h,), "ln_mlp/scale": (h,)}
    out.update({f"attention/{k}": v for k, v in
                attention_leaf_shapes(cfg, attention_kind(cfg, layer)).items()})
    out.update({f"mlp/{k}": v for k, v in
                ffn_leaf_shapes(cfg, ffn_kind(cfg, layer)).items()})
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape of the whole tree; layer ``i`` (1-based, as published)
    is ``layer_{i-1}`` in the tree."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    out = {"wte/embedding": (vocab, h)}
    for layer in range(1, cfg["num_hidden_layers"] + 1):
        out.update({f"layer_{layer - 1}/{k}": v
                    for k, v in layer_leaf_shapes(cfg, layer).items()})
    out["ln_final/scale"] = (h,)
    out["lm_head/kernel"] = (h, vocab)
    return out


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in leaf_shapes(cfg).values())


def make_leaf(key, name: str, tag, shape):
    """One leaf: ``lib.weights.make_leaf`` but for ``A_log`` and ``dt_bias``."""
    if name.endswith("A_log"):
        u = jax.random.uniform(jax.random.fold_in(key, tag), shape, jnp.float32)
        return jnp.log(A_MIN + (A_MAX - A_MIN) * u)
    if name.endswith("dt_bias"):
        u = jax.random.uniform(jax.random.fold_in(key, tag), shape, jnp.float32)
        dt = jnp.exp(math.log(DT_MIN) + (math.log(DT_MAX) - math.log(DT_MIN)) * u)
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    return W.make_leaf(key, name, tag, shape)


def make_leaves(key, shapes: dict) -> dict:
    """name -> float32 array; call under jit with ``key`` as an argument
    (``lib.weights.make_leaves`` says why)."""
    return {n: make_leaf(key, n, W.name_tag(n), s) for n, s in shapes.items()}
