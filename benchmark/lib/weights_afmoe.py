"""Leaf shapes and leaves of the AFMoE decoder (``configs/trinity-mini.json``
key names), beside ``lib/weights.py``.

Layers are 0-based, as ``layer_types`` and ``num_dense_layers`` count them:
layer ``i`` is ``layer_{i}`` in the tree, its attention ``sliding`` or ``full``
by ``layer_types[i]``, its FFN dense where ``i < num_dense_layers`` and experts
after. Every leaf comes from ``lib.weights.make_leaves`` (a function of seed,
leaf name and shape; there is no maker here): 0.02 noise, norm scales 1 + noise (the four layer norms,
the two head norms), the router's bias noise 0.02. Query-key norm gives the
scores a unit variance whatever the draw, so a head attends to some keys far
more than to others, and the window and the rotation change which."""

import math

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def dims(cfg: dict) -> dict:
    """The sizes a builder needs, from the configuration file's keys."""
    return {
        "h": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "eps": float(cfg["rms_norm_eps"]),
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"], "window": cfg["sliding_window"],
        "theta": float(cfg["rope_theta"]),
        "ffn": cfg["intermediate_size"], "expert_ffn": cfg["moe_intermediate_size"],
        "router": cfg["published"]["num_experts"], "held": cfg["num_experts"],
        "held_first": cfg["deployment"]["experts_held_first"],
        "top_k": cfg["num_experts_per_tok"], "route_scale": float(cfg["route_scale"]),
        "shared": cfg["num_shared_experts"],
        "embed_scale": math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0,
    }


def attention_kind(cfg: dict, layer: int) -> str:
    """``sliding`` or ``full`` for the 0-based ``layer``."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types names {len(types)} layers, not "
                         f"{cfg['num_hidden_layers']}")
    return KINDS[types[layer]]


def ffn_kind(cfg: dict, layer: int) -> str:
    return "dense" if layer < cfg["num_dense_layers"] else "experts"


def kinds(cfg: dict, layer: int) -> tuple:
    return attention_kind(cfg, layer), ffn_kind(cfg, layer)


def attention_leaf_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    h, q, kv = d["h"], d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    return {"q_proj/kernel": (h, q), "k_proj/kernel": (h, kv), "v_proj/kernel": (h, kv),
            "gate_proj/kernel": (h, q), "q_norm/scale": (d["head_dim"],),
            "k_norm/scale": (d["head_dim"],), "o_proj/kernel": (q, h)}


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
    """Leaf name (inside the layer) -> shape for the 0-based ``layer``."""
    h = cfg["hidden_size"]
    out = {f"{n}/scale": (h,) for n in ("ln_attn", "ln_post_attn", "ln_mlp", "ln_post_mlp")}
    out.update({f"attention/{k}": v for k, v in attention_leaf_shapes(cfg).items()})
    out.update({f"mlp/{k}": v for k, v in
                ffn_leaf_shapes(cfg, ffn_kind(cfg, layer)).items()})
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape of the whole tree."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    out = {"wte/embedding": (vocab, h)}
    for layer in range(cfg["num_hidden_layers"]):
        out.update({f"layer_{layer}/{k}": v
                    for k, v in layer_leaf_shapes(cfg, layer).items()})
    out["ln_final/scale"] = (h,)
    out["lm_head/kernel"] = (h, vocab)
    return out


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in leaf_shapes(cfg).values())
