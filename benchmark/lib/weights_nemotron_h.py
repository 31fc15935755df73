"""Leaf shapes and leaves of the Nemotron-H decoder (``configs/
nemotron-3-nano-30b-a3b.json`` key names), beside ``lib/weights.py``.

A layer is one mixer behind one norm, its kind the layer's letter in
``hybrid_override_pattern``: ``M`` Mamba-2, ``*`` attention, ``E`` experts. The
mixer's leaves sit where the program's tree has them: an ``M`` or ``*`` layer
under ``ln_attn`` / ``attention``, an ``E`` layer under ``ln_mlp`` / ``mlp``.

Every leaf comes from ``lib.weights.make_leaf`` (a function of seed, leaf name
and shape) but four of the Mamba-2 mixer, which that function would draw as
noise about 0: ``A_log``, ``dt_bias``, ``D`` and the convolution's taps. Noise
about 0 in the first two means a step of ``softplus(0)`` = 0.69 and a decay of
a half a token: the state would forget within a chunk. Taps of 0.02 make ``x``,
``B`` and ``C`` a fiftieth of what they are in the model, and the state's part
of ``y`` a thousandth of the skip's ``D x``: a kernel that lost the state
between chunks would pass either way. They are drawn as the published
implementation initialises them: ``A_log = log(U(1, 16))`` a head, ``dt_bias =
softplus^-1(dt)`` with ``dt`` log-uniform in [``time_step_min``,
``time_step_max``] and not under ``time_step_floor``, ``D = 1`` (here with
``lib.weights``' noise, so that no two heads' are alike), the taps
``U(-k^-1/2, k^-1/2)`` with ``k = conv_kernel`` (a depthwise ``Conv1d``'s
default).
"""

import math

import jax
import jax.numpy as jnp

from lib import weights as W

A_MIN, A_MAX = 1.0, 16.0
KINDS = {"M": "mamba2", "*": "gqa", "E": "experts"}


def dims(cfg: dict) -> dict:
    """The sizes a builder needs, from the configuration file's keys."""
    heads, dim = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "h": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "eps": float(cfg["layer_norm_epsilon"]),
        "m_heads": heads, "m_dim": dim, "state": state, "groups": groups,
        "inner": heads * dim, "conv_dim": heads * dim + 2 * groups * state,
        "conv": cfg["conv_kernel"], "chunk": cfg["chunk_size"],
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "shared_ffn": cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"],
        "router": cfg["published"]["n_routed_experts"], "held": cfg["n_routed_experts"],
        "held_first": cfg["deployment"]["experts_held_first"],
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "dt_min": float(cfg["time_step_min"]), "dt_max": float(cfg["time_step_max"]),
        "dt_floor": float(cfg["time_step_floor"]),
    }


def kind(cfg: dict, layer: int) -> str:
    """``mamba2``, ``gqa`` or ``experts`` for the 1-based ``layer``."""
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern {pattern!r} does not name "
                         f"{cfg['num_hidden_layers']} layers")
    return KINDS[pattern[layer - 1]]


def mixer_leaf_shapes(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    h = d["h"]
    if kind == "mamba2":
        return {"in_proj/kernel": (h, d["inner"] + d["conv_dim"] + d["m_heads"]),
                "conv/kernel": (d["conv"], d["conv_dim"]), "conv/bias": (d["conv_dim"],),
                "A_log": (d["m_heads"],), "dt_bias": (d["m_heads"],), "D": (d["m_heads"],),
                "norm/scale": (d["inner"],), "out_proj/kernel": (d["inner"], h)}
    if kind == "gqa":
        q, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
        return {"q_proj/kernel": (h, q), "k_proj/kernel": (h, kv), "v_proj/kernel": (h, kv),
                "o_proj/kernel": (q, h)}
    e, w = d["held"], d["expert_ffn"]
    out = {"router/kernel": (h, d["router"]), "router_bias": (d["router"],),
           "w_up": (e, h, w), "w_down": (e, w, h)}
    if d["shared_ffn"]:
        out.update({"shared/up/kernel": (h, d["shared_ffn"]),
                    "shared/down/kernel": (d["shared_ffn"], h)})
    return out


def layer_leaf_shapes(cfg: dict, layer: int) -> dict:
    """Leaf name (inside the layer) -> shape for the 1-based ``layer``."""
    k = kind(cfg, layer)
    norm, under = ("ln_mlp", "mlp") if k == "experts" else ("ln_attn", "attention")
    out = {f"{norm}/scale": (cfg["hidden_size"],)}
    out.update({f"{under}/{n}": s for n, s in mixer_leaf_shapes(cfg, k).items()})
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


def leaf_maker(cfg: dict):
    """``make_leaf(key, name, tag, shape)`` for this configuration:
    ``lib.weights.make_leaf`` but for ``A_log``, ``dt_bias``, ``D`` and the
    convolution's taps."""
    d = dims(cfg)
    lo, hi, floor = math.log(d["dt_min"]), math.log(d["dt_max"]), d["dt_floor"]
    taps = d["conv"]

    def make_leaf(key, name: str, tag, shape):
        if name.endswith("/A_log"):
            u = jax.random.uniform(jax.random.fold_in(key, tag), shape, jnp.float32)
            return jnp.log(A_MIN + (A_MAX - A_MIN) * u)
        if name.endswith("/dt_bias"):
            u = jax.random.uniform(jax.random.fold_in(key, tag), shape, jnp.float32)
            dt = jnp.maximum(jnp.exp(lo + (hi - lo) * u), floor)
            return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
        if name.endswith("/D"):
            return 1.0 + W.make_leaf(key, name, tag, shape)
        if name.endswith("/conv/kernel"):
            return jax.random.uniform(jax.random.fold_in(key, tag), shape, jnp.float32,
                                      -taps ** -0.5, taps ** -0.5)
        return W.make_leaf(key, name, tag, shape)

    return make_leaf


def make_leaves(key, cfg: dict, shapes: dict) -> dict:
    """name -> float32 array; call under jit with ``key`` as an argument
    (``lib.weights.make_leaves`` says why)."""
    make_leaf = leaf_maker(cfg)
    return {n: make_leaf(key, n, W.name_tag(n), s) for n, s in shapes.items()}
