"""Weights from ``--seed``, made by the benchmark and not by the program.

Each leaf is a function of (seed, leaf name, shape) alone, so the program's
whole tree can be made on the device in one jitted call and the reference can
make the same leaves again, one layer at a time, without taking anything the
program holds. Distributions follow GPT-2's published ``initializer_range``
0.02; biases and norm scales are perturbed too, as a trained checkpoint's are,
so that no term of the model is a silent zero."""

import zlib

import jax
import jax.numpy as jnp


def gpt2_leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> shape for a GPT-2 decoder as the configuration file
    states it (names are the paths of the parameter tree, '/'-joined)."""
    h, ffn = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    out = {"wte/embedding": (cfg["vocab_size"], h),
           "wpe/embedding": (cfg["n_positions"], h)}
    for i in range(cfg["n_layer"]):
        out.update({f"layer_{i}/{k}": v for k, v in layer_leaf_shapes(cfg).items()})
    out["ln_final/scale"] = (h,)
    out["ln_final/bias"] = (h,)
    out["lm_head/kernel"] = (h, cfg["vocab_size"])     # untied, as the program builds it
    out["lm_head/bias"] = (cfg["vocab_size"],)
    return out


def layer_leaf_shapes(cfg: dict) -> dict:
    h, ffn = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    out = {}
    for ln in ("ln_attn", "ln_mlp"):
        out[f"{ln}/scale"] = (h,)
        out[f"{ln}/bias"] = (h,)
    for name in ("query", "key", "value", "out"):
        out[f"attention/{name}/kernel"] = (h, h)
        out[f"attention/{name}/bias"] = (h,)
    out["mlp_in/kernel"] = (h, ffn)
    out["mlp_in/bias"] = (ffn,)
    out["mlp_out/kernel"] = (ffn, h)
    out["mlp_out/bias"] = (h,)
    return out


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds may pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def name_tag(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def make_leaf(key, name: str, tag, shape):
    """One leaf. ``name`` picks the distribution (static); ``tag`` is
    ``name_tag`` of the full leaf name and may be traced, so one compiled
    layer generator serves every layer."""
    k = jax.random.fold_in(key, tag)
    noise = 0.02 * jax.random.normal(k, shape, jnp.float32)
    if name.endswith("scale"):
        return 1.0 + noise
    return noise


def make_leaves(key, shapes: dict) -> dict:
    """name -> float32 array for every leaf in ``shapes``. Call under jit
    with ``key`` (``seed_key(seed)``) as an ARGUMENT, so that the whole tree
    is made on the device in one call and the compiled program is the same
    for every seed (a seed closed over would be a new program each run)."""
    return {n: make_leaf(key, n, name_tag(n), s) for n, s in shapes.items()}


def nest(flat: dict) -> dict:
    """'a/b/c' -> nested dicts, the shape of a flax parameter tree."""
    out: dict = {}
    for name, v in flat.items():
        node = out
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict) or hasattr(v, "items"):
            out.update(flatten(v, name))
        else:
            out[name] = v
    return out
