"""Plain reference of the AFMoE decoder as ``configs/trinity-mini.json`` cuts
it: straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision,
no kernels, no sorting of tokens. It imports nothing of the program and takes
nothing the program has made: weights come from ``lib.weights_afmoe`` (seed,
leaf name, shape). What it shares with ``reference/kimi_linear.py`` (the float8
rounding, RMSNorm, the router, SwiGLU and the held experts' sum) is that
file's.

Follows the published model (``arcee-ai/Trinity-Mini`` ``config.json``,
``model_type`` ``afmoe``). All norms RMSNorm, untied head, no bias. With
``E`` the embedding, ``h0 = E[ids] * sqrt(hidden_size)`` (``mup_enabled``); no
scale on the head. Layer ``n`` has four norms, a sandwich around each half::

    h = h + N_post_attn(Attn(N_in(h)))
    h = h + N_post_mlp(FFN(N_pre_mlp(h)))

* **Attention** on ``x = N_in(h)``: ``q = x W_q`` (``heads`` of ``head_dim``),
  ``k = x W_k``, ``v = x W_v`` (``kv_heads`` each), ``g = x W_g`` (as wide as
  ``q``); ``q`` and ``k`` RMS-normed a head with a learned scale each; on a
  ``sliding_attention`` layer only, both rotated (``rope_theta``, all columns,
  the half-rotation convention: column ``c`` paired with ``c + head_dim / 2``)
  at positions ``0..S-1``; head ``h`` attends with key-value head ``h // (heads
  / kv_heads)`` at scale ``head_dim^-1/2``; query ``i`` sees keys ``j <= i``, on
  a sliding layer those with ``i - j < sliding_window`` only; softmax, ``o = P
  v``, ``o = o * sigmoid(g)``, out ``= o W_o``. A ``full_attention`` layer has no
  position signal at all. In blocks of queries, one after another.
* **FFN**: dense SwiGLU ``(silu(x W1) * (x W3)) W2`` in the first
  ``num_dense_layers`` layers; after them ``s = sigmoid(x W_r)``, the top ``k``
  of ``s + b`` chosen (``b`` a buffer with no gradient), ``w_e = route_scale *
  s_e / sum_chosen s``, ``y = sum_chosen w_e E_e(x) + E_shared(x)``.
* **Loss**: next-token cross entropy, nothing added to it.

Departures, stated in the configuration file: the sum over chosen experts
runs over those this chip holds only, and that partial ``y`` goes on; the
vocabulary is the slice; the buffer ``b`` and the weights are ``assumed``.

``precision`` selects the arithmetic: ``"float32"`` is the reference;
``"fp8"`` is the control (``reference/kimi_linear.py::_fp8``). ``fault`` plants
a fault for ``tools/control_afmoe.py``: ``"window_ignored"`` (a sliding layer
sees every key before it), ``"rotation_off"`` (no layer rotates) and
``"gate_off"`` (the heads' outputs go to ``W_o`` as they are).
"""

import functools
import math

import jax
import jax.numpy as jnp

from lib import weights as W
from lib import weights_afmoe as A
from reference.kimi_linear import (HIGHEST, _mm, _norms, _prep, _sub, expert_ffn, rms_norm,
                                   swiglu)

FAULTS = ("window_ignored", "rotation_off", "gate_off")


def rotate(x, theta):
    """Rotary embedding of ``x [B, S, H, D]`` at positions ``0..S-1``."""
    s, half = x.shape[1], x.shape[-1] // 2
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)        # [S, D/2]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w, d, kind, prep, fault=None, q_block=512):
    bsz, s, _ = x.shape
    heads, kv_heads, dim = d["heads"], d["kv_heads"], d["head_dim"]
    q = _mm(prep, x, w["q_proj/kernel"]).reshape(bsz, s, heads, dim)
    k = _mm(prep, x, w["k_proj/kernel"]).reshape(bsz, s, kv_heads, dim)
    v = _mm(prep, x, w["v_proj/kernel"]).reshape(bsz, s, kv_heads, dim)
    gate = _mm(prep, x, w["gate_proj/kernel"])
    q, k = rms_norm(q, w["q_norm/scale"], d["eps"]), rms_norm(k, w["k_norm/scale"], d["eps"])
    if kind == "sliding" and fault != "rotation_off":
        q, k = rotate(q, d["theta"]), rotate(k, d["theta"])
    k, v = (jnp.repeat(m, heads // kv_heads, axis=2) for m in (k, v))
    window = d["window"] if kind == "sliding" and fault != "window_ignored" else s
    blk = q_block if s % q_block == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def rows(q_blk, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", prep(q_blk), prep(k),
                            precision=HIGHEST) * dim ** -0.5
        behind = (first + jnp.arange(blk))[:, None] - pos[None, :]
        visible = (behind >= 0) & (behind < window)
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", prep(probs), prep(v), precision=HIGHEST)

    # one block of queries after another (``lax.map``), so that one block's
    # scores exist at a time, backward too
    blocks = jnp.moveaxis(q.reshape(bsz, s // blk, blk, heads, dim), 1, 0)
    out = jax.lax.map(lambda xs: rows(*xs), (blocks, jnp.arange(0, s, blk)))
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, s, heads * dim)
    if fault != "gate_off":
        out = out * jax.nn.sigmoid(gate)
    return _mm(prep, out, w["o_proj/kernel"])


def layer(x, w, cfg, kinds, precision="float32", fault=None):
    """One layer of ``kinds = (attention kind, FFN kind)`` on ``x [B, S, h]``;
    ``w`` maps the layer's leaf names (``lib.weights_afmoe.layer_leaf_shapes``)
    to arrays."""
    d, prep = A.dims(cfg), _prep(precision)
    norm = lambda m, name: rms_norm(m, w[f"{name}/scale"], d["eps"])
    x = x + norm(attention(norm(x, "ln_attn"), _sub(w, "attention/"), d, kinds[0], prep, fault),
                 "ln_post_attn")
    m, fw = norm(x, "ln_mlp"), _sub(w, "mlp/")
    if kinds[1] == "dense":
        y = swiglu(m, fw["gate/kernel"], fw["up/kernel"], fw["down/kernel"], prep)
    else:
        y = expert_ffn(m, fw, d, prep)
    return x + norm(y, "ln_post_mlp")


def embed(w, ids, cfg):
    return w["wte/embedding"][ids] * A.dims(cfg)["embed_scale"]


def hidden_states(w, ids, cfg, precision="float32", fault=None):
    """Final-norm hidden states ``[B, S, h]``; ``w`` is the flat leaf dict."""
    x = embed(w, ids, cfg)
    for n in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, kinds=A.kinds(cfg, n), precision=precision, fault=fault))(
                x, _sub(w, f"layer_{n}/"))
    return rms_norm(x, w["ln_final/scale"], float(cfg["rms_norm_eps"]))


def logits(w, ids, cfg, precision="float32", fault=None):
    return _mm(_prep(precision), hidden_states(w, ids, cfg, precision, fault),
               w["lm_head/kernel"])


def head_sum_ce(w, x, ids, cfg, precision="float32"):
    """Sum of next-token cross entropy from the last layer's output ``x``;
    ``w`` holds ``ln_final/scale`` and ``lm_head/kernel``."""
    hid = rms_norm(x, w["ln_final/scale"], float(cfg["rms_norm_eps"]))
    logp = jax.nn.log_softmax(_mm(_prep(precision), hid, w["lm_head/kernel"])[:, :-1],
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def sum_ce(w, ids, cfg, precision="float32", fault=None):
    """Sum over rows and positions of next-token cross entropy."""
    logp = jax.nn.log_softmax(logits(w, ids, cfg, precision, fault)[:, :-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def weights(cfg, seed):
    """The flat leaf dict from the seed, made on the device in one call."""
    shapes = A.leaf_shapes(cfg)
    return jax.jit(lambda key: W.make_leaves(key, shapes))(W.seed_key(seed))


HEAD = ("ln_final/scale", "lm_head/kernel")
EMBED = "wte/embedding"


def learning_rate(optimizer: dict, step: int) -> float:
    """The rate of the ``step``-th update, counted from 1. ``schedule``
    ``constant`` (or none): ``learning_rate``. ``warmup_cosine``: a straight
    line from nought, at the first update, to ``learning_rate`` after
    ``warmup_steps`` updates, then half a cosine down to nought at
    ``total_steps``."""
    peak, schedule = float(optimizer["learning_rate"]), optimizer.get("schedule", "constant")
    if schedule == "constant":
        return peak
    if schedule != "warmup_cosine":
        raise ValueError(f"no schedule {schedule!r} in the reference")
    done, warm = step - 1, int(optimizer["warmup_steps"])
    if done < warm:
        return peak * done / warm
    length = max(int(optimizer["total_steps"]), warm + 1) - warm
    return peak * 0.5 * (1.0 + math.cos(math.pi * min(done - warm, length) / length))


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, steps: int = 3,
                rows_block: int = 1, precision: str = "float32",
                keep_rows=None, fault=None) -> dict:
    """Follow the first ``steps`` Adam steps on ``batches`` (each
    ``int32 [rows, seq]``), as ``reference/nemotron_h.py::train_steps`` does
    and for its reason: parameters and Adam's two moments are 8.5 GB of the
    chip's 16 at the cut configuration, so the whole model's gradient never
    exists at once and the moments wait on the host between a group's steps.
    Backpropagation is written out a layer at a time: the forward keeps each
    layer's input for each block of ``rows_block`` rows; then, from the head
    down, a layer's gradient is summed over the blocks (``jax.vjp`` of that
    layer alone), its norm noted, its Adam step taken, and the gradient let go.
    The result is ``jax.grad`` of :func:`sum_ce` and Adam on all leaves at once
    (the CPU tests compare them).

    Returns ``{"loss": [per step], "grad_norm": {leaf: norm of the first
    gradient}, "delta_norm": {leaf: norm of the parameters' change after the
    steps}}``. ``keep_rows`` (a count) plants the fault "half of the batch left
    out, the mean taken over the rest"; ``fault`` is handed to the layers."""
    b1, b2, aeps = (float(optimizer[k]) for k in ("b1", "b2", "eps"))
    n_layers = cfg["num_hidden_layers"]
    kinds = [A.kinds(cfg, i) for i in range(n_layers)]
    w = weights(cfg, seed)
    groups = [[EMBED]] + [[n for n in w if n.startswith(f"layer_{i}/")]
                          for i in range(n_layers)] + [list(HEAD)]

    @functools.partial(jax.jit, static_argnames=("kinds",))
    def forward(x, lw, kinds):
        return layer(x, lw, cfg, kinds, precision, fault)

    @functools.partial(jax.jit, static_argnames=("kinds",))
    def backward(x, lw, dx, acc, kinds):
        """``(dx below, acc + this block's gradient)`` of one layer."""
        _, pull = jax.vjp(lambda x_, w_: layer(x_, w_, cfg, kinds, precision, fault), x, lw)
        dx, dw = pull(dx)
        return dx, jax.tree.map(jnp.add, acc, dw)

    @jax.jit
    def head(hw, x, ids, acc):
        loss, (dw, dx) = jax.value_and_grad(
            lambda w_, x_: head_sum_ce(w_, x_, ids, cfg, precision), argnums=(0, 1))(hw, x)
        return loss, dx, jax.tree.map(jnp.add, acc, dw)

    @jax.jit
    def embed_backward(dx, ids, acc):
        return acc.at[ids].add(dx * A.dims(cfg)["embed_scale"])

    @functools.partial(jax.jit, donate_argnums=(0,))
    def adam(w, g, m, v, t, lr, tokens):
        """One Adam step on a group of leaves from its summed gradient; also
        the norms of the mean gradient."""
        g = jax.tree.map(lambda g_: g_ / tokens, g)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        w = jax.tree.map(
            lambda w_, m_, v_: w_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + aeps),
            w, m, v)
        return w, m, v, _norms(g)

    shapes = A.leaf_shapes(cfg)
    delta_norms = jax.jit(lambda w, key: _norms(
        {n: w[n] - W.make_leaf(key, n, W.name_tag(n), s) for n, s in shapes.items()}))
    m, v = {}, {}                       # on the host (numpy) between steps
    out = {"loss": [], "grad_norm": {}, "delta_norm": None}

    def step_group(names, grads, t, tokens):
        def moment(kept):
            return {n: kept[n] if n in kept else jnp.zeros_like(w[n]) for n in names}

        new_w, new_m, new_v, norms = adam(
            {n: w[n] for n in names}, grads, moment(m), moment(v),
            jnp.float32(t), jnp.float32(learning_rate(optimizer, t)), jnp.float32(tokens))
        w.update(new_w)
        m.update(jax.device_get(new_m)), v.update(jax.device_get(new_v))
        if t == 1:
            out["grad_norm"].update({n: float(x) for n, x in jax.device_get(norms).items()})

    def zeros(names):
        return {n: jnp.zeros_like(w[n]) for n in names}

    for t in range(1, steps + 1):
        ids = jnp.asarray(batches[t - 1], jnp.int32)
        if keep_rows is not None:
            ids = ids[:keep_rows]
        if ids.shape[0] % rows_block:
            raise ValueError(f"{ids.shape[0]} rows do not split into blocks of {rows_block}")
        tokens = ids.shape[0] * (ids.shape[1] - 1)
        blocks = [ids[r:r + rows_block] for r in range(0, ids.shape[0], rows_block)]
        # forward: each layer's input, for each block of rows
        inputs = []
        for blk in blocks:
            x, kept = embed(w, blk, cfg), []
            for i in range(n_layers):
                kept.append(x)
                x = forward(x, {n[len(f"layer_{i}/"):]: w[n] for n in groups[i + 1]},
                            kinds=kinds[i])
            inputs.append(kept + [x])
        # the head: the loss, and what flows back into the last layer
        total, acc, flowing = 0.0, zeros(HEAD), []
        for blk, kept in zip(blocks, inputs):
            loss, dx, acc = head({n: w[n] for n in HEAD}, kept[-1], blk, acc)
            total += float(loss)
            flowing.append(dx)
        step_group(HEAD, acc, t, tokens)
        out["loss"].append(total / tokens)
        # the layers, from the last to the first
        for i in reversed(range(n_layers)):
            prefix = f"layer_{i}/"
            lw = {n[len(prefix):]: w[n] for n in groups[i + 1]}
            acc = jax.tree.map(jnp.zeros_like, lw)
            for j, kept in enumerate(inputs):
                flowing[j], acc = backward(kept[i], lw, flowing[j], acc, kinds=kinds[i])
                kept[i] = None
            del lw
            step_group(groups[i + 1], {prefix + n: g for n, g in acc.items()}, t, tokens)
        acc = jnp.zeros_like(w[EMBED])
        for blk, dx in zip(blocks, flowing):
            acc = embed_backward(dx, blk, acc)
        step_group([EMBED], {EMBED: acc}, t, tokens)
        del acc, flowing, inputs
    del m, v
    out["delta_norm"] = {k: float(x) for k, x in
                         jax.device_get(delta_norms(w, W.seed_key(seed))).items()}
    return out
