"""Plain reference of the Nemotron-H decoder as ``configs/
nemotron-3-nano-30b-a3b.json`` cuts it: straightforward ``jax.numpy`` in
float32 with ``highest`` matmul precision, no kernels, no chunked algebra, no
sorting of tokens. It imports nothing of the program and takes nothing the
program has made: weights come from ``lib.weights_nemotron_h`` (seed, leaf
name, shape). What it shares with ``reference/kimi_linear.py`` (the float8
rounding, RMSNorm, the causal convolution, the router) is that file's.

Follows the published model (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
``config.json``, ``model_type`` ``nemotron_h``; the Nemotron-H report; Mamba-2,
Dao & Gu 2024). All norms are RMSNorm, no embedding scale, untied head, no bias
but the convolution's. Layer ``n`` is ``x += Mixer_n(RMSNorm_n(x))``, the mixer
by the letter of ``hybrid_override_pattern``. With ``u [T, h]``:

* **M, Mamba-2**: ``[z | xBC | dt] = u W_in`` (``d_inner`` | ``d_inner + 2 G N``
  | ``H``, with ``d_inner = H P``); ``xBC = silu(conv4(xBC) + b_conv)``
  (depthwise, causal, the last tap on the current token); ``xBC`` splits into
  ``x`` (``H`` heads of ``P``), ``B`` and ``C`` (``G`` groups of ``N`` each; head
  ``h`` uses group ``h // (H / G)``); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head, state ``h`` in ``R^{P x N}``, ``h_0 = 0``::

      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
      y_t = h_t C_t + D x_t

  then ``y = RMSNorm_group(y * silu(z))`` (the mean square over each of the
  ``G`` groups of ``d_inner / G`` channels, times a ``scale [d_inner]``) and
  out ``= y W_out``. The recurrence runs one token at a time (``lax.scan``),
  under ``jax.checkpoint`` per 128 steps so that its backward fits.
* **\\*, attention**: ``q = u W_q`` (``heads`` of ``head_dim``), ``k = u W_k``,
  ``v = u W_v`` (``kv_heads`` each); head ``h`` attends with key-value head
  ``h // (heads / kv_heads)``; causal softmax at scale ``head_dim^-1/2``, in
  blocks of queries; no rotary embedding (the published implementation of
  ``nemotron_h`` applies none); out ``= concat_h(o_h) W_o``.
* **E, experts**: ``s = sigmoid(u W_r)``; the top ``k`` of ``s + b`` are chosen
  (``b`` a buffer with no gradient); ``w_e = scale * s_e / sum_chosen s``;
  ``y = sum_chosen w_e E_e(u) + E_shared(u)``, ``E(u) = relu(u W_up)^2 W_down``.

Departures, stated in the configuration file: the sum over chosen experts
runs over those this chip holds only, and that partial ``y`` goes on; the
vocabulary is the slice; the buffer ``b`` and the weights are ``assumed``. Each
held expert is applied to every token and weighted by ``w_e`` (0 where it was
not chosen): the same sum, with no sorting.

``precision`` selects the arithmetic: ``"float32"`` is the reference;
``"fp8"`` is the control (``reference/kimi_linear.py::_fp8``). ``fault`` plants
a fault for ``tools/control_nemotron_h.py``: ``"ssd_state_zeroed"`` loses the
scan's state at every 128th token.
"""

import functools

import jax
import jax.numpy as jnp

from lib import weights as W
from lib import weights_nemotron_h as N
from reference.kimi_linear import (HIGHEST, _mm, _norms, _prep, _sub, causal_conv,
                                   rms_norm, route)

SSD_CHECKPOINT = 128          # tokens of the recurrence per checkpoint


def ssd_recurrence(x, dt, a, b, c, d, zero_state_every=None):
    """The recurrence above, one token at a time. ``x [B, S, H, P]``,
    ``dt [B, S, H]``, ``a, d [H]``, ``b, c [B, S, G, N]``; returns
    ``y [B, S, H, P]``."""
    bsz, s, heads, p = x.shape
    per = heads // b.shape[2]
    b, c = (jnp.repeat(m, per, axis=2) for m in (b, c))       # a head's group's

    def token(state, xs):
        x_t, dt_t, b_t, c_t, t = xs                      # [B, H, P], [B, H], [B, H, N] x 2, []
        if zero_state_every:
            state = jnp.where(t % zero_state_every == 0, 0.0, state)
        state = state * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x_t * dt_t[..., None], b_t, precision=HIGHEST)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)

    @jax.checkpoint
    def stretch(state, xs):
        return jax.lax.scan(token, state, xs)

    n = SSD_CHECKPOINT if s % SSD_CHECKPOINT == 0 else 1

    def by_time(m):                                      # [B, S, ...] -> [S/n, n, B, ...]
        m = jnp.moveaxis(m, 1, 0)
        return m.reshape((s // n, n) + m.shape[1:])

    xs = tuple(by_time(m) for m in (x, dt, b, c)) + (jnp.arange(s).reshape(s // n, n),)
    _, y = jax.lax.scan(stretch, jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1) + d[:, None] * x


def mamba2_mixer(u, w, d, prep, fault=None):
    bsz, s, _ = u.shape
    heads, p, groups, n, inner = (d[k] for k in ("m_heads", "m_dim", "groups", "state", "inner"))
    zxbcdt = _mm(prep, u, w["in_proj/kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + d["conv_dim"]], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, w["conv/kernel"]) + w["conv/bias"])
    x, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    y = ssd_recurrence(
        x.reshape(bsz, s, heads, p), jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["A_log"]),
        b.reshape(bsz, s, groups, n), c.reshape(bsz, s, groups, n), w["D"],
        SSD_CHECKPOINT if fault == "ssd_state_zeroed" else None)
    y = (y.reshape(bsz, s, inner) * jax.nn.silu(z)).reshape(bsz, s, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + d["eps"])
    return _mm(prep, y.reshape(bsz, s, inner) * w["norm/scale"], w["out_proj/kernel"])


def gqa_attention(u, w, d, prep, q_block=512):
    bsz, s, _ = u.shape
    heads, kv_heads, dim = d["heads"], d["kv_heads"], d["head_dim"]
    q = _mm(prep, u, w["q_proj/kernel"]).reshape(bsz, s, heads, dim)
    k, v = (jnp.repeat(_mm(prep, u, w[f"{n}_proj/kernel"]).reshape(bsz, s, kv_heads, dim),
                       heads // kv_heads, axis=2) for n in "kv")
    blk = q_block if s % q_block == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def rows(q_blk, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", prep(q_blk), prep(k),
                            precision=HIGHEST) * dim ** -0.5
        visible = (first + jnp.arange(blk))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", prep(probs), prep(v), precision=HIGHEST)

    # one block of queries after another (``lax.map``), so that one block's
    # scores exist at a time, backward too: 16 blocks at once take 15 GB
    blocks = jnp.moveaxis(q.reshape(bsz, s // blk, blk, heads, dim), 1, 0)
    out = jax.lax.map(lambda xs: rows(*xs), (blocks, jnp.arange(0, s, blk)))
    out = jnp.moveaxis(out, 0, 1)
    return _mm(prep, out.reshape(bsz, s, heads * dim), w["o_proj/kernel"])


def relu2_ffn(x, up, down, prep):
    return _mm(prep, jnp.square(jax.nn.relu(_mm(prep, x, up))), down)


def expert_ffn(x, w, d, prep, held=None):
    """The experts ``held = (first, count)`` give; the shared expert besides.
    Every held expert is applied to every token, weighted by ``w_e``."""
    first, count = held if held is not None else (d["held_first"], d["held"])
    weights = route(x, w, d)
    y = jnp.zeros_like(x)
    for e in range(count):
        y = y + weights[..., first + e, None] * relu2_ffn(x, w["w_up"][e], w["w_down"][e], prep)
    if d["shared_ffn"]:
        y = y + relu2_ffn(x, w["shared/up/kernel"], w["shared/down/kernel"], prep)
    return y


def layer(x, w, cfg, kind, precision="float32", fault=None):
    """One layer of ``kind`` on ``x [B, S, h]``; ``w`` maps the layer's leaf
    names (``lib.weights_nemotron_h.layer_leaf_shapes``) to arrays."""
    d, prep = N.dims(cfg), _prep(precision)
    if kind == "experts":
        return x + expert_ffn(rms_norm(x, w["ln_mlp/scale"], d["eps"]), _sub(w, "mlp/"), d, prep)
    u, mw = rms_norm(x, w["ln_attn/scale"], d["eps"]), _sub(w, "attention/")
    if kind == "mamba2":
        return x + mamba2_mixer(u, mw, d, prep, fault)
    return x + gqa_attention(u, mw, d, prep)


def hidden_states(w, ids, cfg, precision="float32", fault=None):
    """Final-norm hidden states ``[B, S, h]``; ``w`` is the flat leaf dict."""
    x = w["wte/embedding"][ids]
    for number in range(1, cfg["num_hidden_layers"] + 1):
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, kind=N.kind(cfg, number), precision=precision, fault=fault))(
                x, _sub(w, f"layer_{number - 1}/"))
    return rms_norm(x, w["ln_final/scale"], float(cfg["layer_norm_epsilon"]))


def logits(w, ids, cfg, precision="float32", fault=None):
    return _mm(_prep(precision), hidden_states(w, ids, cfg, precision, fault),
               w["lm_head/kernel"])


def head_sum_ce(w, x, ids, cfg, precision="float32"):
    """Sum of next-token cross entropy from the last layer's output ``x``;
    ``w`` holds ``ln_final/scale`` and ``lm_head/kernel``."""
    hid = rms_norm(x, w["ln_final/scale"], float(cfg["layer_norm_epsilon"]))
    logp = jax.nn.log_softmax(_mm(_prep(precision), hid, w["lm_head/kernel"])[:, :-1],
                              axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def sum_ce(w, ids, cfg, precision="float32", fault=None):
    """Sum over rows and positions of next-token cross entropy."""
    logp = jax.nn.log_softmax(logits(w, ids, cfg, precision, fault)[:, :-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def weights(cfg, seed):
    """The flat leaf dict from the seed, made on the device in one call."""
    shapes = N.leaf_shapes(cfg)
    return jax.jit(lambda key: N.make_leaves(key, cfg, shapes))(W.seed_key(seed))


HEAD = ("ln_final/scale", "lm_head/kernel")
EMBED = "wte/embedding"


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, steps: int = 3,
                rows_block: int = 1, precision: str = "float32",
                keep_rows=None, fault=None) -> dict:
    """Follow the first ``steps`` Adam steps on ``batches`` (each
    ``int32 [rows, seq]``), as ``reference/kimi_linear.py::train_steps`` does
    and for its reason: parameters and Adam's two moments are 8.0 GB of the
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
    lr, b1, b2, aeps = (float(optimizer[k]) for k in ("learning_rate", "b1", "b2", "eps"))
    n_layers = cfg["num_hidden_layers"]
    kinds = [N.kind(cfg, i + 1) for i in range(n_layers)]
    w = weights(cfg, seed)
    groups = [[EMBED]] + [[n for n in w if n.startswith(f"layer_{i}/")]
                          for i in range(n_layers)] + [list(HEAD)]

    @functools.partial(jax.jit, static_argnames=("kind",))
    def forward(x, lw, kind):
        return layer(x, lw, cfg, kind, precision, fault)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def backward(x, lw, dx, acc, kind):
        """``(dx below, acc + this block's gradient)`` of one layer."""
        _, pull = jax.vjp(lambda x_, w_: layer(x_, w_, cfg, kind, precision, fault), x, lw)
        dx, dw = pull(dx)
        return dx, jax.tree.map(jnp.add, acc, dw)

    @jax.jit
    def head(hw, x, ids, acc):
        loss, (dw, dx) = jax.value_and_grad(
            lambda w_, x_: head_sum_ce(w_, x_, ids, cfg, precision), argnums=(0, 1))(hw, x)
        return loss, dx, jax.tree.map(jnp.add, acc, dw)

    @jax.jit
    def embed_backward(dx, ids, acc):
        return acc.at[ids].add(dx)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def adam(w, g, m, v, t, tokens):
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

    shapes, make_leaf = N.leaf_shapes(cfg), N.leaf_maker(cfg)
    delta_norms = jax.jit(lambda w, key: _norms(
        {n: w[n] - make_leaf(key, n, W.name_tag(n), s) for n, s in shapes.items()}))
    m, v = {}, {}                       # on the host (numpy) between steps
    out = {"loss": [], "grad_norm": {}, "delta_norm": None}

    def step_group(names, grads, t, tokens):
        def moment(kept):
            return {n: kept[n] if n in kept else jnp.zeros_like(w[n]) for n in names}

        new_w, new_m, new_v, norms = adam(
            {n: w[n] for n in names}, grads, moment(m), moment(v),
            jnp.float32(t), jnp.float32(tokens))
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
            x, kept = w[EMBED][blk], []
            for i in range(n_layers):
                kept.append(x)
                x = forward(x, {n[len(f"layer_{i}/"):]: w[n] for n in groups[i + 1]},
                            kind=kinds[i])
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
                flowing[j], acc = backward(kept[i], lw, flowing[j], acc, kind=kinds[i])
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
