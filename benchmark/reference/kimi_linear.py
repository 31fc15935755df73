"""Plain reference of the Kimi-Linear decoder as ``configs/
kimi-linear-48b-a3b.json`` cuts it: straightforward ``jax.numpy`` in float32
with ``highest`` matmul precision, no kernels, no chunked algebra, no sorting
of tokens. It imports nothing of the program and takes nothing the program
has made: weights come from ``lib.weights_kimi_linear`` (seed, leaf name,
shape).

Follows the published model (``moonshotai/Kimi-Linear-48B-A3B-Instruct``
``config.json``; Kimi Linear technical report, 2025). All norms are RMSNorm,
pre-norm blocks ``x += Attn(norm(x))``, ``x += FFN(norm(x))``, final norm,
untied head, no biases. With ``x [T, h]``:

* **KDA** (layers in ``linear_attn_config.kda_layers``), per head of 32,
  ``d_k = d_v = 128``: ``q, k = L2norm(silu(conv4(x W_q)))``,
  ``L2norm(silu(conv4(x W_k)))``, ``v = silu(conv4(x W_v))`` (depthwise causal
  convolution of 4 taps); ``q *= 128^-1/2``; log-decay
  ``g_t = -exp(A_log[h]) * softplus((x W_fa W_fb)_t + dt_bias)`` in ``R^128``,
  ``beta_t = sigmoid(x W_b)``; state ``S`` in ``R^{128x128}``, ``S_0 = 0``::

      S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  out ``= (RMSNorm_128(o_t) * sigmoid((x W_ga W_gb)_t)) W_o``. The recurrence
  runs one token at a time (``lax.scan``), under ``jax.checkpoint`` per 64
  steps so that its backward fits.
* **MLA without positions** (``full_attn_layers``; ``mla_use_nope``):
  ``q = x W_q`` -> 32 x 192; ``c = RMSNorm_512((x W_kva)[:512])``,
  ``k_pe = (x W_kva)[512:]`` (64, shared by all heads, not rotated);
  ``[k_nope | v] = c W_kvb`` -> 32 x (128 + 128); ``k = [k_nope | k_pe]``;
  causal softmax attention at scale ``192^-1/2``, in blocks of queries;
  out ``= concat_h(o_h) W_o``.
* **Experts** (layers past ``first_k_dense_replace``): ``s = sigmoid(x W_r)``
  in ``R^256``; the top 8 of ``s + b`` are chosen (``b`` a buffer with no
  gradient); ``w_e = 2.446 * s_e / sum_chosen s``;
  ``y = sum_chosen w_e E_e(x) + E_shared(x)``,
  ``E(x) = (silu(x W_1) * (x W_3)) W_2``.

Departures, stated in the configuration file: the sum over chosen experts
runs over those this chip holds (experts ``held_first .. held_first + held -
1``) only, and that partial ``y`` goes on; the vocabulary is the slice; the
gates' rank, the buffer ``b`` and the weights are ``assumed``. Each held
expert is applied to every token and weighted by ``w_e`` (0 where it was not
chosen): the same sum, with no sorting.

``precision`` selects the arithmetic: ``"float32"`` is the reference;
``"fp8"`` is the control: every matmul's operands rounded to float8 (e4m3
forward, e5m2 for the gradients that flow back to them, per-tensor scale),
the nearest precision below the bf16 compute the configuration states.
``fault`` plants a fault for ``tools/control_kimi_linear.py``:
``"kda_state_zeroed"`` loses KDA's state at every 64th token.
"""

import functools

import jax
import jax.numpy as jnp

from lib import weights as W
from lib import weights_kimi_linear as K

HIGHEST = jax.lax.Precision.HIGHEST
KDA_CHECKPOINT = 64          # tokens of the recurrence per checkpoint
L2_EPS = 1e-6


def _round_fp8(x, dtype, top):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8(x):
    """A matmul operand in float8: e4m3 forward, and the gradient that flows
    back to it in e5m2."""
    return _round_fp8(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_fp8(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _prep(precision):
    if precision == "float32":
        return lambda x: x
    if precision == "fp8":
        return _fp8
    raise ValueError(f"unknown reference precision {precision!r}")


def _mm(prep, a, b):
    return jnp.matmul(prep(a), prep(b), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, w):
    """Depthwise causal convolution over time: ``x [B, S, C]``, ``w [taps, C]``,
    the last tap on the current token."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s] * w[j] for j in range(taps))


def kda_recurrence(q, k, v, g, beta, zero_state_every=None):
    """The recurrence above, one token at a time. ``q, k, v, g [B, S, H, 128]``,
    ``beta [B, S, H]``; returns ``o [B, S, H, 128]``."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t, t = xs                  # [B, H, d], [B, H], []
        if zero_state_every:
            state = jnp.where(t % zero_state_every == 0, 0.0, state)
        state = state * jnp.exp(g_t)[..., None]          # diag(exp(g)) S
        kS = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=HIGHEST)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t * b_t[..., None],
                                   v_t - kS, precision=HIGHEST)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state, precision=HIGHEST)

    @jax.checkpoint
    def stretch(state, xs):
        return jax.lax.scan(token, state, xs)

    n = KDA_CHECKPOINT if s % KDA_CHECKPOINT == 0 else 1

    def by_time(x):                                      # [B, S, ...] -> [S/n, n, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((s // n, n) + x.shape[1:])

    xs = tuple(by_time(x) for x in (q, k, v, g, beta)) + (
        jnp.arange(s).reshape(s // n, n),)
    _, o = jax.lax.scan(stretch, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def kda_attention(x, w, d, prep, fault=None):
    b, s, _ = x.shape
    heads, dim = d["kda_heads"], d["kda_dim"]

    def mixed(name):
        y = causal_conv(_mm(prep, x, w[f"{name}_proj/kernel"]), w[f"{name}_conv/kernel"])
        return jax.nn.silu(y).reshape(b, s, heads, dim)

    q = l2_norm(mixed("q")) * dim ** -0.5
    k = l2_norm(mixed("k"))
    v = mixed("v")
    f = _mm(prep, _mm(prep, x, w["f_a/kernel"]), w["f_b/kernel"])
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (f + w["dt_bias"]).reshape(b, s, heads, dim))
    beta = jax.nn.sigmoid(_mm(prep, x, w["b_proj/kernel"]))
    o = kda_recurrence(q, k, v, g, beta,
                       KDA_CHECKPOINT if fault == "kda_state_zeroed" else None)
    gate = _mm(prep, _mm(prep, x, w["g_a/kernel"]), w["g_b/kernel"])
    o = rms_norm(o, w["o_norm/scale"], d["eps"]) * jax.nn.sigmoid(
        gate.reshape(b, s, heads, dim))
    return _mm(prep, o.reshape(b, s, heads * dim), w["o_proj/kernel"])


def mla_attention(x, w, d, prep, q_block=512):
    b, s, _ = x.shape
    heads, nope, rope, dv, rank = (d[k] for k in ("heads", "nope", "rope", "v_dim", "kv_rank"))
    q = _mm(prep, x, w["q_proj/kernel"]).reshape(b, s, heads, nope + rope)
    kva = _mm(prep, x, w["kv_a/kernel"])
    c = rms_norm(kva[..., :rank], w["kv_norm/scale"], d["eps"])
    k_pe = kva[..., rank:]                               # [B, S, 64], not rotated
    kvb = _mm(prep, c, w["kv_b/kernel"]).reshape(b, s, heads, nope + dv)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe[:, :, None, :], (b, s, heads, rope))], axis=-1)
    v = kvb[..., nope:]
    scale = (nope + rope) ** -0.5
    blk = q_block if s % q_block == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def rows(q_blk, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", prep(q_blk), prep(k),
                            precision=HIGHEST) * scale
        visible = (first + jnp.arange(blk))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", prep(probs), prep(v), precision=HIGHEST)

    out = [rows(q[:, i:i + blk], i) for i in range(0, s, blk)]
    out = jnp.concatenate(out, axis=1).reshape(b, s, heads * dv)
    return _mm(prep, out, w["o_proj/kernel"])


def swiglu(x, gate, up, down, prep):
    return _mm(prep, jax.nn.silu(_mm(prep, x, gate)) * _mm(prep, x, up), down)


def route(x, w, d):
    """``[T, router]`` weight of each expert for each token: ``w_e`` where the
    expert was chosen, 0 elsewhere. The router's matmul is not put in float8
    by the control: the configuration states it in float32."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router/kernel"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(w["router_bias"]), d["top_k"])
    picked = jnp.sum(jax.nn.one_hot(chosen, d["router"], dtype=s.dtype), axis=-2)
    kept = s * picked
    return d["route_scale"] * kept / jnp.sum(kept, axis=-1, keepdims=True)


def expert_ffn(x, w, d, prep, held=None):
    """The experts ``held = (first, count)`` give; the shared expert besides.
    Every held expert is applied to every token, weighted by ``w_e``."""
    first, count = held if held is not None else (d["held_first"], d["held"])
    weights = route(x, w, d)
    y = jnp.zeros_like(x)
    for e in range(count):
        y = y + weights[..., first + e, None] * swiglu(
            x, w["w_gate"][e], w["w_up"][e], w["w_down"][e], prep)
    if d["shared"]:
        y = y + swiglu(x, w["shared/gate/kernel"], w["shared/up/kernel"],
                       w["shared/down/kernel"], prep)
    return y


def _sub(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def layer(x, w, cfg, kinds, precision="float32", fault=None):
    """One layer of ``kinds = (attention kind, FFN kind)`` on ``x [B, S, h]``;
    ``w`` maps the layer's leaf names
    (``lib.weights_kimi_linear.layer_leaf_shapes``) to arrays."""
    d, prep = K.dims(cfg), _prep(precision)
    a = rms_norm(x, w["ln_attn/scale"], d["eps"])
    if kinds[0] == "kda":
        x = x + kda_attention(a, _sub(w, "attention/"), d, prep, fault)
    else:
        x = x + mla_attention(a, _sub(w, "attention/"), d, prep)
    m = rms_norm(x, w["ln_mlp/scale"], d["eps"])
    mw = _sub(w, "mlp/")
    if kinds[1] == "dense":
        return x + swiglu(m, mw["gate/kernel"], mw["up/kernel"], mw["down/kernel"], prep)
    return x + expert_ffn(m, mw, d, prep)


def layer_kinds(cfg, number):
    """``(attention kind, FFN kind)`` of the 1-based layer ``number``."""
    return K.attention_kind(cfg, number), K.ffn_kind(cfg, number)


def hidden_states(w, ids, cfg, precision="float32", fault=None):
    """Final-norm hidden states ``[B, S, h]``; ``w`` is the flat leaf dict."""
    x = w["wte/embedding"][ids]
    for number in range(1, cfg["num_hidden_layers"] + 1):
        lw = _sub(w, f"layer_{number - 1}/")
        x = jax.checkpoint(functools.partial(
            layer, cfg=cfg, kinds=layer_kinds(cfg, number), precision=precision,
            fault=fault))(x, lw)
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
    shapes = K.leaf_shapes(cfg)
    return jax.jit(lambda key: K.make_leaves(key, shapes))(W.seed_key(seed))


def _norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v))) for n, v in tree.items()}


HEAD = ("ln_final/scale", "lm_head/kernel")
EMBED = "wte/embedding"


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, steps: int = 3,
                rows_block: int = 1, precision: str = "float32",
                keep_rows=None, fault=None) -> dict:
    """Follow the first ``steps`` Adam steps on ``batches`` (each
    ``int32 [rows, seq]``).

    Parameters and Adam's two moments are 7.2 GB of the chip's 16 at the cut
    configuration and one layer's backward takes 5 GB more (float32, 8,192
    tokens), so the whole model's gradient never exists at once and the
    moments wait on the host between a group's steps: backpropagation is
    written out a layer at a time. The forward keeps each
    layer's input for each block of ``rows_block`` rows; then, from the head
    down, a layer's gradient is summed over the blocks (``jax.vjp`` of that
    layer alone), its norm noted, its Adam step taken, and the gradient let
    go. The result is ``jax.grad`` of :func:`sum_ce` and Adam on all leaves
    at once (the CPU tests compare them); the start is made again from the
    seed at the end and not kept.

    Returns ``{"loss": [per step], "grad_norm": {leaf: norm of the first
    gradient}, "delta_norm": {leaf: norm of the parameters' change after the
    steps}}``. ``keep_rows`` (a count) plants the fault "half of the batch left
    out, the mean taken over the rest"; ``fault`` is handed to the layers."""
    lr, b1, b2, aeps = (float(optimizer[k]) for k in ("learning_rate", "b1", "b2", "eps"))
    n_layers = cfg["num_hidden_layers"]
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

    shapes = K.leaf_shapes(cfg)
    delta_norms = jax.jit(lambda w, key: _norms(
        {n: w[n] - K.make_leaf(key, n, W.name_tag(n), s) for n, s in shapes.items()}))
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
                            kinds=layer_kinds(cfg, i + 1))
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
                flowing[j], acc = backward(kept[i], lw, flowing[j], acc,
                                           kinds=layer_kinds(cfg, i + 1))
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
