"""Plain reference of the GPT-2 decoder: straightforward ``jax.numpy`` in
float32 with ``highest`` matmul precision, no kernels, no cache, no batching
tricks. It imports nothing of the program and takes nothing the program has
made: weights come from ``lib.weights`` (seed, leaf name, shape).

Follows the published model (Radford et al. 2019; ``openai-community/gpt2*``
``config.json``): learned positions, pre-LayerNorm blocks, multi-head causal
attention, GELU (tanh approximation, ``gelu_new``), final LayerNorm.
Departure, stated in the configuration files under ``assumed``: the LM head
is a separate (untied) matrix with a bias, as the program builds it.

``precision`` selects the arithmetic: ``"float32"`` is the reference;
``"fp8"`` is the control of "How correct is decided" — the same mathematics
with every matmul's operands rounded to float8 (e4m3 forward, e5m2 for the
gradients that flow back to them, per-tensor scale), the nearest precision
below the bf16 compute that the configurations state.
"""

import functools
import math

import jax
import jax.numpy as jnp

from lib import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _round_fp8(x, dtype, top):
    """Round to a float8 type with a per-tensor scale that puts the largest
    magnitude at ``top``."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8(x):
    """A matmul operand in float8: e4m3 forward, and the gradient that flows
    back to it in e5m2 — the usual float8 training recipe."""
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


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, w, n_head: int, eps: float, precision: str):
    """One decoder block on ``x [B, S, h]``; ``w`` maps the layer's leaf
    names (``lib.weights.layer_leaf_shapes``) to arrays."""
    prep = _prep(precision)

    def dense(a, name):
        return jnp.matmul(prep(a), prep(w[f"{name}/kernel"]),
                          precision=HIGHEST) + w[f"{name}/bias"]

    b, s, h = x.shape
    d = h // n_head
    a = layer_norm(x, w["ln_attn/scale"], w["ln_attn/bias"], eps)
    q = dense(a, "attention/query").reshape(b, s, n_head, d)
    k = dense(a, "attention/key").reshape(b, s, n_head, d)
    v = dense(a, "attention/value").reshape(b, s, n_head, d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", prep(q), prep(k),
                        precision=HIGHEST) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", prep(probs), prep(v),
                     precision=HIGHEST).reshape(b, s, h)
    x = x + dense(ctx, "attention/out")
    m = layer_norm(x, w["ln_mlp/scale"], w["ln_mlp/bias"], eps)
    return x + dense(gelu_new(dense(m, "mlp_in")), "mlp_out")


# ---------------------------------------------------------------------------
# training: loss, gradients and Adam over the first steps, in blocks of rows
# ---------------------------------------------------------------------------

def _stacked_weights(cfg, seed):
    """Global leaves by name plus ``layers``: each layer leaf stacked on a
    leading [n_layer] axis, so that the blocks run under ``lax.scan``."""
    key = W.seed_key(seed)
    lshapes = W.layer_leaf_shapes(cfg)
    glob = {n: s for n, s in W.gpt2_leaf_shapes(cfg).items()
            if not n.startswith("layer_")}

    def make(key):
        out = {n: W.make_leaf(key, n, W.name_tag(n), s) for n, s in glob.items()}
        out["layers"] = {
            n: jnp.stack([W.make_leaf(key, n, W.name_tag(f"layer_{i}/{n}"), s)
                          for i in range(cfg["n_layer"])])
            for n, s in lshapes.items()}
        return out

    return jax.jit(make)(key)


def _sum_ce(w, ids, *, n_head, eps, precision):
    """Sum over rows and positions of next-token cross entropy."""
    prep = _prep(precision)
    s = ids.shape[1]
    x = w["wte/embedding"][ids] + w["wpe/embedding"][jnp.arange(s)][None]

    @jax.checkpoint
    def body(x, lw):
        return block(x, lw, n_head, eps, precision), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    hid = layer_norm(x, w["ln_final/scale"], w["ln_final/bias"], eps)[:, :-1]
    logits = jnp.matmul(prep(hid), prep(w["lm_head/kernel"]),
                        precision=HIGHEST) + w["lm_head/bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = ids[:, 1:]
    return -jnp.sum(jnp.take_along_axis(logp, tgt[..., None], axis=-1))


def _leaf_norms(tree, n_layer):
    out = {}
    for n, v in tree.items():
        if n == "layers":
            for ln, lv in v.items():
                norms = jnp.sqrt(jnp.sum(jnp.square(lv.reshape(n_layer, -1)), axis=1))
                for i in range(n_layer):
                    out[f"layer_{i}/{ln}"] = norms[i]
        else:
            out[n] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def train_steps(cfg: dict, seed: int, batches, optimizer: dict, steps: int = 3,
                rows_block: int = 2, precision: str = "float32",
                keep_rows=None) -> dict:
    """Follow the first ``steps`` Adam steps on ``batches`` (each
    ``int32 [rows, seq]``), gradients accumulated in blocks of rows.

    Returns ``{"loss": [per step], "grad_norm": {leaf: norm of the first
    gradient}, "delta_norm": {leaf: norm of the parameters' change after
    the steps}}``. ``keep_rows`` (a count) plants the fault "half of the
    batch left out, the mean taken over the rest"."""
    n_layer, n_head = cfg["n_layer"], cfg["n_head"]
    eps = float(cfg["layer_norm_epsilon"])
    lr, b1, b2, aeps = (float(optimizer[k]) for k in ("learning_rate", "b1", "b2", "eps"))
    w = _stacked_weights(cfg, seed)
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        _sum_ce, n_head=n_head, eps=eps, precision=precision)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    @jax.jit
    def adam(w, g, m, v, t):
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        w = jax.tree.map(
            lambda w_, m_, v_: w_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + aeps),
            w, m, v)
        return w, m, v

    norms = jax.jit(functools.partial(_leaf_norms, n_layer=n_layer))
    w0_norm_src = w
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    out = {"loss": [], "grad_norm": None, "delta_norm": None}
    for t in range(1, steps + 1):
        ids = jnp.asarray(batches[t - 1], jnp.int32)
        if keep_rows is not None:
            ids = ids[:keep_rows]
        tokens = ids.shape[0] * (ids.shape[1] - 1)
        total, grads = None, None
        for r in range(0, ids.shape[0], rows_block):
            lv, g = grad_fn(w, ids[r:r + rows_block])
            total = lv if total is None else total + lv
            grads = g if grads is None else add(grads, g)
        grads = jax.tree.map(lambda g_: g_ / tokens, grads)
        out["loss"].append(float(total) / tokens)
        if t == 1:
            out["grad_norm"] = {k: float(x) for k, x in
                                jax.device_get(norms(grads)).items()}
        w, m, v = adam(w, grads, m, v, jnp.float32(t))
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(w, w0_norm_src)
    out["delta_norm"] = {k: float(x) for k, x in
                         jax.device_get(norms(delta)).items()}
    return out
