"""Trained target/draft fixture for speculative decoding.

Random weights give near-zero acceptance (the lower bound) and a
self-draft gives exactly 1.0 (the upper bound); neither resembles a
deployed draft/target pair, so tests on either say almost nothing
about real speculative behavior.

This module trains a tiny byte-level target and a smaller draft on the
SAME low-entropy synthetic text for a few hundred Adam steps — enough
for both to lock onto the distribution, so the draft's greedy proposals
agree with the target's often but not always. The whole training loop
is one ``lax.scan`` under one jit per model (seconds on CPU, trivial on
a chip), deterministic by seed.

Text source: sentences drawn from a tiny first-order Markov chain over
a dozen words (seeded). The entropy is low enough that two different
model sizes both learn it quickly, and high enough (branching successors)
that a half-size draft keeps disagreeing with the target sometimes —
which is exactly the regime speculative decoding is for.

Reference counterpart: none (the reference has no generation at all);
the fixture pattern follows the standard practice of evaluating
speculative decoding with a distilled/smaller draft of the same data.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

# word -> possible successors; deterministic-ish chain with branching so
# a smaller model stays imperfect on it
_CHAIN = {
    "the": ["tpu", "mesh", "ring", "chip"],
    "tpu": ["shards", "runs", "compiles"],
    "mesh": ["shards", "holds"],
    "ring": ["passes", "runs"],
    "chip": ["runs", "holds"],
    "shards": ["the"],
    "runs": ["the", "fast", "."],
    "holds": ["the"],
    "passes": ["the"],
    "compiles": ["the", "fast", "."],
    "fast": ["."],
    ".": ["the"],
}


def synthetic_text(n_chars: int, seed: int = 0,
                   skew: float = 0.75) -> str:
    """First successor drawn with p=``skew``, the rest uniform: the
    SKEW is load-bearing. With uniform branching the conditional argmax
    at a branch point is a near-tie, so two independently trained
    models pick branches by optimization noise and greedy acceptance
    collapses (measured: longer training DROPPED acceptance, and
    CPU-f32 vs TPU numerics landed on different sides of 0.5). A clear
    favorite gives both models the same learnable ranking;
    disagreements move to the genuinely hard spots (word boundaries
    under the draft's smaller context capacity), which is the regime
    speculative decoding deploys in. The default rose 0.6 -> 0.75 in
    round 5: 0.6 margins survived CPU f32 (0.84 acceptance) but not
    the TPU's pass-shape reduction noise (0.31 — the draft's s=1
    decode and the target's chunked verify reduce rows in different
    orders, flipping near-argmax ties; the self-draft ceiling itself
    measured 0.944). Bigger margins are the only fix that keeps greedy
    acceptance meaningful across backends."""
    rng = np.random.default_rng(seed)
    words, word = [], "the"
    total = 0
    while total < n_chars:
        words.append(word)
        total += len(word) + 1
        succ = _CHAIN[word]
        if len(succ) == 1:
            word = succ[0]
        else:
            rest = (1.0 - skew) / (len(succ) - 1)
            p = np.asarray([skew] + [rest] * (len(succ) - 1))
            word = succ[int(rng.choice(len(succ), p=p))]
    return " ".join(words)


def _pack_rows(seq_len: int, n_rows: int, seed: int = 0,
               skew: float = 0.75) -> np.ndarray:
    """[n_rows, seq_len] int32 byte tokens cut from one generated stream."""
    from pyspark_tf_gke_tpu.data.text import ByteTokenizer

    tok = ByteTokenizer()
    stream = np.asarray(
        tok.encode(synthetic_text(seq_len * (n_rows + 1), seed=seed,
                                  skew=skew)),
        dtype=np.int32)
    need = seq_len * n_rows
    assert stream.size >= need, "generator under-produced"
    return stream[:need].reshape(n_rows, seq_len)


def _train_lm(model, rows: np.ndarray, steps: int, lr: float,
              seed: int):
    """A few hundred Adam steps over the fixed row set, the whole loop
    inside one jitted ``lax.scan`` (no per-step dispatch overhead)."""
    import jax
    import jax.numpy as jnp
    import optax
    from flax import linen as nn

    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    params = nn.meta.unbox(
        jax.jit(model.init)(make_rng(seed), jnp.asarray(rows[:1]))["params"])
    tx = optax.adam(lr)
    data = jnp.asarray(rows)
    n_rows = rows.shape[0]

    def one_step(carry, i):
        params, opt = carry
        ids = jax.lax.dynamic_index_in_dim(data, i % n_rows, axis=0,
                                           keepdims=True)

        def loss_fn(p):
            logits = model.apply({"params": p}, ids, train=True)
            lg = logits[:, :-1].astype(jnp.float32)
            per = optax.softmax_cross_entropy_with_integer_labels(
                lg, ids[:, 1:])
            return per.mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), opt), loss

    @jax.jit
    def train(params):
        opt = tx.init(params)
        (params, _), losses = jax.lax.scan(
            one_step, (params, opt), jnp.arange(steps))
        return params, losses[-1]

    # HIGHEST matmul precision: on TPU the default f32 matmul uses
    # bf16 passes, which shifts these tiny models' near-argmax logits
    # enough to change greedy agreements — the fixture's acceptance
    # must mean the same thing on every backend (the first full
    # hardware capture measured 0.327 where CPU f32 gives ~0.6, purely
    # from this). Costs nothing at h64/h32 scale.
    with jax.default_matmul_precision("highest"):
        params, _ = train(params)
    return params


def make_spec_fixture(steps: int = 1500, seq_len: int = 64,
                      seed: int = 0, skew: float = 0.75) -> Tuple:
    """Returns ``(target, tparams, draft, dparams, prompt)``: a trained
    2-layer h64 byte target, a trained 1-layer h32 draft (same data),
    and an in-distribution prompt row. Deterministic by seed.

    The 1500-step default and the skewed chain are sized for BACKEND
    ROBUSTNESS, not convergence: with uniform branching, acceptance was
    noise (0.59 CPU / 0.33 TPU at 400 steps; MORE training made it
    WORSE on CPU — 0.45 at 1500 — because sharper models tie-break
    branch points differently). The 0.6-skewed chain made the ranking
    learnable (0.84 on CPU f32) but its margins still lost to TPU
    pass-shape reduction noise (0.31 measured, against a 0.944
    self-draft ceiling); skew 0.75 keeps the CPU middle (0.818 at 1500
    steps) with roughly doubled logit margins for the TPU argmax to
    hold (not measured on the chip since)."""
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig

    common = dict(vocab_size=259, intermediate_size=128, max_seq_len=256,
                  dtype=jnp.float32)
    tcfg = CausalLMConfig(hidden_size=64, num_layers=2, num_heads=4,
                          **common)
    dcfg = CausalLMConfig(hidden_size=32, num_layers=1, num_heads=2,
                          **{**common, "intermediate_size": 64})
    rows = _pack_rows(seq_len, n_rows=32, seed=seed, skew=skew)
    target, draft = CausalLM(tcfg), CausalLM(dcfg)
    tparams = _train_lm(target, rows, steps, lr=3e-3, seed=seed)
    dparams = _train_lm(draft, rows, steps, lr=3e-3, seed=seed + 1)
    prompt = jnp.asarray(_pack_rows(16, n_rows=1, seed=seed + 2,
                                    skew=skew))
    return target, tparams, draft, dparams, prompt
