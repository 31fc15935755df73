"""Speculative decoding: a small draft model proposes, the target model
verifies — greedy-exact.

No counterpart in the reference (it has no serving at all; SURVEY §5).
This is the latency lever for single-stream serving: autoregressive
decode runs one HBM-bound step per token, but a TARGET-model forward
over a CHUNK of gamma+1 tokens costs barely more than one step (same
weight streaming, gamma+1 columns of compute). So a cheap draft model
autoregresses gamma candidate tokens, and the target scores the whole
proposal in ONE chunk forward against its KV cache
(``CausalSelfAttention._decode_attend`` handles s>1 with the causal
offset mask). Accepted prefix + one correction token emit per round:
between 1 and gamma+1 tokens per target forward.

Greedy acceptance (``d_i == argmax(target logits at i-1)``) makes the
output PROVABLY identical to plain greedy decoding of the target model
— ``tests/test_speculative.py`` asserts token-for-token equality, and
the draft model only affects speed, never content.

Cache bookkeeping: both models' caches are flax "cache" pytrees whose
scalar ``index`` leaf is the fill level and whose suffix past it is
masked, so ROLLBACK after a rejected proposal is just resetting
``index`` — the stale K/V rows beyond it are invisible and will be
overwritten. Batch is restricted to 1: acceptance length varies per
row, and the scalar fill index (deliberately scalar — it keeps decode
masks cheap) cannot roll rows back independently. Speculation is a
latency tool; batch throughput is better served by plain batched decode.

Two round-loop drivers share the per-round pieces (draft scan, target
chunk forward — module-level jits keyed by static shapes):

- **host loop**: each round syncs the accepted count to the host (the
  classic speculative-decoding structure): 2-3 blocking host
  readbacks per round, so its floor is the dispatch latency. The
  2026-08 trail measured it at 66.5 ms per dispatch, where that floor
  dwarfed the compute; not measured on a local chip.
- **device loop** (``_device_rounds``): the ENTIRE propose → verify →
  accept → rollback iteration runs inside one ``lax.while_loop`` — a
  whole generation is ONE dispatch with ONE readback at the end. The
  per-round variable advance (1..gamma+1 tokens) stays static-shaped:
  accepted drafts + correction are written as a fixed (gamma+1)-wide
  masked window into a token buffer, and the draft cache is resynced by
  REWRITING the last gamma+1 rows before the fill point from that
  buffer each round (a fixed-width chunk feed; rewriting a row with its
  own token/position is idempotent, and rows past the fill index are
  invisible by the cache mask).

``speculative_generate`` auto-picks the device loop whenever the
slightly stricter sequence bound fits (the verify chunk may overhang by
gamma; see the validation) — both drivers emit the target model's own
greedy tokens, so the choice affects speed only.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pyspark_tf_gke_tpu.models.causal_lm import CausalLM, _prefill

# ---------------------------------------------------------------------------
# THE acceptance rule — one implementation site.
#
# Both speculative drivers here AND the continuous-batching engine's
# in-slot speculation (train/continuous.py ``_spec_chunk``) accept a
# draft proposal through these helpers, so the acceptance semantics
# cannot drift between the standalone drivers and the serving plane.
# ---------------------------------------------------------------------------


def greedy_accept_len(drafts, target_picks):
    """Greedy acceptance: number of leading draft tokens that equal the
    target's own pick at the position before them. ``drafts [..., k]``
    vs ``target_picks [..., k]`` (the target's argmax at positions
    0..k-1 of the verify chunk) -> ``[...]`` int32 accepted-prefix
    length in [0, k]. Accepting exactly this prefix makes the emitted
    stream PROVABLY identical to plain greedy decoding of the target
    model — the draft affects speed only, never content."""
    match = (drafts == target_picks).astype(jnp.int32)
    return jnp.sum(jnp.cumprod(match, axis=-1), axis=-1)


def emit_window(drafts, correction, accepted):
    """Fixed-width emission window ``[..., k+1]``: positions below
    ``accepted`` carry the accepted drafts, position ``accepted`` the
    correction/bonus token, and the tail repeats the correction (static
    shapes; callers mask or overwrite past the frontier). Shared by the
    device-loop driver below and the engine's spec rounds."""
    k = drafts.shape[-1]
    iota = jnp.arange(k + 1, dtype=jnp.int32)
    padded = jnp.concatenate(
        [drafts, jnp.zeros_like(drafts[..., :1])], axis=-1)
    return jnp.where(iota < accepted[..., None], padded,
                     correction[..., None])


def accept_and_correct(drafts, draft_logits, target_logits, *,
                       temps=None, topps=None, keys=None, mesh=None):
    """Batched accept + correct, one rule per sampling lane.

    ``drafts [B, k]`` proposed tokens; ``draft_logits [B, k, V]`` the
    logits each draft token was picked from; ``target_logits
    [B, k+1, V]`` the verify chunk's logits (position i scores the
    token AFTER feeding draft i-1). Returns ``(accepted [B],
    correction [B])``.

    Greedy rows (``temps == 0``): accept while the draft equals the
    target argmax — exact. Sampling rows: the standard speculative
    rejection rule (Leviathan et al.): draft token d_i sampled from
    q_i is kept with probability min(1, p_i(d_i)/q_i(d_i)); on the
    first rejection the correction samples from the residual
    ``norm(max(p - q, 0))``, and a fully-accepted proposal samples the
    bonus token from p_k directly (the q-at-k row is zero-padded, so
    the residual formula degenerates to exactly p_k). Temperature and
    top-p shape BOTH distributions identically, so the rule stays a
    valid sampler for the filtered target distribution. ``keys``
    ``[B, 2]`` uint32 threefry key data drives the uniforms and the
    correction draw (greedy rows never read them); pass
    ``temps=None`` for an all-greedy pool (the sampling math compiles
    out). ``mesh``: on a tensor-parallel mesh the sampled path must
    replicate the small [B, k(+1), V] working sets before the nucleus
    sort/cumsum — the same guard as the engine's ``_pick_tokens``
    (a vocab-sharded sort would compile fresh cross-process
    collectives mid-serving, the documented 2-process-wire deadlock
    class)."""
    from pyspark_tf_gke_tpu.models.causal_lm import _filter_logits

    k = drafts.shape[-1]
    tgt_pick = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
    a_greedy = greedy_accept_len(drafts, tgt_pick[..., :k])
    corr_greedy = jnp.take_along_axis(
        tgt_pick, a_greedy[..., None], axis=-1)[..., 0]
    if temps is None:
        return a_greedy, corr_greedy

    def dist(logits):
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None, None]
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            scaled = jax.lax.with_sharding_constraint(
                scaled, NamedSharding(mesh, PartitionSpec()))
        return jax.nn.softmax(
            _filter_logits(scaled, None, topps[:, None, None]), axis=-1)

    q = dist(draft_logits)                                 # [B, k, V]
    p_full = dist(target_logits)                           # [B, k+1, V]
    q_d = jnp.take_along_axis(q, drafts[..., None], -1)[..., 0]
    p_d = jnp.take_along_axis(p_full[:, :k], drafts[..., None],
                              -1)[..., 0]
    base = jax.vmap(
        lambda kd: jax.random.wrap_key_data(kd, impl="threefry2x32"))(keys)
    u_keys = jax.vmap(lambda kk: jax.random.fold_in(kk, 0))(base)
    c_keys = jax.vmap(lambda kk: jax.random.fold_in(kk, 1))(base)
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(u_keys)
    ok = (u * jnp.maximum(q_d, 1e-20) < p_d).astype(jnp.int32)
    a_samp = jnp.sum(jnp.cumprod(ok, axis=-1), axis=-1)
    p_a = jnp.take_along_axis(p_full, a_samp[:, None, None],
                              axis=1)[:, 0]                # [B, V]
    q_pad = jnp.concatenate([q, jnp.zeros_like(q[:, :1])], axis=1)
    q_a = jnp.take_along_axis(q_pad, a_samp[:, None, None],
                              axis=1)[:, 0]
    resid = jnp.maximum(p_a - q_a, 0.0)
    resid = resid / jnp.maximum(resid.sum(-1, keepdims=True), 1e-20)
    corr_samp = jax.vmap(jax.random.categorical)(
        c_keys, jnp.log(jnp.maximum(resid, 1e-30))).astype(jnp.int32)
    sampled = temps > 0
    return (jnp.where(sampled, a_samp, a_greedy),
            jnp.where(sampled, corr_samp, corr_greedy))


def _set_cache_index(cache, value):
    """Return a cache pytree with every scalar ``index`` leaf set to
    ``value`` (rollback / sync). Structure-generic: works per layer."""
    val = jnp.asarray(value, jnp.int32)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: val
        if any(getattr(k, "key", None) == "index" for k in path) else leaf,
        cache)


@partial(jax.jit, static_argnames=("model", "cache_only"))
def _extend(model: CausalLM, params, cache, chunk, pos,
            cache_only: bool = False):
    """Feed ``chunk [B, c]`` against the cache at fill ``pos``: returns
    ``(logits [B, c, V], cache)`` with fill = pos + c. One forward —
    this is the verify step. ``cache_only`` (the draft resync) skips the
    lm_head projection via ``return_hidden=True`` and returns
    ``(None, cache)`` — nobody reads those logits, and the [c, vocab]
    matmul is the chunk's dominant cost."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    b, c = chunk.shape
    positions = pos + jnp.arange(c, dtype=jnp.int32)[None, :]
    out, mutated = model.apply(
        {"params": dequantize_tree(params), "cache": cache}, chunk,
        decode=True, positions=jnp.broadcast_to(positions, (b, c)),
        return_hidden=cache_only, mutable=["cache"])
    return (None if cache_only else out), mutated["cache"]


@partial(jax.jit, static_argnames=("model", "gamma"))
def _draft_propose(model: CausalLM, params, cache, last_tok, pos, gamma: int):
    """Greedy-autoregress ``gamma`` draft tokens starting from
    ``last_tok`` at fill ``pos``. Returns proposals ``[B, gamma]`` and
    the updated draft cache, which now holds last_tok .. d_{gamma-2}
    (the final proposal d_{gamma-1} is sampled but never fed, so it is
    not cached — fill grows by exactly gamma rows)."""
    from pyspark_tf_gke_tpu.ops.quant import dequantize_tree

    p = dequantize_tree(params)
    b = last_tok.shape[0]

    def step(carry, t):
        cache, tok = carry
        logits, mutated = model.apply(
            {"params": p, "cache": cache}, tok[:, None], decode=True,
            positions=jnp.broadcast_to(pos + t, (b, 1)).astype(jnp.int32),
            mutable=["cache"])
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return (mutated["cache"], nxt), nxt

    (cache, _), toks = jax.lax.scan(
        step, (cache, last_tok), jnp.arange(gamma, dtype=jnp.int32))
    return toks.T, cache  # [B, gamma]


def _pad_after_eos(out, max_new_tokens: int, eos_token_id: Optional[int]):
    """``generate()``'s output contract: truncate at the first eos and
    pad with it to the fixed length; without eos, repeat the last
    token."""
    if eos_token_id is not None and eos_token_id in out:
        stop = out.index(eos_token_id)
        return out[:stop + 1] + [eos_token_id] * (max_new_tokens - stop - 1)
    return out + [out[-1]] * (max_new_tokens - len(out))


@partial(jax.jit, static_argnames=("target_model", "draft_model", "gamma",
                                   "max_new_tokens", "eos_token_id"))
def _device_rounds(target_model: CausalLM, target_params,
                   draft_model: CausalLM, draft_params,
                   t_cache, d_cache, all_tokens, s_prompt,
                   gamma: int, max_new_tokens: int,
                   eos_token_id: Optional[int]):
    """The whole speculative round loop as ONE jitted ``while_loop``.

    ``all_tokens [1, s_prompt + max_new + gamma + 1]`` starts as
    prompt + first-emitted-token (+ zero tail); rounds append through a
    fixed-width masked window. Returns the filled buffer plus
    ``(n_emitted, rounds, accepted)`` scalars — the only host readback
    of the generation.
    """
    g = gamma
    width = g + 1  # verify chunk = [newest emitted, d_0..d_{g-1}]
    iota = jnp.arange(width, dtype=jnp.int32)

    def body(carry):
        (all_toks, n_emitted, t_cache, d_cache, done, rounds, proposed,
         accepted) = carry
        t_fill = s_prompt + n_emitted - 1  # rows FED to the target

        # 1. draft resync: rewrite the last `width` rows before t_fill
        #    from the token buffer. Any round advances <= width rows, so
        #    the window always covers whatever a previous round left
        #    stale; near the sequence start it clamps to 0 and the
        #    out-of-frontier columns it feeds land past the fill index —
        #    invisible, and overwritten by the very next propose.
        start = jnp.maximum(t_fill - width, 0)
        chunk = jax.lax.dynamic_slice(all_toks, (0, start), (1, width))
        d_synced = _set_cache_index(d_cache, start)
        _, d_synced = _extend(
            draft_model, draft_params, d_synced, chunk, start,
            cache_only=True)
        d_synced = _set_cache_index(d_synced, t_fill)

        # 2. propose + 3. verify — the same jitted pieces the host loop
        #    uses (they inline here)
        last_tok = jax.lax.dynamic_slice(all_toks, (0, t_fill), (1, 1))[:, 0]
        drafts, d_synced = _draft_propose(
            draft_model, draft_params, d_synced, last_tok, t_fill, g)
        vchunk = jnp.concatenate([last_tok[:, None], drafts], axis=1)
        t_next = _set_cache_index(t_cache, t_fill)
        logits, t_next = _extend(
            target_model, target_params, t_next, vchunk, t_fill)
        preds = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)  # [g+1]

        # 4. greedy acceptance + fixed-width emit (the shared rule:
        #    greedy_accept_len / emit_window — one implementation with
        #    the engine's in-slot speculation): positions < a carry
        #    accepted drafts, position a the correction token, and the
        #    tail repeats the correction — written past the frontier and
        #    overwritten by the next round's window.
        a = greedy_accept_len(drafts[0], preds[:-1])
        window = emit_window(drafts[0], preds[a], a)
        all_toks = jax.lax.dynamic_update_slice(
            all_toks, window[None], (0, s_prompt + n_emitted))
        if eos_token_id is not None:
            done = done | jnp.any(
                (window == eos_token_id) & (iota <= a))
        # Stats use the HOST loop's budget-capped definitions: the host
        # drafts only min(gamma, budget) in a short final round, while
        # this loop always drafts gamma (static shapes) and trims the
        # overshoot on readback — counting the raw gamma would bias
        # acceptance low and tokens/round high for short generations.
        budget = max_new_tokens - n_emitted
        g_eff = jnp.minimum(g, budget)
        proposed = proposed + g_eff
        accepted = accepted + jnp.minimum(a, g_eff)
        n_emitted = n_emitted + a + 1

        # 5. rollback = index reset (stale rows are invisible)
        new_fill = s_prompt + n_emitted - 1
        t_next = _set_cache_index(t_next, new_fill)
        d_synced = _set_cache_index(d_synced, new_fill)
        return (all_toks, n_emitted, t_next, d_synced, done,
                rounds + 1, proposed, accepted)

    def cond(carry):
        _, n_emitted, _, _, done, _, _, _ = carry
        return jnp.logical_and(n_emitted < max_new_tokens,
                               jnp.logical_not(done))

    done0 = jnp.asarray(False)
    if eos_token_id is not None:  # prefill's token may already end it
        done0 = jnp.squeeze(jax.lax.dynamic_slice(
            all_tokens, (0, s_prompt), (1, 1)) == eos_token_id)
    init = (all_tokens, jnp.asarray(1, jnp.int32), t_cache, d_cache,
            done0, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32))
    (all_toks, n_emitted, _, _, _, rounds, proposed,
     accepted) = jax.lax.while_loop(cond, body, init)
    return all_toks, n_emitted, rounds, proposed, accepted


def speculative_generate(
    target_model: CausalLM,
    target_params,
    draft_model: CausalLM,
    draft_params,
    prompt_ids,                      # [1, S_prompt] int32
    max_new_tokens: int,
    gamma: int = 4,
    eos_token_id: Optional[int] = None,
    return_stats: bool = False,
    device_loop: Optional[bool] = None,
) -> jnp.ndarray:
    """Greedy generation from the TARGET model, accelerated by a draft.

    Returns ``[1, S_prompt + max_new_tokens]`` — identical tokens to
    ``generate(target_model, target_params, prompt_ids, ...)`` greedy
    (after eos, positions pad with eos). With ``return_stats`` also
    returns ``{"rounds": r, "proposed": p, "accepted": a}``.

    ``device_loop`` selects the driver: ``True`` forces the one-dispatch
    ``lax.while_loop`` form, ``False`` the per-round host-sync form,
    ``None`` (default) picks the device loop whenever its slightly
    stricter bound fits — the in-loop verify chunk may overhang the
    final token by up to ``gamma``, so it needs
    ``s_prompt + max_new_tokens + gamma - 1 <= max_seq_len`` on both
    models (the host loop shrinks its last chunks instead).
    """
    if prompt_ids.shape[0] != 1:
        raise ValueError(
            f"speculative decoding is batch-1 (latency tool; the scalar "
            f"cache fill index cannot roll rows back independently), "
            f"got batch {prompt_ids.shape[0]}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if target_model.cfg.vocab_size != draft_model.cfg.vocab_size:
        raise ValueError(
            f"draft vocab {draft_model.cfg.vocab_size} != target vocab "
            f"{target_model.cfg.vocab_size}: the models must share a "
            f"tokenizer")
    s_prompt = prompt_ids.shape[1]
    if s_prompt + max_new_tokens > target_model.cfg.max_seq_len:
        raise ValueError(
            f"prompt {s_prompt} + {max_new_tokens} new tokens exceeds the "
            f"target's max_seq_len {target_model.cfg.max_seq_len}")
    if s_prompt + max_new_tokens > draft_model.cfg.max_seq_len:
        raise ValueError(
            f"prompt {s_prompt} + {max_new_tokens} new tokens exceeds the "
            f"DRAFT's max_seq_len {draft_model.cfg.max_seq_len}")

    device_fits = (
        s_prompt + max_new_tokens + gamma - 1 <= target_model.cfg.max_seq_len
        and s_prompt + max_new_tokens + gamma - 1
        <= draft_model.cfg.max_seq_len)
    if device_loop is None:
        device_loop = device_fits
    elif device_loop and not device_fits:
        raise ValueError(
            f"device_loop needs prompt {s_prompt} + {max_new_tokens} new "
            f"+ gamma {gamma} - 1 within both models' max_seq_len "
            f"(target {target_model.cfg.max_seq_len}, draft "
            f"{draft_model.cfg.max_seq_len}); use device_loop=None/False")

    # Prefill both models on the prompt. The target's last-token logits
    # give the first emitted token for free.
    t_cache, t_last = _prefill(target_model, target_params, prompt_ids)
    d_cache, _ = _prefill(draft_model, draft_params, prompt_ids)

    # host readbacks route through as_host_array: on a multi-process
    # mesh these drive the (deterministic) control flow, so every
    # process must read the same values — a bare np.asarray would raise
    # on non-addressable shards instead
    from pyspark_tf_gke_tpu.parallel.distributed import as_host_array

    if device_loop:
        buf = jnp.zeros((1, s_prompt + max_new_tokens + gamma + 1),
                        jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, prompt_ids, (0, 0))
        first_tok = jnp.argmax(t_last, axis=-1).astype(jnp.int32)
        buf = jax.lax.dynamic_update_slice(
            buf, first_tok[:, None], (0, s_prompt))
        all_toks, n_emitted, rounds, proposed, accepted = _device_rounds(
            target_model, target_params, draft_model, draft_params,
            t_cache, d_cache, buf, jnp.asarray(s_prompt, jnp.int32),
            gamma, max_new_tokens, eos_token_id)
        host_buf = np.asarray(as_host_array(all_toks))[0]
        n_emitted = int(np.asarray(as_host_array(n_emitted)))
        rounds = int(np.asarray(as_host_array(rounds)))
        proposed_total = int(np.asarray(as_host_array(proposed)))
        accepted_total = int(np.asarray(as_host_array(accepted)))
        emitted = [int(t) for t in
                   host_buf[s_prompt:s_prompt + min(n_emitted,
                                                    max_new_tokens)]]
        out = _pad_after_eos(emitted, max_new_tokens, eos_token_id)
        result = jnp.concatenate(
            [prompt_ids, jnp.asarray([out], jnp.int32)], axis=1)
        if return_stats:
            return result, {"rounds": rounds, "proposed": proposed_total,
                            "accepted": accepted_total,
                            "tokens_per_round":
                            (min(n_emitted, max_new_tokens) - 1)
                            / max(rounds, 1)}
        return result

    first = int(np.asarray(as_host_array(jnp.argmax(t_last, axis=-1)))[0])
    emitted = [first]
    # fill levels: cache rows written so far (prompt only; the freshly
    # emitted token is fed next round)
    t_fill = d_fill = s_prompt
    rounds = proposed = accepted_total = 0

    while len(emitted) < max_new_tokens and (
            eos_token_id is None or eos_token_id not in emitted):
        rounds += 1
        budget = max_new_tokens - len(emitted)
        g = min(gamma, budget)

        # 1. draft syncs on any emitted tokens it hasn't cached yet
        #    (everything but the newest, which _draft_propose feeds):
        #    the draft cache holds the first d_fill tokens of
        #    prompt+emitted, so the gap is emitted[d_fill - s_prompt
        #    : -1].
        pending = emitted[d_fill - s_prompt:len(emitted) - 1]
        if pending:
            chunk = jnp.asarray([pending], jnp.int32)
            _, d_cache = _extend(
                draft_model, draft_params, d_cache, chunk,
                jnp.asarray(d_fill, jnp.int32), cache_only=True)
            d_fill += len(pending)
        last_tok = jnp.asarray([emitted[-1]], jnp.int32)
        drafts, d_cache = _draft_propose(
            draft_model, draft_params, d_cache, last_tok,
            jnp.asarray(d_fill, jnp.int32), g)
        d_fill += g  # holds last_tok .. d_{g-2} (d_{g-1} never fed)
        drafts_host = np.asarray(as_host_array(drafts))[0]  # [g]
        proposed += g

        # 2. target verifies the whole proposal in ONE chunk forward:
        #    feed [last_tok, d_0..d_{g-1}] → logits for each position.
        chunk = jnp.asarray(
            [[emitted[-1], *drafts_host.tolist()]], jnp.int32)  # [1, g+1]
        logits, t_cache = _extend(target_model, target_params, t_cache,
                                  chunk, jnp.asarray(t_fill, jnp.int32))
        t_fill += g + 1
        preds = np.asarray(as_host_array(
            jnp.argmax(logits, axis=-1)))[0]  # [g+1]

        # 3. greedy acceptance: d_i is kept iff it equals the target's
        #    own argmax at the position before it (the ONE shared rule).
        a = int(greedy_accept_len(jnp.asarray(drafts_host[:g]),
                                  jnp.asarray(preds[:g])))
        accepted_total += a
        # emit accepted drafts + the target's correction/extension token
        emitted.extend(int(t) for t in drafts_host[:a])
        if len(emitted) < max_new_tokens:
            emitted.append(int(preds[a]))

        # 4. rollback both caches to the verified prefix: prompt +
        #    emitted tokens that have been FED (everything but the
        #    newest). Index reset is the whole rollback — the masked
        #    suffix is invisible and gets overwritten.
        t_fill = s_prompt + len(emitted) - 1
        d_fill = min(d_fill, t_fill)
        t_cache = _set_cache_index(t_cache, t_fill)
        d_cache = _set_cache_index(d_cache, d_fill)

    # eos padding to the fixed output length (generate()'s contract)
    out = _pad_after_eos(emitted[:max_new_tokens], max_new_tokens,
                         eos_token_id)
    result = jnp.concatenate(
        [prompt_ids, jnp.asarray([out], jnp.int32)], axis=1)
    if return_stats:
        # the first token came free from the prefill, not from a round —
        # excluding it keeps the stat within its gamma+1 ceiling; the
        # cap keeps the final round's draft overshoot out of the stat
        # (same definition as the device driver)
        return result, {"rounds": rounds, "proposed": proposed,
                        "accepted": accepted_total,
                        "tokens_per_round":
                        (min(len(emitted), max_new_tokens) - 1)
                        / max(rounds, 1)}
    return result
