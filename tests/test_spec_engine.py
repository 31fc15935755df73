"""Self-draft speculative decoding inside the continuous-batching
engine (train/continuous.py ``_spec_chunk`` + the OP_CB wire bits).

The correctness oracle is unchanged from test_continuous.py: a request
decoded through the SPECULATIVE slot engine must produce EXACTLY the
tokens ``models.causal_lm.generate`` produces greedily for the same
prompt alone — the draft (self-draft or a separate small model) may
only ever change speed, never content. The compositions the engine
already ships (eos, cancel, deadlines, radix prefix cache + COW,
chunked prefill, step-token budget, decode-ahead, sampling lanes,
announce/replay wire) must all hold under speculation.

One shared tiny model across tests keeps the module inside the tier-1
compile budget (module-level jits cache per shape); the heavy
composition sweeps are slow-marked.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.models.causal_lm import (CausalLM, CausalLMConfig,
                                                 generate)
from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine

K = 3  # spec width shared by most tests (one compiled round program)


@pytest.fixture(scope="module")
def tiny():
    cfg = CausalLMConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, max_seq_len=256)
    from flax import linen as nn

    model = CausalLM(cfg)
    params = nn.meta.unbox(
        model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
    paged = CausalLM(dataclasses.replace(cfg, kv_page_size=16,
                                         kv_num_pages=64))
    return model, paged, params


@pytest.fixture(scope="module")
def tiny_draft():
    """A structurally different, untrained draft: acceptance is near
    zero, which exercises the full-rollback path — output must still
    be exact."""
    dcfg = CausalLMConfig(
        vocab_size=97, hidden_size=16, num_layers=1, num_heads=2,
        num_kv_heads=2, intermediate_size=32, max_seq_len=256)
    from flax import linen as nn

    draft = CausalLM(dcfg)
    dparams = nn.meta.unbox(
        draft.init(jax.random.key(7), jnp.ones((1, 8), jnp.int32))["params"])
    return draft, dparams


def _reference_tokens(model, params, prompt, max_new, eos=None):
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None, :],
                   max_new_tokens=max_new, eos_token_id=eos)
    toks = np.asarray(out)[0, len(prompt):]
    if eos is not None:
        hit = np.nonzero(toks == eos)[0]
        if hit.size:
            toks = toks[:hit[0] + 1]
    return [int(t) for t in toks]


# ---- acceptance-rule helpers (models/speculative.py — the ONE rule) --------


def test_accept_rule_helpers():
    from pyspark_tf_gke_tpu.models.speculative import (emit_window,
                                                       greedy_accept_len)

    drafts = jnp.asarray([[5, 6, 7], [5, 9, 7], [1, 2, 3]])
    picks = jnp.asarray([[5, 6, 7], [5, 6, 7], [9, 9, 9]])
    a = greedy_accept_len(drafts, picks)
    assert a.tolist() == [3, 1, 0]
    corr = jnp.asarray([40, 41, 42])
    win = emit_window(drafts, corr, a)
    assert win.shape == (3, 4)
    assert win[0].tolist() == [5, 6, 7, 40]   # all accepted + bonus
    assert win[1].tolist() == [5, 41, 41, 41]  # 1 accepted + correction
    assert win[2].tolist() == [42, 42, 42, 42]  # rejected outright


def test_accept_and_correct_greedy_and_rejection():
    from pyspark_tf_gke_tpu.models.speculative import accept_and_correct

    rng = np.random.default_rng(3)
    b, k, v = 4, 3, 11
    tgt = jnp.asarray(rng.normal(size=(b, k + 1, v)), jnp.float32)
    picks = np.asarray(jnp.argmax(tgt, -1))
    drafts = jnp.asarray(picks[:, :k])  # perfect drafts
    dlog = jnp.asarray(rng.normal(size=(b, k, v)), jnp.float32)
    a, corr = accept_and_correct(drafts, dlog, tgt)
    assert a.tolist() == [k] * b
    assert corr.tolist() == picks[:, k].tolist()  # bonus = argmax at k
    # rejection rule, temps > 0: p == q (identical logits) must accept
    # everything (u < p/q = 1 always for u in [0,1)); bonus from p_k
    temps = jnp.full((b,), 0.7)
    topps = jnp.ones((b,))
    keys = jnp.asarray(
        np.stack([np.asarray(jax.random.key_data(
            jax.random.key(i, impl="threefry2x32"))) for i in range(b)]),
        jnp.uint32)
    a2, corr2 = accept_and_correct(drafts, tgt[:, :k], tgt,
                                   temps=temps, topps=topps, keys=keys)
    assert a2.tolist() == [k] * b
    assert all(0 <= int(c) < v for c in corr2)
    # a draft the target gives ~zero mass must reject at its position.
    # The argmax's neighbour under normal logits is not such a draft (row
    # 3 gives it p = 0.0147 at this temperature and draws u = 0.0087, a
    # rightful accept), so the target's logit for it is pushed down too
    bad = drafts.at[:, 0].set((picks[:, 0] + 1) % v)
    bad_dlog = jnp.full((b, k, v), -20.0).at[
        jnp.arange(b), 0, bad[:, 0]].set(20.0)
    bad_tgt = tgt.at[jnp.arange(b), 0, bad[:, 0]].set(-40.0)
    a3, _ = accept_and_correct(bad, bad_dlog, bad_tgt, temps=temps,
                               topps=topps, keys=keys)
    assert a3.tolist() == [0] * b


def test_standalone_spec_workload_still_exact(tiny):
    # the standalone driver is now a thin caller of the shared rule —
    # its greedy-exactness contract must be untouched
    from pyspark_tf_gke_tpu.models.speculative import speculative_generate

    model, _, params = tiny
    prompt = np.random.default_rng(11).integers(1, 97, 9)
    out = speculative_generate(
        model, params, model, params,
        jnp.asarray(prompt, jnp.int32)[None], max_new_tokens=8, gamma=3)
    ref = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new_tokens=8)
    assert np.asarray(out).tolist() == np.asarray(ref).tolist()


# ---- engine parity (fast anchors) ------------------------------------------


def test_spec_single_request_matches_generate(tiny):
    model, paged, params = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 97, 11)
    eng = ContinuousEngine(paged, params, num_slots=2, chunk=8,
                           buckets=(16, 32), spec_tokens=K)
    rid = eng.submit(prompt, max_new_tokens=10)
    results = dict(eng.run_until_drained())
    assert results[rid] == _reference_tokens(model, params, prompt, 10)
    spec = eng.stats["spec"]
    assert spec["spec_tokens"] == K and spec["self_draft"]
    # self-draft: the target agrees with itself — acceptance ~1, and
    # every accepted token skipped a full-model forward
    assert spec["accepted"] > 0
    assert spec["recent_accept_rate"] > 0.5
    assert eng.spec_accept_rate() == spec["recent_accept_rate"]


def test_spec_eos_truncates_inside_window(tiny):
    model, paged, params = tiny
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 97, 8)
    solo = _reference_tokens(model, params, prompt, 12)
    eos = solo[2]  # lands mid-window with K=3
    eng = ContinuousEngine(paged, params, num_slots=1, chunk=8,
                           eos_token_id=eos, buckets=(16,), spec_tokens=K)
    rid = eng.submit(prompt, max_new_tokens=12)
    results = dict(eng.run_until_drained())
    expected = _reference_tokens(model, params, prompt, 12, eos=eos)
    assert results[rid] == expected
    assert results[rid][-1] == eos and len(results[rid]) < 12
    assert eng.stats["paged"]["pages_in_use"] == 0


def test_spec_cow_on_trie_shared_page_and_refcounts(tiny):
    # THE regression the rollback must not break: a radix-cache hit
    # installs trie-shared pages and COWs the partially-filled tail
    # page BEFORE any write of the new slot lands — the very first
    # engine write under speculation is a (k+1)-row verify chunk, so a
    # missing COW would corrupt the shared page for every later
    # matcher. Both hit requests must stay token-exact and the full
    # refcount audit must stay green.
    from pyspark_tf_gke_tpu.chaos.invariants import check_engine

    model, paged, params = tiny
    rng = np.random.default_rng(5)
    shared = rng.integers(1, 97, 24)  # 24 % 16 != 0 -> partial tail page
    p1 = np.concatenate([shared, rng.integers(1, 97, 5)])
    p2 = np.concatenate([shared, rng.integers(1, 97, 8)])
    eng = ContinuousEngine(paged, params, num_slots=2, chunk=6,
                           buckets=(16, 32, 64), prefix_cache_size=32,
                           spec_tokens=K)
    r1 = eng.submit(p1, max_new_tokens=6)
    results = dict(eng.run_until_drained())
    r2 = eng.submit(p2, max_new_tokens=6)
    results.update(dict(eng.run_until_drained()))
    assert results[r1] == _reference_tokens(model, params, p1, 6)
    assert results[r2] == _reference_tokens(model, params, p2, 6)
    assert eng.stats["prefix_cache"]["hits"] == 1
    audit = check_engine(eng)
    assert audit["ok"], audit["violations"]
    # and a THIRD request re-matching the (speculatively decoded-over)
    # prefix still reads intact shared pages
    p3 = np.concatenate([shared, rng.integers(1, 97, 6)])
    r3 = eng.submit(p3, max_new_tokens=6)
    results.update(dict(eng.run_until_drained()))
    assert results[r3] == _reference_tokens(model, params, p3, 6)


def test_spec_announce_stream_replays_with_nonzero_accepts(tiny):
    # Record the OP_CB_* stream of a spec engine run (single process:
    # _bcast is identity), replay it through serve_worker_loop, and
    # require the replica's device state — block tables AND fill
    # positions — to land BIT-IDENTICAL to process 0's, with nonzero
    # accepted counts having crossed the collect gathers. The chunk
    # header's flags slot must carry spec_tokens and the admit ops the
    # draft-prefill payload (bit4).
    from pyspark_tf_gke_tpu.train import continuous as cont
    from pyspark_tf_gke_tpu.train import serving

    model, paged, params = tiny
    rng = np.random.default_rng(9)
    stream = []
    real = serving._bcast

    def recording(x):
        stream.append(np.asarray(x).copy())
        return real(x)

    serving._bcast = recording
    try:
        eng = ContinuousEngine(paged, params, num_slots=2, chunk=6,
                               buckets=(16, 32), announce=True,
                               spec_tokens=K)
        p1, p2 = rng.integers(1, 97, 9), rng.integers(1, 97, 20)
        r1 = eng.submit(p1, max_new_tokens=8)
        r2 = eng.submit(p2, max_new_tokens=6)
        results = dict(eng.run_until_drained())
        serving.announce_shutdown()
    finally:
        serving._bcast = real
    assert results[r1] == _reference_tokens(model, params, p1, 8)
    assert results[r2] == _reference_tokens(model, params, p2, 6)
    assert eng.stats["spec"]["accepted"] > 0
    chunk_flags = {int(h[7]) for h in stream
                   if h.shape == (8,) and h[0] == serving.OP_CB_CHUNK}
    assert chunk_flags == {K}, "chunk headers must carry spec_tokens"
    admit_flags = [int(h[7]) for h in stream
                   if h.shape == (8,) and h[0] == serving.OP_CB_ADMIT]
    assert admit_flags and all(f & 16 for f in admit_flags), \
        "every admit must carry the draft-prefill payload"

    replicas = []
    orig = cont.SlotDeviceState

    class Capturing(orig):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            replicas.append(self)

    replay = list(stream)

    def replaying(x):
        got = replay.pop(0)
        assert got.shape == np.asarray(x).shape, (
            f"wire desync: worker expects {np.asarray(x).shape}, "
            f"stream has {got.shape}")
        return got

    cont.SlotDeviceState = Capturing
    serving._bcast = replaying
    try:
        served = serving.serve_worker_loop(paged, params, mesh=None)
    finally:
        serving._bcast = real
        cont.SlotDeviceState = orig
    assert not replay and served > 0

    def block_tables(state):
        out = []

        def walk(pool):
            if hasattr(pool, "keys"):
                if "block_table" in pool:
                    out.append(np.asarray(pool["block_table"]))
                else:
                    for key in pool:
                        walk(pool[key])

        walk(state.cache)
        return out

    mine = block_tables(eng._device.state)
    theirs = block_tables(replicas[-1].state)
    assert mine and len(mine) == len(theirs)
    for a, b in zip(mine, theirs):
        assert (a == b).all(), "replica block tables diverged"
    assert (np.asarray(eng._device.state.positions)
            == np.asarray(replicas[-1].state.positions)).all()


@pytest.mark.slow  # heavy compile set: chunked prefill + spec + replay
def test_pipelined_announce_stream_replays_identically_to_serial(tiny):
    """Record/replay parity is the async-core oracle: a
    pipeline_depth=1 announce engine must emit the SAME tokens as the
    serial engine (and solo generate()), and the OP_CB_* stream it
    broadcast must replay on a worker into a BIT-IDENTICAL replica —
    block tables and fill positions — across admission (whole AND
    chunked-prefill pieces) and speculative rounds. Workers replay the
    one-deep pipelined schedule exactly (deferred dispatch + matching
    collect); any host-side reorder in the pipelined loop desyncs
    here."""
    from pyspark_tf_gke_tpu.train import continuous as cont
    from pyspark_tf_gke_tpu.train import serving

    model, paged, params = tiny
    rng = np.random.default_rng(17)
    p_long = rng.integers(1, 97, 50)   # admits in chunked pieces
    p_short = rng.integers(1, 97, 9)   # admits whole
    kw = dict(num_slots=2, chunk=6, buckets=(16, 32, 64),
              prefill_chunk=32, spec_tokens=K)

    serial = ContinuousEngine(paged, params, **kw)
    s1 = serial.submit(p_long, max_new_tokens=8)
    s2 = serial.submit(p_short, max_new_tokens=6)
    serial_results = dict(serial.run_until_drained())

    stream = []
    real = serving._bcast

    def recording(x):
        stream.append(np.asarray(x).copy())
        return real(x)

    serving._bcast = recording
    try:
        eng = ContinuousEngine(paged, params, announce=True,
                               pipeline_depth=1, **kw)
        r1 = eng.submit(p_long, max_new_tokens=8)
        r2 = eng.submit(p_short, max_new_tokens=6)
        results = dict(eng.run_until_drained())
        serving.announce_shutdown()
    finally:
        serving._bcast = real
    # token parity: pipelined == serial == solo generate()
    assert results[r1] == serial_results[s1]
    assert results[r2] == serial_results[s2]
    assert results[r1] == _reference_tokens(model, params, p_long, 8)
    assert results[r2] == _reference_tokens(model, params, p_short, 6)
    assert eng.stats["spec"]["accepted"] > 0
    assert not eng._inflight_q
    # the wire carried chunked-admit pieces, spec-width flags, and the
    # one-deep deferred schedule with a collect per deferred dispatch
    admit_flags = [int(h[7]) for h in stream
                   if h.shape == (8,) and h[0] == serving.OP_CB_ADMIT]
    assert any(f & 2 for f in admit_flags)
    # draft prefill rides the whole admit / the FINAL chunked piece
    assert any(f & 16 for f in admit_flags)
    chunk_heads = [h for h in stream
                   if h.shape == (8,) and h[0] == serving.OP_CB_CHUNK]
    assert {int(h[7]) for h in chunk_heads} == {K}
    deferred = [int(h[2]) for h in chunk_heads]
    assert any(deferred), "pipelined schedule never crossed the wire"
    collects = sum(1 for h in stream
                   if h.shape == (8,) and h[0] == serving.OP_CB_COLLECT)
    assert collects == sum(deferred)

    replicas = []
    orig = cont.SlotDeviceState

    class Capturing(orig):
        def __init__(self, *a, **kw2):
            super().__init__(*a, **kw2)
            replicas.append(self)

    replay = list(stream)

    def replaying(x):
        got = replay.pop(0)
        assert got.shape == np.asarray(x).shape, (
            f"wire desync: worker expects {np.asarray(x).shape}, "
            f"stream has {got.shape}")
        return got

    cont.SlotDeviceState = Capturing
    serving._bcast = replaying
    try:
        served = serving.serve_worker_loop(paged, params, mesh=None)
    finally:
        serving._bcast = real
        cont.SlotDeviceState = orig
    assert not replay and served > 0

    def block_tables(state):
        out = []

        def walk(pool):
            if hasattr(pool, "keys"):
                if "block_table" in pool:
                    out.append(np.asarray(pool["block_table"]))
                else:
                    for key in pool:
                        walk(pool[key])

        walk(state.cache)
        return out

    mine = block_tables(eng._device.state)
    theirs = block_tables(replicas[-1].state)
    assert mine and len(mine) == len(theirs)
    for a, b in zip(mine, theirs):
        assert (a == b).all(), "replica block tables diverged"
    assert (np.asarray(eng._device.state.positions)
            == np.asarray(replicas[-1].state.positions)).all()


def test_spec_stats_span_events_and_validation(tiny):
    # per-request accept-rate span event (the /traces speculation-
    # quality satellite) + constructor validation
    from pyspark_tf_gke_tpu.obs.trace import TraceRecorder

    model, paged, params = tiny
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, 97, 9)
    rec = TraceRecorder(sample=1.0)
    span = rec.start_span("req")
    eng = ContinuousEngine(paged, params, num_slots=1, chunk=6,
                           buckets=(16,), spec_tokens=K)
    rid = eng.submit(prompt, max_new_tokens=8, span=span)
    results = dict(eng.run_until_drained())
    assert results[rid] == _reference_tokens(model, params, prompt, 8)
    events = [e for e in span.events if e.get("name") == "spec"]
    assert len(events) == 1
    ev = events[0]
    assert ev["proposed"] > 0 and 0 <= ev["accept_rate"] <= 1.0
    assert ev["accepted"] <= ev["proposed"]
    term = [e for e in span.events if e.get("name") == "terminal"]
    assert len(term) == 1 and term[0]["outcome"] == "ok"
    with pytest.raises(ValueError, match="spec_tokens"):
        ContinuousEngine(paged, params, num_slots=1, spec_tokens=-1)
    draft_bad = CausalLM(dataclasses.replace(model.cfg, vocab_size=64))
    with pytest.raises(ValueError, match="vocab"):
        ContinuousEngine(paged, params, num_slots=1, spec_tokens=2,
                         draft_model=draft_bad, draft_params=params)


# ---- composition sweeps (slow: heavy compile sets) -------------------------


@pytest.mark.slow
def test_spec_staggered_requests_match_generate_each(tiny):
    model, paged, params = tiny
    rng = np.random.default_rng(1)
    specs = [(rng.integers(1, 97, int(n)), int(m))
             for n, m in [(5, 12), (19, 3), (17, 8), (7, 15)]]
    eng = ContinuousEngine(paged, params, num_slots=2, chunk=6,
                           buckets=(16, 32), spec_tokens=K)
    rids = {eng.submit(p, max_new_tokens=m): (p, m) for p, m in specs}
    results = dict(eng.run_until_drained())
    for rid, (p, m) in rids.items():
        assert results[rid] == _reference_tokens(model, params, p, m)
    assert eng.stats["finished"] == len(specs)


@pytest.mark.slow
def test_spec_separate_draft_exact_despite_rejections(tiny, tiny_draft):
    # an untrained draft disagrees with the target ~always: every round
    # rolls back to the correction token, and the output must STILL be
    # token-exact (the acceptance rule's whole guarantee)
    model, paged, params = tiny
    draft, dparams = tiny_draft
    rng = np.random.default_rng(17)
    prompt = rng.integers(1, 97, 13)
    eng = ContinuousEngine(paged, params, num_slots=2, chunk=8,
                           buckets=(16, 32), spec_tokens=4,
                           draft_model=draft, draft_params=dparams)
    rid = eng.submit(prompt, max_new_tokens=12)
    results = dict(eng.run_until_drained())
    assert results[rid] == _reference_tokens(model, params, prompt, 12)
    spec = eng.stats["spec"]
    assert not spec["self_draft"]
    assert spec["proposed"] > 0
    assert spec["accept_rate"] <= 1.0


@pytest.mark.slow
def test_spec_chunked_prefill_and_budget_composition(tiny):
    # long prompt admits in pieces under the step-token budget while a
    # short request speculates — draft+verify tokens count against the
    # budget (bounded rounds), both exact
    model, paged, params = tiny
    rng = np.random.default_rng(19)
    long_p = rng.integers(1, 97, 100)
    short_p = rng.integers(1, 97, 6)
    eng = ContinuousEngine(paged, params, num_slots=2, chunk=8,
                           buckets=(16, 32, 64, 128), prefill_chunk=32,
                           step_token_budget=40, spec_tokens=K)
    rs = eng.submit(short_p, max_new_tokens=12)
    rl = eng.submit(long_p, max_new_tokens=5)
    results = dict(eng.run_until_drained())
    assert results[rl] == _reference_tokens(model, params, long_p, 5)
    assert results[rs] == _reference_tokens(model, params, short_p, 12)
    assert eng.stats["prefill_chunks"] >= 4
    # budget cap: 40 tokens/step over >=1 live slot allows at most
    # (40 // (2K+2)) rounds/step -> with K=3, never more than 4
    assert eng.stats["spec"]["rounds"] <= eng.stats["spec"]["proposed"]


@pytest.mark.slow
def test_spec_decode_ahead_parity(tiny):
    model, paged, params = tiny
    rng = np.random.default_rng(23)
    specs = [(rng.integers(1, 97, int(n)), int(m))
             for n, m in [(5, 12), (19, 3), (17, 8)]]
    eng = ContinuousEngine(paged, params, num_slots=2, chunk=6,
                           buckets=(16, 32), pipeline_depth=1,
                           spec_tokens=2)
    rids = {eng.submit(p, max_new_tokens=m): (p, m) for p, m in specs}
    results = dict(eng.run_until_drained())
    for rid, (p, m) in rids.items():
        assert results[rid] == _reference_tokens(model, params, p, m)


@pytest.mark.slow
def test_spec_sampling_lane_deterministic_greedy_isolated(tiny):
    # sampled rows ride the rejection rule (valid tokens, seed-
    # deterministic); greedy rows in the same pool stay EXACT
    model, paged, params = tiny
    rng = np.random.default_rng(29)
    pg, pt = rng.integers(1, 97, 9), rng.integers(1, 97, 9)

    def run():
        eng = ContinuousEngine(paged, params, num_slots=2, chunk=6,
                               buckets=(16, 32), spec_tokens=K)
        rg = eng.submit(pg, max_new_tokens=8)
        rt = eng.submit(pt, max_new_tokens=8, temperature=0.8,
                        top_p=0.9, seed=5)
        res = dict(eng.run_until_drained())
        return res[rg], res[rt]

    g1, t1 = run()
    g2, t2 = run()
    assert g1 == g2 == _reference_tokens(model, params, pg, 8)
    assert t1 == t2  # same seed, same engine config -> same stream
    assert len(t1) == 8 and all(0 <= t < 97 for t in t1)


@pytest.mark.slow
def test_spec_cancel_and_deadline_release_pages(tiny):
    model, paged, params = tiny
    rng = np.random.default_rng(31)
    eng = ContinuousEngine(paged, params, num_slots=1, chunk=4,
                           buckets=(16,), spec_tokens=2)
    rc = eng.submit(rng.integers(1, 97, 6), max_new_tokens=50)
    eng.step()
    assert eng.cancel(rc)
    rd = eng.submit(rng.integers(1, 97, 6), max_new_tokens=50,
                    deadline_s=0.05)
    time.sleep(0.1)
    finished = []
    while (eng.stats["queued"] or eng.stats["active"]
           or eng.stats["inflight"]):
        finished += eng.step()
    assert any(r.rid == rd and r.expired for r in finished)
    assert eng.stats["paged"]["pages_in_use"] == 0


@pytest.mark.slow
def test_spec_dense_engine_parity(tiny):
    # speculation is not paged-only: the dense slot engine runs the
    # same draft/verify rounds through the dense chunk attend
    model, _, params = tiny
    rng = np.random.default_rng(37)
    prompt = rng.integers(1, 97, 11)
    eng = ContinuousEngine(model, params, num_slots=2, chunk=8,
                           buckets=(16, 32), spec_tokens=K)
    rid = eng.submit(prompt, max_new_tokens=10)
    results = dict(eng.run_until_drained())
    assert results[rid] == _reference_tokens(model, params, prompt, 10)
