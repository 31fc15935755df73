"""Serving deployment surface e2e: export bundle → HTTP server →
generate/score over the wire (train/serve.py), incl. the remote
lm_eval mode (evaluate/lm_eval.py --endpoint)."""

import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
from pyspark_tf_gke_tpu.train.export import export_serving_bundle
from pyspark_tf_gke_tpu.train.serve import BundleServer, start_http_server
from pyspark_tf_gke_tpu.utils.seeding import make_rng

# vocab must cover the byte tokenizer (259) the bundle records by default
CFG = dict(vocab_size=259, hidden_size=32, num_layers=2, num_heads=2,
           intermediate_size=64, max_seq_len=64, dtype=jnp.float32)


@pytest.fixture(scope="module")
def endpoint(tmp_path_factory):
    cfg = CausalLMConfig(**CFG)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(0), ids)["params"])
    bundle = str(tmp_path_factory.mktemp("serve") / "bundle")
    export_serving_bundle(cfg, params, bundle, quantize=True,
                          quantize_min_size=64)

    server = BundleServer(bundle)
    httpd = start_http_server(server, host="127.0.0.1", port=0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield url
    httpd.shutdown()


def _post(url, path, payload):
    req = urllib.request.Request(url + path, data=json.dumps(payload).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def test_healthz(endpoint):
    with urllib.request.urlopen(endpoint + "/healthz") as resp:
        health = json.loads(resp.read())
    assert health["status"] == "ok"
    assert health["quantized"] is True
    assert health["vocab_size"] == 259
    assert health["max_seq_len"] == 64
    # a parent that may not touch JAX reads the replica's device here
    assert (health["platform"], health["device_kind"]) == (
        jax.devices()[0].platform, jax.devices()[0].device_kind)


def test_generate_over_the_wire_batches_mixed_lengths(endpoint):
    """Prompts of different token lengths group into separate decode
    batches but return in request order, each extended by new tokens."""
    prompts = ["hello", "ab", "world", "xy"]  # lengths 5, 2, 5, 2
    out = _post(endpoint, "/v1/generate",
                {"prompts": prompts, "max_new_tokens": 6})["completions"]
    assert [o["prompt"] for o in out] == prompts
    for o in out:
        assert o["completion"].startswith(o["prompt"])
        assert 0 < o["new_tokens"] <= 6
        assert o["latency_ms"] > 0


def test_generate_single_prompt_and_beams(endpoint):
    out = _post(endpoint, "/v1/generate",
                {"prompt": "abc", "max_new_tokens": 4,
                 "num_beams": 2})["completions"]
    assert len(out) == 1
    assert "beam_score" in out[0]


def test_score_over_the_wire(endpoint):
    # "z" is a 1-token text: no next-token NLL exists — it must come
    # back skipped without failing the rest of the batch (remote
    # perplexity eval feeds arbitrary documents)
    texts = ["hello world", "z", "zq"]
    scores = _post(endpoint, "/v1/score", {"texts": texts})["scores"]
    assert len(scores) == 3
    assert scores[1] == {"nll": 0.0, "tokens": 0, "truncated": False,
                         "skipped": True}
    for s, t in ((scores[0], texts[0]), (scores[2], texts[2])):
        assert s["tokens"] == len(t.encode()) - 1
        assert s["nll"] > 0 and np.isfinite(s["nll"])
        assert s["truncated"] is False


def test_http_errors(endpoint):
    # malformed body → 400
    req = urllib.request.Request(endpoint + "/v1/generate", data=b"{nope",
                                 headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req)
    assert e.value.code == 400
    # over-long prompt → 400 with the explanation
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(endpoint, "/v1/generate",
              {"prompts": ["x" * 100], "max_new_tokens": 10})
    assert e.value.code == 400
    assert "max_seq_len" in json.loads(e.value.read())["error"]
    # unknown route → 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(endpoint, "/v1/nope", {})
    assert e.value.code == 404
    # JSON null for a numeric field → 400, not 500 (int(None) raises
    # TypeError)
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(endpoint, "/v1/generate",
              {"prompts": ["ab"], "max_new_tokens": None})
    assert e.value.code == 400
    # oversize Content-Length → 413 before the body is read
    from pyspark_tf_gke_tpu.train.serve import MAX_BODY_BYTES

    req = urllib.request.Request(
        endpoint + "/v1/generate", data=b"{}",
        headers={"Content-Type": "application/json",
                 "Content-Length": str(MAX_BODY_BYTES + 1)})
    req.method = "POST"
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req)
    assert e.value.code == 413


def test_lm_eval_endpoint_mode(endpoint, tmp_path, capsys):
    """The full loop the k8s deployment enables: a client evaluates a
    DEPLOYED model over the wire — no jax/bundle on the client path."""
    corpus = tmp_path / "heldout"
    corpus.mkdir()
    rng = np.random.default_rng(0)
    (corpus / "h.txt").write_text(
        "\n\n".join("".join(chr(rng.integers(97, 123)) for _ in range(20))
                    for _ in range(8)))

    from pyspark_tf_gke_tpu.evaluate.lm_eval import main

    res = main([
        "--endpoint", endpoint,
        "--data-pattern", str(corpus / "*.txt"),
        "--batches", "2", "--batch-size", "4",
        "--prompt", "ab", "--max-new-tokens", "4",
    ])
    assert res["perplexity"] > 1.0
    assert res["tokens"] > 0
    assert len(res["samples"]) == 1
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line)["perplexity"] == res["perplexity"]


def test_lm_eval_requires_exactly_one_source():
    from pyspark_tf_gke_tpu.evaluate.lm_eval import main

    with pytest.raises(SystemExit):
        main(["--data-pattern", "x*.txt"])  # neither bundle nor endpoint


def test_sampling_varies_across_requests(endpoint):
    """temperature>0 must not hand every request the same 'random'
    completion (a fixed PRNG seed would)."""
    body = {"prompts": ["abcd"], "max_new_tokens": 10, "temperature": 1.0}
    outs = {_post(endpoint, "/v1/generate", body)["completions"][0]["completion"]
            for _ in range(4)}
    assert len(outs) > 1


def test_speculative_serving_same_tokens(tmp_path):
    """A server with a draft bundle serves single-prompt greedy requests
    through speculative decoding — identical completion to the plain
    server, plus acceptance stats in the response."""
    cfg = CausalLMConfig(**CFG)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(5), ids)["params"])
    target_dir = str(tmp_path / "target")
    export_serving_bundle(cfg, params, target_dir, quantize=False)

    dcfg = CausalLMConfig(**{**CFG, "hidden_size": 16, "num_layers": 1})
    draft = CausalLM(dcfg)
    dparams = nn.meta.unbox(jax.jit(draft.init)(make_rng(6), ids)["params"])
    draft_dir = str(tmp_path / "draft")
    export_serving_bundle(dcfg, dparams, draft_dir, quantize=False)

    plain = BundleServer(target_dir)
    spec = BundleServer(target_dir, draft_bundle_dir=draft_dir)
    assert spec.health()["speculative_draft"] == draft_dir

    ref = plain.generate(["hello tpu"], max_new_tokens=10)[0]
    out = spec.generate(["hello tpu"], max_new_tokens=10)[0]
    assert out["completion"] == ref["completion"]
    assert "speculative" in out and "acceptance_rate" in out["speculative"]
    # multi-prompt and sampling requests fall back to the batched path
    multi = spec.generate(["ab", "cd"], max_new_tokens=4)
    assert len(multi) == 2 and "speculative" not in multi[0]


def test_speculative_serving_on_tp_mesh(tmp_path, devices):
    """Draft params shard onto the same tp mesh as the target; the
    speculative path must produce the plain tp server's tokens."""
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

    cfg = CausalLMConfig(**CFG)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(7), ids)["params"])
    target_dir = str(tmp_path / "t")
    export_serving_bundle(cfg, params, target_dir, quantize=False)

    dcfg = CausalLMConfig(**{**CFG, "hidden_size": 16, "num_layers": 1})
    draft = CausalLM(dcfg)
    dparams = nn.meta.unbox(jax.jit(draft.init)(make_rng(8), ids)["params"])
    draft_dir = str(tmp_path / "d")
    export_serving_bundle(dcfg, dparams, draft_dir, quantize=False)

    mesh = make_mesh({"tp": 2}, devices[:2])
    plain = BundleServer(target_dir, mesh=mesh)
    spec = BundleServer(target_dir, mesh=mesh, draft_bundle_dir=draft_dir)
    # the draft's divisible kernels actually shard onto the mesh (its
    # vocab-259 head replicates — 259 % 2 != 0 falls back per leaf)
    assert any(not l.sharding.is_fully_replicated
               for l in jax.tree.leaves(spec.draft_params))
    ref = plain.generate(["sharded tpu"], max_new_tokens=8)[0]
    out = spec.generate(["sharded tpu"], max_new_tokens=8)[0]
    assert out["completion"] == ref["completion"]
    assert "speculative" in out


def test_speculative_falls_back_beyond_draft_context(tmp_path):
    """A request longer than the DRAFT's max_seq_len must serve through
    the plain path (the target can handle it), not error."""
    cfg = CausalLMConfig(**CFG)  # max_seq_len 64
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(9), ids)["params"])
    target_dir = str(tmp_path / "t")
    export_serving_bundle(cfg, params, target_dir, quantize=False)
    dcfg = CausalLMConfig(**{**CFG, "max_seq_len": 16, "num_layers": 1})
    draft = CausalLM(dcfg)
    dparams = nn.meta.unbox(jax.jit(draft.init)(make_rng(10), ids)["params"])
    draft_dir = str(tmp_path / "d")
    export_serving_bundle(dcfg, dparams, draft_dir, quantize=False)

    spec = BundleServer(target_dir, draft_bundle_dir=draft_dir)
    out = spec.generate(["a prompt well past sixteen"],
                        max_new_tokens=8)[0]  # 26 tokens > draft's 16
    assert "speculative" not in out
    assert out["new_tokens"] > 0


# -- continuous batching over the wire ---------------------------------------


@pytest.fixture(scope="module")
def cb_endpoints(tmp_path_factory):
    """One plain server + one continuous server on the SAME bundle so
    tests can assert greedy token-identity across serving modes."""
    cfg = CausalLMConfig(**CFG)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(1), ids)["params"])
    bundle = str(tmp_path_factory.mktemp("serve-cb") / "bundle")
    export_serving_bundle(cfg, params, bundle)

    plain = BundleServer(bundle)
    cont = BundleServer(bundle, continuous_slots=2, continuous_chunk=3)
    servers, urls = [], []
    for server in (plain, cont):
        httpd = start_http_server(server, host="127.0.0.1", port=0)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        servers.append((server, httpd))
        urls.append(f"http://127.0.0.1:{httpd.server_address[1]}")
    yield urls
    for server, httpd in servers:
        httpd.shutdown()
        if server._front is not None:
            server._front.shutdown()


def test_continuous_matches_plain_greedy(cb_endpoints):
    plain_url, cont_url = cb_endpoints
    payload = {"prompts": ["hello", "ab", "continuous"],
               "max_new_tokens": 6}
    plain = _post(plain_url, "/v1/generate", payload)["completions"]
    cont = _post(cont_url, "/v1/generate", payload)["completions"]
    assert [o["completion"] for o in cont] == \
        [o["completion"] for o in plain]


def test_continuous_concurrent_requests_share_slots(cb_endpoints):
    plain_url, cont_url = cb_endpoints
    prompts = ["aa", "bb", "cc", "dd", "ee"]
    budgets = [3, 9, 5, 7, 4]  # mixed lengths: slots must recycle
    expected = {}
    for p, m in zip(prompts, budgets):
        out = _post(plain_url, "/v1/generate",
                    {"prompts": [p], "max_new_tokens": m})
        expected[p] = out["completions"][0]["completion"]

    results, errors = {}, []

    def one(p, m):
        try:
            out = _post(cont_url, "/v1/generate",
                        {"prompts": [p], "max_new_tokens": m})
            results[p] = out["completions"][0]["completion"]
        except Exception as exc:  # noqa: BLE001 — surfaced via `errors`
            errors.append((p, repr(exc)))

    threads = [threading.Thread(target=one, args=(p, m))
               for p, m in zip(prompts, budgets)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert results == expected  # token-identical to solo whole-batch runs


def test_continuous_health_reports_engine(cb_endpoints):
    _, cont_url = cb_endpoints
    with urllib.request.urlopen(cont_url + "/healthz") as resp:
        health = json.loads(resp.read())
    assert health["continuous"]["num_slots"] == 2
    assert health["continuous"]["chunk"] == 3


def test_loadz_snapshot_key_stability(cb_endpoints):
    """GET /loadz is the router-prober contract: the KEY SET is pinned
    here so a refactor can't silently break replica scoring (the
    router reads queued_tokens/active/draining; kv_pages_free is None
    on dense engines, a number on paged ones)."""
    plain_url, cont_url = cb_endpoints
    want_keys = {"queued", "queued_tokens", "active", "slots_total",
                 "kv_pages_free", "inflight_http", "draining",
                 "bundle_generation",
                 "prefix_cache_pages", "prefix_hit_rate",
                 "capacity_free", "queue_delay_ms", "tenants",
                 "spec_accept_rate", "step_host_overhead_frac",
                 "step_tokens_per_sec", "role"}
    for url in (plain_url, cont_url):
        with urllib.request.urlopen(url + "/loadz") as resp:
            assert resp.status == 200
            out = json.loads(resp.read())
        assert set(out) == want_keys
        assert out["draining"] is False
        assert out["kv_pages_free"] is None  # dense engine / whole-batch
        # autoscale terms: a whole-batch server has no admission queue
        # (zeros); the slot engine advertises real token headroom
        assert isinstance(out["capacity_free"], int)
        assert isinstance(out["tenants"], dict)
        # step telemetry: a fraction in [0, 1] (0.0 on whole-batch —
        # no step loop; the slot engine's windowed host-overhead share)
        assert 0.0 <= out["step_host_overhead_frac"] <= 1.0
    with urllib.request.urlopen(cont_url + "/loadz") as resp:
        assert json.loads(resp.read())["capacity_free"] > 0
    with urllib.request.urlopen(cont_url + "/loadz") as resp:
        cont = json.loads(resp.read())
    assert cont["slots_total"] == 2  # the slot engine's pool
    with urllib.request.urlopen(plain_url + "/loadz") as resp:
        plain = json.loads(resp.read())
    assert plain["slots_total"] == 0  # whole-batch: zeros, still ranks


def test_continuous_sampling_routes_through_engine(cb_endpoints):
    # temperature/top-p requests ride the slot engine (per-slot keys);
    # beams stay on the whole-batch path — both must serve.
    _, cont_url = cb_endpoints
    with urllib.request.urlopen(cont_url + "/healthz") as resp:
        before = json.loads(resp.read())["continuous"]["finished"]
    out = _post(cont_url, "/v1/generate",
                {"prompts": ["ab"], "max_new_tokens": 4,
                 "temperature": 0.8, "top_p": 0.9})["completions"]
    assert len(out) == 1 and out[0]["new_tokens"] > 0
    with urllib.request.urlopen(cont_url + "/healthz") as resp:
        after = json.loads(resp.read())["continuous"]["finished"]
    assert after == before + 1  # the engine served it
    beams = _post(cont_url, "/v1/generate",
                  {"prompts": ["ab"], "max_new_tokens": 4,
                   "num_beams": 2})["completions"]
    assert "beam_score" in beams[0]  # whole-batch fallback intact


def test_seed_pins_sampled_completions(cb_endpoints):
    """PR 15 satellite: a client-pinned ``seed`` makes SAMPLED
    completions deterministic on both serving paths (slot engine and
    whole-batch), greedy stays byte-identical with or without it, and
    a garbage seed is a 400."""
    plain_url, cont_url = cb_endpoints
    for url in (plain_url, cont_url):
        sampled = {"prompts": ["ab"], "max_new_tokens": 6,
                   "temperature": 0.9, "seed": 1234}
        a = _post(url, "/v1/generate", sampled)["completions"]
        b = _post(url, "/v1/generate", sampled)["completions"]
        assert a[0]["completion"] == b[0]["completion"]
        # greedy ignores seed entirely
        g1 = _post(url, "/v1/generate",
                   {"prompts": ["ab"], "max_new_tokens": 6})
        g2 = _post(url, "/v1/generate",
                   {"prompts": ["ab"], "max_new_tokens": 6,
                    "seed": 7})
        assert g1["completions"][0]["completion"] == \
            g2["completions"][0]["completion"]
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(cont_url, "/v1/generate",
              {"prompts": ["ab"], "max_new_tokens": 2, "seed": "x"})
    assert exc.value.code == 400
    assert "seed" in json.loads(exc.value.read())["error"]


def test_stream_continuation_framing(cb_endpoints):
    """PR 15: continuation-aware SSE framing — a stream whose prompt
    embeds previously-emitted text frames its terminal entry against
    the ORIGINAL prompt and the CUMULATIVE token count, token-exactly
    vs an uninterrupted control stream."""
    _, cont_url = cb_endpoints

    def stream(body):
        req = urllib.request.Request(
            cont_url + "/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        events, terminal = [], None
        with urllib.request.urlopen(req, timeout=120) as resp:
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data: ") \
                        or line == "data: [DONE]":
                    continue
                ev = json.loads(line[len("data: "):])
                if ev.get("done"):
                    terminal = ev
                else:
                    events.append(ev)
        toks = [t for ev in events for t in ev.get("token_ids") or []]
        return events, toks, terminal

    _, control, control_term = stream(
        {"prompts": ["abc"], "stream": True, "max_new_tokens": 8})
    assert control_term["prompt"] == "abc"
    assert control_term["new_tokens"] == len(control)
    assert "resumed" not in control_term
    # simulate the router's splice: cut anywhere and re-submit the
    # ORIGINAL prompt + the emitted token IDS (what the journal holds
    # — ids, not text: random-weight models emit non-UTF-8 byte runs
    # that would not survive a decode→encode round-trip)
    cut = 3
    assert 0 < cut < len(control)
    cont_events, cont_toks, cont_term = stream(
        {"prompts": ["abc"], "stream": True,
         "max_new_tokens": len(control) - cut,
         "continuation": {"emitted_ids": control[:cut]}})
    # greedy continuation is token-exact past the cut, and its running
    # text EXTENDS the original prompt (the router's splice check)
    assert control[:cut] + cont_toks == control
    assert all(ev["text"].startswith("abc") for ev in cont_events)
    assert cont_term["prompt"] == "abc"
    assert cont_term["new_tokens"] == len(control)
    assert cont_term["resumed"] is True
    assert cont_term["completion"] == control_term["completion"]
    # malformed framing is a 400, not a mis-framed stream
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(cont_url, "/v1/generate",
              {"prompts": ["abc"], "stream": True, "max_new_tokens": 4,
               "continuation": {"emitted_ids": []}})
    assert exc.value.code == 400


def test_continuous_front_engine_failure_unit(tmp_path):
    # Unit-level: fault-inject engine.step once; the front must fail
    # that request with a 500-shaped error and serve the next one.
    cfg = CausalLMConfig(**CFG)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(2), ids)["params"])
    from pyspark_tf_gke_tpu.train.serve import _ContinuousFront

    front = _ContinuousFront(model, params, eos_id=None, num_slots=2,
                             chunk=2)
    try:
        boom = RuntimeError("injected device failure")
        original_step = front.engine.step
        calls = {"n": 0}

        def flaky_step():
            calls["n"] += 1
            if calls["n"] == 1:
                raise boom
            return original_step()

        front.engine.step = flaky_step
        with pytest.raises(RuntimeError, match="injected device failure"):
            front.submit_and_wait([1, 2, 3], 4, timeout_s=60)
        # engine was rebuilt (fresh object, un-patched step) and serves
        toks = front.submit_and_wait([1, 2, 3], 4, timeout_s=120)
        assert len(toks) == 4
    finally:
        front.shutdown()


def test_metrics_endpoint(cb_endpoints):
    plain_url, cont_url = cb_endpoints
    _post(plain_url, "/v1/generate", {"prompts": ["zz"],
                                      "max_new_tokens": 3})
    _post(plain_url, "/v1/score", {"texts": ["zz"]})
    try:
        _post(plain_url, "/v1/generate", {"prompts": ["ok"],
                                          "max_new_tokens": None})
    except urllib.error.HTTPError as exc:
        assert exc.code == 400
    with urllib.request.urlopen(plain_url + "/metrics") as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    metrics = {ln.split()[0]: float(ln.split()[1])
               for ln in text.splitlines() if ln and not ln.startswith("#")}
    pre = "pyspark_tf_gke_tpu_serve_"
    assert metrics[pre + "generate_requests_total"] >= 1
    assert metrics[pre + "generate_tokens_total"] >= 3
    assert metrics[pre + "score_requests_total"] >= 1
    assert metrics[pre + "requests_failed_total"] >= 1
    assert metrics[pre + "generate_latency_ms_sum"] > 0
    # the continuous server additionally exposes engine gauges
    with urllib.request.urlopen(cont_url + "/metrics") as resp:
        ctext = resp.read().decode()
    assert pre + "continuous_num_slots 2" in ctext

    # ISSUE 1 acceptance: the exposition is the shared obs registry, so
    # after a served request it carries at least one family from each
    # plane (train_ families are pre-registered by the shared naming
    # scheme; serve_/runtime_ carry live values here)
    families = {ln.split("{")[0].split()[0] for ln in text.splitlines()
                if ln and not ln.startswith("#")}
    assert any(f.startswith("train_") for f in families)
    assert any(f.startswith("serve_") for f in families)
    assert any(f.startswith("runtime_") for f in families)
    # canonical serve counters carry the same live values the legacy
    # aliases report
    assert metrics["serve_requests_total"] >= metrics[
        pre + "generate_requests_total"]
    assert metrics["serve_generate_tokens_total"] == metrics[
        pre + "generate_tokens_total"]
    # strict superset of the pre-obs exposition names
    legacy = {pre + k for k in (
        "requests_total", "requests_failed_total", "generate_tokens_total",
        "generate_latency_ms_sum", "generate_requests_total",
        "score_requests_total")}
    assert legacy <= families


def test_metrics_json_and_events_endpoints(cb_endpoints):
    plain_url, _ = cb_endpoints
    _post(plain_url, "/v1/generate", {"prompts": ["zz"],
                                      "max_new_tokens": 2})
    with urllib.request.urlopen(plain_url + "/metrics.json") as resp:
        snap = json.loads(resp.read())
    assert snap["serve_requests_total"] >= 1
    assert "runtime_process_rss_bytes" in snap
    with urllib.request.urlopen(plain_url + "/events?n=10") as resp:
        out = json.loads(resp.read())
    assert "events" in out  # shape contract; content depends on session


def test_streaming_generate_sse(cb_endpoints):
    plain_url, cont_url = cb_endpoints
    # reference: the non-streaming continuous completion
    ref = _post(cont_url, "/v1/generate",
                {"prompts": ["stream me"],
                 "max_new_tokens": 7})["completions"][0]["completion"]

    req = urllib.request.Request(
        cont_url + "/v1/generate",
        data=json.dumps({"prompt": "stream me", "max_new_tokens": 7,
                         "stream": True}).encode())
    events = []
    with urllib.request.urlopen(req, timeout=300) as resp:
        assert resp.headers["Content-Type"] == "text/event-stream"
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            events.append(json.loads(payload))
    assert events, "no SSE events arrived"
    final = events[-1]
    assert final.get("done") is True
    assert final["completion"] == ref  # token-identical to non-streaming
    assert final["new_tokens"] == 7
    token_events = [e for e in events if "token_ids" in e]
    # chunk=3, budget 7 => at least 3 incremental groups
    assert len(token_events) >= 2
    assert sum(len(e["token_ids"]) for e in token_events) == 7
    # each event carries the full text so far; they must be prefixes
    texts = [e["text"] for e in token_events]
    for a, b in zip(texts, texts[1:]):
        assert b.startswith(a[:len("stream me")])


def test_streaming_rejects_sampling_and_plain_server(cb_endpoints):
    plain_url, cont_url = cb_endpoints
    for url, payload, want in [
        (cont_url, {"prompt": "x", "stream": True, "temperature": 0.9},
         "greedy-only"),
        (cont_url, {"prompts": ["a", "b"], "stream": True},
         "exactly one prompt"),
        (plain_url, {"prompt": "x", "stream": True},
         "requires --continuous-slots"),
    ]:
        try:
            _post(url, "/v1/generate", payload)
            raise AssertionError(f"{payload} should have failed")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
            assert want in json.loads(exc.read())["error"]


@pytest.fixture(scope="module")
def warm_endpoint(tmp_path_factory):
    cfg = CausalLMConfig(**CFG)
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = nn.meta.unbox(jax.jit(model.init)(make_rng(3), ids)["params"])
    bundle = str(tmp_path_factory.mktemp("serve-warm") / "bundle")
    export_serving_bundle(cfg, params, bundle)
    server = BundleServer(bundle, continuous_slots=2, continuous_chunk=3,
                          prefix_cache_size=2)
    httpd = start_http_server(server, host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}", server
    httpd.shutdown()
    server._front.shutdown()


def test_warm_prefix_over_the_wire(warm_endpoint):
    url, server = warm_endpoint
    system = "system: answer briefly. "
    # cold reference BEFORE warming (same engine, no prefix entries)
    cold = _post(url, "/v1/generate",
                 {"prompts": [system + "hi"],
                  "max_new_tokens": 6})["completions"][0]["completion"]
    out = _post(url, "/v1/warm", {"prefix": system})
    assert out["prefix_tokens"] == len(system)
    assert out["prefix_cache"]["entries"] == 1
    warm = _post(url, "/v1/generate",
                 {"prompts": [system + "hi"],
                  "max_new_tokens": 6})["completions"][0]["completion"]
    assert warm == cold  # prefix-hit path is token-identical
    with urllib.request.urlopen(url + "/healthz") as resp:
        health = json.loads(resp.read())
    assert health["continuous"]["prefix_cache"]["hits"] >= 1


def test_warm_validation(warm_endpoint):
    url, _ = warm_endpoint
    for payload in ({"prefix": 7}, {}):
        try:
            _post(url, "/v1/warm", payload)
            raise AssertionError("should 400")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400


def test_chunked_prefill_over_the_wire(tmp_path_factory):
    # Regression: a request whose ONLY engine state is an in-flight
    # piecewise admission (active=0, queued=0) must keep the driver
    # loop stepping — the idle check parking on active/queued alone
    # hung exactly this case.
    cfg = dict(CFG)
    cfg["max_seq_len"] = 128
    c = CausalLMConfig(**cfg)
    model = CausalLM(c)
    params = nn.meta.unbox(jax.jit(model.init)(
        make_rng(4), jnp.zeros((1, 8), jnp.int32))["params"])
    bundle = str(tmp_path_factory.mktemp("serve-cp") / "bundle")
    export_serving_bundle(c, params, bundle)
    server = BundleServer(bundle, continuous_slots=2, continuous_chunk=2,
                          prefill_chunk=32)
    httpd = start_http_server(server, host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        long_prompt = "x" * 50  # 50 byte tokens > prefill_chunk 32
        out = _post(url, "/v1/generate",
                    {"prompt": long_prompt,
                     "max_new_tokens": 4})["completions"][0]
        assert out["new_tokens"] == 4
        assert out["completion"].startswith(long_prompt)
    finally:
        httpd.shutdown()
        server._front.shutdown()


def test_continuous_pipeline_flag_bounds():
    # depth validates at argparse time (before any bundle load): 0..4
    # accepted, negatives and chunk-sized confusions fail fast.
    from pyspark_tf_gke_tpu.train.serve import parse_args

    assert parse_args(["--bundle", "x",
                       "--continuous-pipeline", "2"]).continuous_pipeline == 2
    for bad in ("-1", "5", "64"):
        with pytest.raises(SystemExit):
            parse_args(["--bundle", "x", "--continuous-pipeline", bad])
