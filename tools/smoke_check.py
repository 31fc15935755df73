"""Installation smoke check — the analog of the reference's
``spark_installation_check.py`` (``workloads/raw-spark/spark_checks/
python_checks/spark_installation_check.py:12-46``): where that script
builds a ``local[2]`` in-process Spark session and runs a toy job, this
builds a 2-device virtual CPU mesh and runs a toy sharded training step.
Exit 0 = the framework and its distributed machinery work on this box.

Also the CI hook for the obs metric-naming contract: after an import
sweep over every ``pyspark_tf_gke_tpu`` module, any metric name
registered with two different shapes (type or label set) anywhere in
the process fails the check — a duplicate-name metric would make one
``/metrics`` scrape silently ambiguous.

``--kernels-only`` runs the interpret-mode kernel sweep instead: every
``ops/pallas/*`` kernel executes (interpret=True, tiny shapes) against
its pure-JAX reference, so kernel/reference drift fails fast on a CPU
box long before a TPU ever compiles it.

``--serve-lifecycle`` checks the graceful-drain contract end to end:
a tiny BundleServer subprocess gets SIGTERM with a request in flight
and must BOTH complete that response and exit 0 within the grace
window — the k8s rolling-restart behavior, provable on any dev box.

``--serve-tbt`` checks the chunked-prefill scheduling contract: one
long prompt injected into a decoding engine must interleave with
decode chunks and keep the streamer's worst token gap bounded
(chunking on), while the monolithic prefill's unbounded stall is
detected with it off.

``--router`` checks the replica-router failover contract: 2 CPU
replica subprocesses behind a router subprocess, concurrent requests,
SIGKILL one replica mid-run — every request must reach a terminal
outcome (the survivors via hedge/re-route), and the router must drain
and exit 0 on SIGTERM.

``--prefix-cache`` checks the radix prefix-cache contract through a
live CPU server: two generates sharing a long prompt prefix — the
second request's COMPUTED prefill tokens (engine counter, via
``/healthz``) must stay under unique-suffix + one prefill chunk, and
``/loadz`` must report a nonzero hit rate, so the router's
affinity signal is provably fed by real cache contents.

``--fairness`` checks multi-tenant overload isolation through a live
CPU server with a ``--tenants`` spec: three flooding noisy-tenant
threads vs one serial light tenant — the light tenant completes every
request with bounded p99 while every shed the flood draws is a
PER-TENANT 429 (tenant_quota / tenant_queue_full), never a global one.

``--pipeline`` checks the continuous ETL→train→publish loop end to
end: two coordinator rounds (ingest synthetic rows → native TFRecord
manifest → train → export), a live CPU replica hot-swapped to the new
bundle generation MID generate-stream (explicit stream terminal, zero
drops), a corrupt-bundle publish rolled off with the old generation
intact, and a clean SIGTERM drain.

``--trace`` checks the end-to-end tracing contract live: a generate
with an injected ``traceparent`` through a router subprocess + 1 CPU
replica must surface the SAME trace id on both processes' ``/traces``
(serve-side timeline carrying queue-wait/admission/prefill-chunk/
first-token/terminal events), echo it as ``X-Request-Id`` including on
a per-tenant 429 shed (with the shed verdict on the trace), and a
pipeline round's trace id must be recoverable from the published
bundle's meta.

``--replay`` checks the trace-replay + capacity-planning contract: a
tiny synthetic flash-crowd spec replayed open-loop against a
2-replica CPU localfleet — every request terminal, the SLO report
machine-readable, the offline capacity model's prediction within the
documented band of the measured replay, and a live
``/traces?format=jsonl`` export round-tripped into a replayable spec.

``--spec-serve`` checks in-engine speculative decoding through a live
server: --spec-tokens completions token-identical to the plain engine,
with a nonzero ``/loadz spec_accept_rate``.

``--stepstats`` checks the engine step-telemetry contract live
(docs/OBSERVABILITY.md "Step telemetry & profiling"): a CPU replica
under a small request burst must serve a non-empty ``GET /stepz``
ring whose per-record phase sums reconcile with the step wall, a
populated ``serve_step_host_overhead_ms`` histogram, a ``/loadz
step_host_overhead_frac`` in [0, 1], and ``POST /admin/profile``
must 403 on a token-unconfigured server (the /admin/reload
discipline).

``--failover-stream`` checks the mid-stream failover contract live
(docs/SERVING.md "Stream failover & resume"): SIGKILL the replica
actually holding a streaming generation after >=4 emitted tokens —
the client's stream must still reach ``[DONE]`` with zero error
terminals and be TOKEN-IDENTICAL to an uninterrupted control run
(the router's journal + continuation splice), with exactly one
``router_stream_resumes_total{outcome="ok"}`` on the router.

``--watchtower`` checks the fleet watchtower's chaos-native contract
live (docs/OBSERVABILITY.md "Fleet watchtower"): a 2-replica fleet
behind the router under light load must populate the ``/fleetz``
rollups with ZERO alerts fired during a steady control window; then
SIGKILL one replica — the structural ``replica_down`` alert must fire
within the documented detection bound and resolve (fire_count exactly
1) after the restart re-admits the replica.

``--disagg`` checks the disaggregated prefill/decode handoff live
(docs/SERVING.md "Disaggregated prefill/decode"): 1 prefill-role + 1
decode-role CPU replica behind a router with ``--disagg-min-prompt``
— a long-prompt generate must ride the KV-page transfer
(``router_kv_xfer_total{outcome="ok"}`` >= 1), the decode replica's
radix cache must hold the transferred pages, a same-prefix repeat
must admit as a LOCAL hit (computed prefill tokens under suffix + one
chunk), and both replicas' idle page accounting must balance — every
in-use page trie-resident, the refcount audit green on both sides.

Usage: python tools/smoke_check.py
       [--lint-only|--kernels-only|--serve-lifecycle|--serve-tbt|
        --router|--prefix-cache|--spec-serve|--fairness|--pipeline|
        --trace|--replay|--stepstats|--failover-stream|--watchtower|
        --disagg]
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=2").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pyspark_tf_gke_tpu.data.pipeline import BatchIterator  # noqa: E402
from pyspark_tf_gke_tpu.data.synthetic import synthetic_classification_arrays  # noqa: E402
from pyspark_tf_gke_tpu.models import MLPClassifier  # noqa: E402
from pyspark_tf_gke_tpu.parallel.mesh import make_mesh  # noqa: E402
from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer  # noqa: E402
from pyspark_tf_gke_tpu.utils.seeding import make_rng  # noqa: E402


def lint_duplicate_metrics() -> int:
    """Import every package module, run the platform's registration
    entry points, then fail on any metric name registered with more
    than one (type, labelnames) shape.

    Two stages make the lint non-vacuous: (1) the import sweep catches
    module-level registrations anywhere in the package; (2) the
    canonical constructor-time entry points — ``platform_families``
    (the whole train_/serve_ naming scheme, what Trainer, BundleServer
    and ContinuousEngine register through) and
    ``install_runtime_metrics`` — are invoked explicitly, so a scheme
    name colliding with any module-level registration fails here, not
    in production. A guard asserts the registration record is
    non-empty afterwards: if a refactor ever disconnects the entry
    points from the record, the lint fails loudly instead of passing
    on nothing. Modules that cannot import on this box (optional
    accelerator deps) are reported but don't fail the lint — a missing
    dep is not a naming conflict."""
    import importlib
    import pkgutil

    import pyspark_tf_gke_tpu
    from pyspark_tf_gke_tpu.obs.metrics import (
        MetricsRegistry,
        _REGISTRATIONS,
        duplicate_metric_conflicts,
        platform_families,
    )
    from pyspark_tf_gke_tpu.obs.runtime import install_runtime_metrics

    skipped = []
    for info in pkgutil.walk_packages(pyspark_tf_gke_tpu.__path__,
                                      prefix="pyspark_tf_gke_tpu."):
        try:
            importlib.import_module(info.name)
        except Exception as exc:  # noqa: BLE001 — optional deps may be absent
            skipped.append(f"{info.name}: {type(exc).__name__}: {exc}")
    if skipped:
        print(f"metric lint: {len(skipped)} module(s) not importable "
              "(skipped, not a naming failure):")
        for s in skipped:
            print(f"  - {s}")
    # exercise the canonical registration paths (throwaway registry —
    # the record is process-global either way). router_families is the
    # router plane's entry point (pyspark_tf_gke_tpu/router/) — its
    # router_* names ride the same one-name-one-shape contract.
    from pyspark_tf_gke_tpu.obs.metrics import (
        autopilot_families,
        chaos_families,
        replay_families,
        router_families,
    )

    scheme = MetricsRegistry()
    platform_families(scheme)
    router_families(scheme)
    autopilot_families(scheme)
    replay_families(scheme)
    chaos_families(scheme)
    install_runtime_metrics(scheme)
    if not _REGISTRATIONS:
        print("metric lint FAILED — registration record is empty after "
              "the sweep; the lint is observing nothing")
        return 1
    # presence guard for families the router plane DEPENDS on
    # reading (not just naming-conflict-free): the radix prefix cache's
    # serve_* names feed /loadz's prefix_hit_rate — a refactor that
    # drops one must fail here
    required = {"serve_prefix_cache_hits_total",
                "serve_prefix_cache_hit_tokens_total",
                "serve_prefix_cache_pages",
                "serve_prefix_cache_evictions_total",
                # multi-tenant fairness + the closed-loop autoscale
                # signal: /loadz capacity_free and the HPA manifest
                # (infra/k8s/tpu/tpu-serve-hpa.yaml) depend on these
                # names existing — a rename must fail here first
                "serve_tenant_requests_total",
                "serve_tenant_rejected_total",
                "serve_tenant_tokens_total",
                "serve_tenant_queue_depth",
                "serve_capacity_free_tokens",
                "router_capacity_free_total",
                "router_demand_tokens_total",
                "router_queue_delay_ms",
                "router_tenant_sheds_total",
                # continuous pipeline plane: the coordinator's round
                # loop and the serving fleet's hot-swap rollout signal
                # (docs/PIPELINE.md) — the publish confirmation reads
                # bundle_generation, so these names are load-bearing
                "pipeline_rounds_total",
                "pipeline_stage_seconds",
                "pipeline_stage_failures_total",
                "pipeline_bundle_generation",
                "pipeline_freshness_seconds",
                "serve_bundle_generation",
                "serve_bundle_reloads_total",
                # request tracing: the /traces flight recorders'
                # retention counters, and the histograms that carry
                # per-bucket trace-id exemplars in the JSON snapshot
                # (docs/OBSERVABILITY.md "Tracing") — renames must
                # fail here first
                "serve_traces_recorded_total",
                "router_traces_recorded_total",
                "serve_generate_latency_ms",
                "router_request_latency_ms",
                # trace-replay plane: the SLO reports and the capacity
                # model's agreement check are built on these
                # client-side families (docs/REPLAY.md) — a rename
                # must fail here first
                "replay_requests_total",
                "replay_tenant_requests_total",
                "replay_sheds_total",
                "replay_ttft_ms",
                "replay_tbt_ms",
                "replay_request_latency_ms",
                "replay_sched_lag_ms",
                "replay_goodput",
                # chaos plane: the fault-sweep gates (--chaos, replay
                # run --chaos, test_chaos) assert injections/actions
                # were non-vacuous through these names, and the step
                # watchdog's interventions must stay scrapable
                "fault_injections_total",
                "chaos_actions_total",
                "serve_step_watchdog_reaps_total",
                # self-draft speculative decoding: /loadz
                # spec_accept_rate and the
                # capacity model's (1 + k·accept) what-if knob read
                # these — a rename must fail here first
                "serve_spec_proposed_total",
                "serve_spec_accepted_total",
                "serve_spec_accept_rate",
                # engine step telemetry (obs/stepstats.py): the
                # host/device decomposition — /stepz, engine.stats'
                # step_phases block, /loadz step_host_overhead_frac
                # and the router's autoscale fold all derive from
                # these families. serve_device_idle_fraction is the
                # interval-derived (dispatch/retire) idle number
                # since the async engine core; the --stepstats gate
                # asserts it runs strictly below the same window's
                # legacy host-work share (overlap is live)
                "serve_step_host_overhead_ms",
                "serve_step_phase_ms",
                "serve_device_idle_fraction",
                "serve_mfu",
                # mid-stream failover: the smoke gate
                # (--failover-stream)
                # and docs/OBSERVABILITY.md's resume vocabulary read
                # these — a rename must fail here first
                "router_stream_resumes_total",
                "router_stream_tokens_replayed_total",
                "router_stream_journal_entries",
                "router_stream_journal_tokens",
                "router_idempotent_replays_total",
                # fleet watchtower (router/watchtower.py): the live
                # SLO burn-rate/alerting plane and the /fleetz
                # snapshot ring — the --watchtower gate and the
                # autopilot contract read these names
                "router_slo_burn_rate",
                "router_alerts_firing",
                "router_alert_transitions_total",
                "router_fleet_snapshots_total",
                "router_fleet_snapshot_buckets",
                # autopilot (router/autopilot.py): the closed-loop
                # fleet controller's decision/veto/actuation
                # accounting — the --autopilot gate and
                # docs/AUTOPILOT.md read these
                "autopilot_ticks_total",
                "autopilot_decisions_total",
                "autopilot_vetoes_total",
                "autopilot_actuations_total",
                "autopilot_actuation_retries_total",
                "autopilot_replicas_desired",
                # disaggregated prefill/decode: the KV-page handoff
                # accounting (engine export/import + router transfer
                # legs) and the per-role fleet split the prefill HPA
                # (infra/k8s/tpu/tpu-serve-prefill.yaml) scales on —
                # a rename must fail here first
                "serve_kv_xfer_export_total",
                "serve_kv_xfer_import_total",
                "serve_kv_xfer_bytes_total",
                "serve_kv_xfer_failures_total",
                "router_kv_xfer_total",
                "router_kv_xfer_latency_ms",
                "router_role_replicas",
                "router_role_demand_tokens",
                "router_role_capacity_free"}
    absent = {n for n in required if n not in _REGISTRATIONS}
    if absent:
        print("metric lint FAILED — required metric name(s) never "
              f"registered: {sorted(absent)}")
        return 1
    conflicts = duplicate_metric_conflicts()
    if conflicts:
        print("metric lint FAILED — same name, different shape:")
        for c in conflicts:
            print(f"  - {c}")
        return 1
    print(f"metric lint OK: {len(_REGISTRATIONS)} metric name(s), "
          "no duplicate shapes")
    return 0


def kernel_interpret_sweep() -> int:
    """Run every ``ops/pallas`` kernel in interpret mode on tiny shapes
    and compare against its pure-JAX reference. One tolerance for all:
    these run in f32, so 1e-4 absolute catches real drift (a changed
    mask, a dropped scale) without flaking on accumulation-order ulps.
    Returns the number of failing kernels."""
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.utils.seeding import np_rng

    rng = np_rng(0)
    failures = []

    def check(name, got, want, atol=1e-4):
        got, want = np.asarray(got), np.asarray(want)
        err = float(np.max(np.abs(got - want))) if got.size else 0.0
        ok = got.shape == want.shape and err <= atol
        print(f"kernel {name}: max|err| = {err:.2e} "
              f"({'OK' if ok else 'FAIL'})")
        if not ok:
            failures.append(name)

    # flash attention (fwd, causal + padding mask) vs the dense path
    from pyspark_tf_gke_tpu.ops.attention import dot_product_attention
    from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention

    b, s, h, d = 2, 16, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(rng.integers(0, 2, (b, s)).astype(bool))
    mask = mask.at[:, 0].set(True)  # >= 1 live key per row
    check("flash_attention[causal]",
          flash_attention(q, k, v, causal=True, interpret=True),
          dot_product_attention(q, k, v, causal=True))
    check("flash_attention[kv_mask]",
          flash_attention(q, k, v, kv_mask=mask, interpret=True),
          dot_product_attention(q, k, v,
                                mask=mask[:, None, None, :]))

    # fused layernorm vs the textbook f32 math
    from pyspark_tf_gke_tpu.ops.pallas.layernorm import fused_layernorm

    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    scale = jnp.asarray(rng.standard_normal(16), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16), jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    check("fused_layernorm",
          fused_layernorm(x, scale, bias, eps=1e-6, interpret=True),
          (x - mu) / jnp.sqrt(var + 1e-6) * scale + bias)

    # fused norm+relu matmul (+stats epilogue) vs jnp
    from pyspark_tf_gke_tpu.ops.pallas.fused_matmul import norm_relu_matmul

    xm = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    am = jnp.asarray(rng.standard_normal(16), jnp.float32)
    bm = jnp.asarray(rng.standard_normal(16), jnp.float32)
    y, ssum, ssq = norm_relu_matmul(xm, wm, am, bm, want_stats=True,
                                    interpret=True)
    y_ref = jnp.maximum(xm * am + bm, 0.0) @ wm
    check("norm_relu_matmul", y, y_ref)
    check("norm_relu_matmul[stats]",
          jnp.stack([ssum, ssq]),
          jnp.stack([y_ref.sum(0), (y_ref * y_ref).sum(0)]))

    # fused 3x3 conv vs lax.conv
    from pyspark_tf_gke_tpu.ops.pallas.fused_conv3 import conv3_norm_stats

    xc = jnp.asarray(rng.standard_normal((1, 6, 6, 4)), jnp.float32)
    wc = jnp.asarray(rng.standard_normal((3, 3, 4, 4)) * 0.2, jnp.float32)
    ac = jnp.asarray(rng.standard_normal(4), jnp.float32)
    bc = jnp.asarray(rng.standard_normal(4), jnp.float32)
    ref_in = jnp.maximum(xc * ac + bc, 0.0)
    conv_ref = jax.lax.conv_general_dilated(
        ref_in, wc, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    check("conv3_norm_stats",
          conv3_norm_stats(xc, wc, ac, bc, interpret=True), conv_ref)

    # paged attention (block-table gather, ragged fills, int8 pages)
    from pyspark_tf_gke_tpu.ops.pallas.paged_attention import (
        paged_attention,
        paged_attention_reference,
    )

    n_pg, p_sz, hkv, mp = 8, 4, 2, 3
    kp, vp = (jnp.asarray(rng.standard_normal((n_pg, p_sz, hkv, d)),
                          jnp.float32) for _ in range(2))
    qp = jnp.asarray(rng.standard_normal((3, h * 2, d)), jnp.float32)
    table = jnp.asarray(rng.integers(0, n_pg, (3, mp)), jnp.int32)
    table = table.at[1, 1:].set(n_pg)  # sentinel (unallocated) entries
    fills = jnp.asarray([mp * p_sz, 3, 0], jnp.int32)  # full/partial/empty
    check("paged_attention",
          paged_attention(qp, kp, vp, table, fills, interpret=True),
          paged_attention_reference(qp, kp, vp, table, fills))
    kq = jnp.asarray(rng.integers(-127, 128, (n_pg, p_sz, hkv, d)),
                     jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (n_pg, p_sz, hkv, d)),
                     jnp.int8)
    ks = jnp.asarray(rng.random((n_pg, p_sz, hkv)) * 0.02 + 1e-3,
                     jnp.float32)
    vs = jnp.asarray(rng.random((n_pg, p_sz, hkv)) * 0.02 + 1e-3,
                     jnp.float32)
    check("paged_attention[int8]",
          paged_attention(qp, kq, vq, table, fills, k_scales=ks,
                          v_scales=vs, interpret=True),
          paged_attention_reference(qp, kq, vq, table, fills,
                                    k_scales=ks, v_scales=vs))

    # multi-query paged chunks (chunked prefill): in-chunk causal mask
    # over the same block-table gather; empty slot + partial fill
    from pyspark_tf_gke_tpu.ops.pallas.paged_attention import (
        paged_attention_chunk,
        paged_attention_chunk_reference,
    )

    sq = 4
    qc = jnp.asarray(rng.standard_normal((3, sq, h * 2, d)), jnp.float32)
    fills_c = jnp.asarray([0, sq, p_sz + 2], jnp.int32)
    check("paged_attention_chunk",
          paged_attention_chunk(qc, kp, vp, table, fills_c,
                                interpret=True),
          paged_attention_chunk_reference(qc, kp, vp, table, fills_c))
    check("paged_attention_chunk[int8]",
          paged_attention_chunk(qc, kq, vq, table, fills_c, k_scales=ks,
                                v_scales=vs, interpret=True),
          paged_attention_chunk_reference(qc, kq, vq, table, fills_c,
                                          k_scales=ks, v_scales=vs))

    if failures:
        print(f"kernel sweep FAILED: {failures}")
        return 1
    print("kernel sweep OK: every ops/pallas kernel matches its "
          "pure-JAX reference in interpret mode")
    return 0


def serve_lifecycle_check(grace_s: float = 60.0) -> int:
    """SIGTERM-with-work-in-flight: export a tiny bundle, serve it in a
    subprocess (continuous slots, so the drain covers the slot engine),
    put a long generate in flight, SIGTERM the server, then require

    1. the in-flight response completes (HTTP 200, full budget),
    2. the process exits 0 within ``grace_s`` (the k8s
       terminationGracePeriodSeconds analog),
    3. /healthz flipped to 503 draining in between (best-effort read —
       the server may exit before the probe lands; that's a pass).

    Returns 0 on success. Heavy chaos soaks live in
    tests/test_serve_lifecycle.py (slow-marked); this is the quick CI
    hook."""
    import json as _json
    import signal
    import socket
    import subprocess
    import tempfile
    import threading
    import time as _time
    import urllib.error
    import urllib.request

    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.train.export import export_serving_bundle
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    tmp = tempfile.mkdtemp(prefix="serve-lifecycle-")
    cfg = CausalLMConfig(vocab_size=259, hidden_size=32, num_layers=2,
                         num_heads=2, intermediate_size=64, max_seq_len=64,
                         dtype=jnp.float32)
    model = CausalLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        make_rng(0), jnp.zeros((1, 8), jnp.int32))["params"])
    bundle = os.path.join(tmp, "bundle")
    export_serving_bundle(cfg, params, bundle, quantize=False)

    with socket.socket() as s:  # free port; tiny reuse race is fine here
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pyspark_tf_gke_tpu.train.serve",
         "--bundle", bundle, "--host", "127.0.0.1", "--port", str(port),
         "--continuous-slots", "2", "--continuous-chunk", "2",
         "--drain-timeout", "30",
         "--heartbeat-file", os.path.join(tmp, "hb.json")],
        env=env)

    def post(payload: dict, timeout: float = 120.0) -> dict:
        req = urllib.request.Request(
            url + "/v1/generate", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return _json.loads(resp.read())

    failures = []
    try:
        deadline = _time.time() + 180
        while _time.time() < deadline:
            try:
                urllib.request.urlopen(url + "/healthz", timeout=2)
                break
            except Exception:  # noqa: BLE001 — still booting
                if proc.poll() is not None:
                    print(f"server died during startup (rc={proc.poll()})")
                    return 1
                _time.sleep(0.5)
        else:
            print("server never became healthy")
            return 1
        post({"prompts": ["warm"], "max_new_tokens": 2})  # compile now

        result: dict = {}

        def request():
            try:
                result["completions"] = post(
                    {"prompts": ["graceful"],
                     "max_new_tokens": 48})["completions"]
            except Exception as exc:  # noqa: BLE001 — checked below
                result["error"] = repr(exc)

        t = threading.Thread(target=request)
        t.start()
        # wait for the request to actually occupy a slot, then SIGTERM
        # mid-flight (best effort — a too-fast decode still exercises
        # the drain path, just with an empty engine)
        spot = _time.time() + 5
        while _time.time() < spot:
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=2) as resp:
                    if _json.loads(resp.read())["continuous"]["active"]:
                        break
            except Exception:  # noqa: BLE001
                break
            _time.sleep(0.01)
        proc.send_signal(signal.SIGTERM)
        # best-effort: readiness should now say 503 draining
        try:
            urllib.request.urlopen(url + "/healthz", timeout=2)
        except urllib.error.HTTPError as exc:
            if exc.code != 503:
                failures.append(f"draining healthz gave {exc.code}")
        except Exception:  # noqa: BLE001 — already exited: fine
            pass
        t.join(timeout=grace_s)
        if t.is_alive():
            failures.append("in-flight request HUNG through the drain")
        elif "completions" not in result:
            failures.append(f"in-flight request failed: {result}")
        elif result["completions"][0]["new_tokens"] < 1:
            # > 0, not == budget: the random-init model may greedily
            # emit the byte tokenizer's eos early — truncation there is
            # model behavior, not a drain failure
            failures.append(f"empty completion: {result}")
        try:
            rc = proc.wait(timeout=grace_s)
            if rc != 0:
                failures.append(f"server exited {rc}, want 0")
        except subprocess.TimeoutExpired:
            failures.append(f"server still alive {grace_s}s after SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
    if failures:
        print("serve lifecycle FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("serve lifecycle OK: in-flight request completed, healthz "
          "flipped to draining, process exited 0 within the grace window")
    return 0


def serve_tbt_check() -> int:
    """``--serve-tbt``: the head-of-line-blocking contract, provable on
    a CPU box. A short request streams tokens from the paged slot
    engine while ONE long prompt (1024 tokens) arrives mid-decode:

    * chunked prefill ON  -> the admission must interleave with decode
      chunks (>= 2 decode collects while the admission is in flight)
      and the streamer's worst token gap stays bounded by piece-sized
      stalls;
    * chunked prefill OFF -> the whole admission lands inside ONE
      engine step (no interleaving possible) — the unbounded-stall
      failure mode, detected as a strictly larger worst gap.

    Both engines produce identical tokens (parity is the slot engine's
    standing oracle; here we assert the SCHEDULING difference)."""
    import dataclasses
    import time as _time

    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    cfg = CausalLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_heads=4, num_kv_heads=2,
                         intermediate_size=64, max_seq_len=2048,
                         dtype=jnp.float32)
    model = CausalLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        make_rng(0), jnp.ones((1, 8), jnp.int32))["params"])
    paged = CausalLM(dataclasses.replace(cfg, kv_page_size=64,
                                         kv_num_pages=64))
    rng = np.random.default_rng(0)
    short = rng.integers(1, 97, 12)
    long_p = rng.integers(1, 97, 1024)

    def run(chunked: bool):
        kw = (dict(prefill_chunk=128, step_token_budget=160)
              if chunked else {})
        eng = ContinuousEngine(paged, params, num_slots=2, chunk=4,
                               buckets=(16, 2048), **kw)
        # warm every program (buckets, piece width, decode sizes)
        eng.submit(short, max_new_tokens=2)
        eng.submit(long_p, max_new_tokens=2)
        list(eng.run_until_drained())
        ts = []
        eng.submit(short, max_new_tokens=40,
                   on_tokens=lambda _t: ts.append(_time.perf_counter()))
        while not ts:  # the streamer is decoding before the long
            eng.step()  # prompt arrives
        eng.submit(long_p, max_new_tokens=4)
        interleaved = 0
        while (eng.stats["queued"] or eng.stats["active"]
               or eng.stats["admitting"] is not None):
            before = eng.stats
            eng.step()
            if before["admitting"] is not None and before["active"]:
                interleaved += 1
        gaps = [(b - a) * 1000.0 for a, b in zip(ts, ts[1:])]
        return interleaved, (max(gaps) if gaps else 0.0)

    inter_on, gap_on = run(chunked=True)
    inter_off, gap_off = run(chunked=False)
    if not gap_on < gap_off:
        # the interleave counts are deterministic but the two max-gap
        # numbers are one-shot wall-clock samples — one GC pause on a
        # loaded box can invert them. One full retry before declaring
        # a real scheduling regression.
        print("serve-tbt: timing inequality failed once "
              f"({gap_on:.1f}ms !< {gap_off:.1f}ms); retrying")
        inter_on, gap_on = run(chunked=True)
        inter_off, gap_off = run(chunked=False)
    print(f"serve-tbt: chunked ON  interleaved={inter_on} "
          f"max_gap={gap_on:.1f}ms")
    print(f"serve-tbt: chunked OFF interleaved={inter_off} "
          f"max_gap={gap_off:.1f}ms")
    failures = []
    if inter_on < 2:
        failures.append(
            f"chunked admission interleaved only {inter_on} decode "
            "collects (want >= 2) — pieces are stalling the stream")
    if inter_off != 0:
        failures.append(
            "unchunked engine reported interleaving — the stall "
            "detection baseline is broken")
    if not gap_on < gap_off:
        failures.append(
            f"chunked worst token gap {gap_on:.1f}ms not below the "
            f"unchunked monolithic-prefill stall {gap_off:.1f}ms")
    if failures:
        print("serve-tbt FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("serve-tbt OK: long-prompt admission interleaves with decode "
          "and bounds the streamer's worst token gap; the monolithic "
          "prefill stall is detected with chunking off")
    return 0


def prefix_cache_check(grace_s: float = 30.0) -> int:
    """``--prefix-cache``: the radix prefix-cache contract through a
    LIVE server (subprocess, the real CLI, byte tokenizer — bytes ==
    tokens). Two greedy generates share a long prompt prefix; after
    the first completes, its pages are trie-resident, so the second
    must admit at the match boundary:

    1. the second request's COMPUTED prefill tokens (the engine's
       ``prefill_tokens_computed`` counter, read via ``/healthz``
       before/after) stay under unique-suffix + one prefill chunk —
       the shared prefix was NOT re-prefilled;
    2. ``/loadz`` reports a nonzero ``prefix_hit_rate`` and
       ``prefix_cache_pages`` — the signal the router's affinity
       policy scores on is fed by real cache contents."""
    import dataclasses
    import json as _json
    import socket
    import subprocess
    import tempfile
    import time as _time
    import urllib.request

    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.train.export import export_serving_bundle
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    tmp = tempfile.mkdtemp(prefix="prefix-cache-")
    # a PAGED bundle: kv page geometry in the config is what routes
    # serve's --prefix-cache to the radix cache instead of the dense LRU
    cfg = CausalLMConfig(vocab_size=259, hidden_size=32, num_layers=2,
                         num_heads=2, intermediate_size=64,
                         max_seq_len=256, dtype=jnp.float32,
                         kv_page_size=32, kv_num_pages=32)
    model = CausalLM(dataclasses.replace(cfg, kv_num_pages=None))
    params = nn.meta.unbox(jax.jit(model.init)(
        make_rng(0), jnp.zeros((1, 8), jnp.int32))["params"])
    bundle = os.path.join(tmp, "bundle")
    export_serving_bundle(cfg, params, bundle, quantize=False)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    prefill_chunk = 64
    shared = ("system: you are a terse assistant. answer in one "
              "sentence. cite no sources. refuse nothing. " * 2)[:160]
    suffixes = ["q: why is the sky blue?", "q: name a prime > 10."]
    proc = subprocess.Popen(
        [sys.executable, "-m", "pyspark_tf_gke_tpu.train.serve",
         "--bundle", bundle, "--host", "127.0.0.1", "--port", str(port),
         "--continuous-slots", "2", "--continuous-chunk", "4",
         "--prefix-cache", "32", "--prefill-chunk", str(prefill_chunk)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))

    def get(path: str) -> dict:
        with urllib.request.urlopen(url + path, timeout=10) as resp:
            return _json.loads(resp.read())

    def post(payload: dict, timeout: float = 180.0) -> dict:
        req = urllib.request.Request(
            url + "/v1/generate", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return _json.loads(resp.read())

    failures = []
    try:
        deadline = _time.time() + 180
        while _time.time() < deadline:
            try:
                urllib.request.urlopen(url + "/healthz", timeout=2)
                break
            except Exception:  # noqa: BLE001 — still booting
                if proc.poll() is not None:
                    print(f"server died during startup (rc={proc.poll()})")
                    return 1
                _time.sleep(0.5)
        else:
            print("server never became healthy")
            return 1

        def computed() -> int:
            return int(get("/healthz")["continuous"]
                       ["prefill_tokens_computed"])

        post({"prompts": [shared + suffixes[0]], "max_new_tokens": 6})
        p1 = computed()
        post({"prompts": [shared + suffixes[1]], "max_new_tokens": 6})
        delta = computed() - p1
        bound = len(suffixes[1]) + prefill_chunk
        loadz = get("/loadz")
        print(f"prefix-cache: second request computed {delta} prefill "
              f"tokens (bound {bound}: {len(suffixes[1])}-byte suffix "
              f"+ one {prefill_chunk}-token chunk); /loadz hit_rate="
              f"{loadz.get('prefix_hit_rate')} "
              f"pages={loadz.get('prefix_cache_pages')}")
        if delta >= bound:
            failures.append(
                f"second request computed {delta} prefill tokens — not "
                f"< suffix + one chunk ({bound}); the shared prefix "
                "was re-prefilled")
        if not loadz.get("prefix_hit_rate"):
            failures.append(
                f"/loadz prefix_hit_rate={loadz.get('prefix_hit_rate')} "
                "— the router's affinity signal reads a cold cache")
        if not loadz.get("prefix_cache_pages"):
            failures.append(
                "/loadz prefix_cache_pages=0 — nothing stayed resident")
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    if failures:
        print("prefix-cache FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("prefix-cache OK: shared prefix prefilled once — the second "
          "request computed only its unique suffix, and /loadz exposes "
          "the hit rate the router scores on")
    return 0


def spec_serve_check(grace_s: float = 30.0) -> int:
    """``--spec-serve``: in-engine speculative decoding through a LIVE
    server (subprocess, the real CLI — the serve wiring from
    ``--spec-tokens``/``--draft-bundle`` down to the engine's
    draft/verify rounds):

    1. a server at ``--spec-tokens 3`` with a draft bundle answers
       greedy generates TOKEN-IDENTICAL to a ``--spec-tokens 0``
       server on the same bundle (the greedy-exactness contract, over
       real HTTP);
    2. ``/loadz`` reports ``spec_accept_rate > 0`` — speculation
       actually ran and accepted drafts (the draft bundle here holds
       the target's own weights, so acceptance is high by
       construction)."""
    import dataclasses
    import json as _json
    import socket
    import subprocess
    import tempfile
    import time as _time
    import urllib.request

    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.train.export import export_serving_bundle
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    tmp = tempfile.mkdtemp(prefix="spec-serve-")
    cfg = CausalLMConfig(vocab_size=259, hidden_size=32, num_layers=2,
                         num_heads=2, intermediate_size=64,
                         max_seq_len=256, dtype=jnp.float32,
                         kv_page_size=32, kv_num_pages=32)
    model = CausalLM(dataclasses.replace(cfg, kv_num_pages=None))
    params = nn.meta.unbox(jax.jit(model.init)(
        make_rng(0), jnp.zeros((1, 8), jnp.int32))["params"])
    bundle = os.path.join(tmp, "bundle")
    export_serving_bundle(cfg, params, bundle, quantize=False)
    # the draft bundle: same weights on the DENSE config — a real
    # second bundle on disk, so the --draft-bundle load/vocab-check
    # path runs; sharing the target's weights pins acceptance high
    draft_dir = os.path.join(tmp, "draft")
    export_serving_bundle(dataclasses.replace(cfg, kv_num_pages=None),
                          params, draft_dir, quantize=False)
    prompts = ["the quick brown fox jumps over ",
               "serving plane speculative check "]

    def serve_once(spec_tokens: int, want_accept: bool):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        url = f"http://127.0.0.1:{port}"
        argv = [sys.executable, "-m", "pyspark_tf_gke_tpu.train.serve",
                "--bundle", bundle, "--host", "127.0.0.1",
                "--port", str(port), "--continuous-slots", "2",
                "--continuous-chunk", "4"]
        if spec_tokens:
            argv += ["--spec-tokens", str(spec_tokens),
                     "--draft-bundle", draft_dir]
        proc = subprocess.Popen(
            argv, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        try:
            deadline = _time.time() + 180
            while _time.time() < deadline:
                try:
                    urllib.request.urlopen(url + "/healthz", timeout=2)
                    break
                except Exception:  # noqa: BLE001 — still booting
                    if proc.poll() is not None:
                        raise RuntimeError(
                            f"server died during startup "
                            f"(rc={proc.poll()})")
                    _time.sleep(0.5)
            else:
                raise RuntimeError("server never became healthy")
            req = urllib.request.Request(
                url + "/v1/generate",
                data=_json.dumps({"prompts": prompts,
                                  "max_new_tokens": 24}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=180) as resp:
                out = _json.loads(resp.read())
            texts = [c["completion"] for c in out["completions"]]
            accept = None
            if want_accept:
                with urllib.request.urlopen(url + "/loadz",
                                            timeout=10) as resp:
                    accept = _json.loads(resp.read())["spec_accept_rate"]
            return texts, accept
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)

    failures = []
    spec_texts, accept = serve_once(3, want_accept=True)
    plain_texts, _ = serve_once(0, want_accept=False)
    print(f"spec-serve: accept_rate={accept} "
          f"parity={'OK' if spec_texts == plain_texts else 'MISMATCH'}")
    if spec_texts != plain_texts:
        failures.append(
            f"speculative completions diverged from --spec-tokens 0: "
            f"{spec_texts!r} != {plain_texts!r}")
    if not accept or accept <= 0:
        failures.append(
            f"/loadz spec_accept_rate={accept!r} — speculation never "
            "accepted a draft (or the signal is dead)")
    if failures:
        print("spec-serve FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("spec-serve OK: --spec-tokens engine is token-identical to "
          "the plain engine over live HTTP, with a nonzero accept rate "
          "on /loadz")
    return 0


def router_check(grace_s: float = 30.0, n_requests: int = 10) -> int:
    """``--router``: the kill-one-replica failover contract as a
    subprocess check. 2 tiny CPU replicas + the router (all
    subprocesses, the real CLIs), concurrent generates, SIGKILL one
    replica mid-run:

    1. every request reaches a terminal outcome (no hangs),
    2. ZERO requests are lost — the failover/hedge path absorbs the
       kill (two idle replicas can carry this load),
    3. SIGTERM drains the router and it exits 0.

    The in-process fast variants live in tests/test_router.py. Launch
    scaffolding is shared with them via ``router/localfleet.py``."""
    import signal
    import subprocess
    import tempfile
    import threading
    import time as _time

    from pyspark_tf_gke_tpu.router.localfleet import (
        export_tiny_bundle,
        free_port,
        launch_replica,
        launch_router,
        post_generate,
        wait_healthy,
    )

    tmp = tempfile.mkdtemp(prefix="router-smoke-")
    bundle = export_tiny_bundle(os.path.join(tmp, "bundle"))

    ports = [free_port(), free_port()]
    router_port = free_port()
    # not quiet: replica/router logs belong in the smoke transcript
    replicas = [launch_replica(bundle, p, quiet=False) for p in ports]
    router_proc = None
    failures = []
    try:
        deadline = _time.time() + 180
        for p, proc in zip(ports, replicas):
            try:
                wait_healthy(f"http://127.0.0.1:{p}", deadline,
                             proc=proc)
            except RuntimeError as exc:
                print(str(exc))
                return 1
        router_proc = launch_router(
            ports, router_port, quiet=False,
            extra_args=("--hedge-max-ms", "500", "--drain-timeout", "1"))
        url = f"http://127.0.0.1:{router_port}"
        try:
            wait_healthy(url, deadline, proc=router_proc)
        except RuntimeError as exc:
            print(str(exc))
            return 1

        def post(prompt, timeout=120.0, base=None):
            return post_generate(base or url, prompt,
                                 max_new_tokens=6, timeout_s=timeout)

        # warm each replica DIRECTLY — routed warms can hash onto the
        # same replica, leaving the other to pay first-request JIT
        # compile mid-run (slower smoke, muddier timings)
        for p in ports:
            post("warm a", base=f"http://127.0.0.1:{p}")
            post("warm b", base=f"http://127.0.0.1:{p}")

        done, errors = [], []

        def one(i):
            try:
                out = post(f"req {i}")
                done.append(out["completions"][0]["new_tokens"])
            except Exception as exc:  # noqa: BLE001 — judged below
                errors.append((i, repr(exc)))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_requests)]
        for i, t in enumerate(threads):
            t.start()
            if i == n_requests // 3:
                replicas[0].send_signal(signal.SIGKILL)  # mid-traffic
            _time.sleep(0.05)
        for t in threads:
            t.join(timeout=grace_s * 4)
        hung = sum(t.is_alive() for t in threads)
        if hung:
            failures.append(f"{hung} request(s) never reached a "
                            "terminal outcome")
        if errors:
            failures.append(
                f"{len(errors)} request(s) lost to the kill (want 0 — "
                f"failover should absorb it): {errors[:3]}")
        router_proc.send_signal(signal.SIGTERM)
        try:
            rc = router_proc.wait(timeout=grace_s)
            if rc != 0:
                failures.append(f"router exited {rc}, want 0")
        except subprocess.TimeoutExpired:
            failures.append(f"router still alive {grace_s}s after "
                            "SIGTERM")
    finally:
        for p in [router_proc, *replicas]:
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    if failures:
        print("router smoke FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print(f"router smoke OK: {len(done)}/{n_requests} requests "
          "terminal with one replica SIGKILLed mid-run; router "
          "drained and exited 0")
    return 0


def fairness_check(grace_s: float = 30.0) -> int:
    """``--fairness``: the multi-tenant overload-isolation contract
    through a LIVE CPU server (the real CLI with a ``--tenants`` spec).
    Three greedy "noisy"-tenant threads flood the replica while the
    "light" tenant runs serial requests:

    1. the light tenant completes EVERY request (goodput 1.0 — DWRR
       admission + its private queue share keep it admitting),
    2. its p99 stays within a bounded factor of its isolated-run p99
       (the flood cannot starve it, only share slots with it),
    3. the noisy tenant's sheds are all PER-TENANT 429s
       (tenant_quota / tenant_queue_full + X-Tenant-Shed) — the
       global queue never rejects anyone,
    4. zero lost requests: every outcome is a 200 or an explicit shed,
    5. ``/loadz`` exports the per-tenant queue map + capacity_free
       (the router's autoscale signal is fed by real state)."""
    import json as _json
    import subprocess
    import tempfile
    import urllib.request

    from pyspark_tf_gke_tpu.router.localfleet import (
        export_tiny_bundle,
        free_port,
        launch_replica,
        percentile,
        post_tenant,
        run_noisy_neighbor,
        wait_healthy,
    )

    tmp = tempfile.mkdtemp(prefix="fairness-smoke-")
    bundle = export_tiny_bundle(os.path.join(tmp, "bundle"))
    port = free_port()
    url = f"http://127.0.0.1:{port}"
    proc = launch_replica(
        bundle, port, quiet=False,
        extra_args=("--tenants", "light=3,noisy=1:60:120",
                    "--max-queue-depth", "6"))
    failures = []
    try:
        import time as _time
        wait_healthy(url, _time.time() + 180, proc=proc)
        # warm the compiled shapes so the isolated baseline is steady
        for t in ("light", "noisy"):
            post_tenant(url, "warm", t, max_new_tokens=6)
        iso = []
        for i in range(4):
            status, _body, dt = post_tenant(url, f"iso {i}", "light",
                                            max_new_tokens=6)
            if status == 200:
                iso.append(dt)
        p99_iso = percentile(iso, 0.99)
        out = run_noisy_neighbor(url, light_requests=8, light_budget=6,
                                 flood_threads=3, flood_budget=12)
        p99_flood = percentile(out["light"]["lat_ms"], 0.99)
        bound = max(25.0 * max(p99_iso, 250.0), 5000.0)
        print(f"fairness: light {out['light']['ok']}/8 ok, p99 "
              f"{p99_flood:.0f}ms flooded vs {p99_iso:.0f}ms isolated "
              f"(bound {bound:.0f}ms); noisy ok={out['noisy']['ok']} "
              f"tenant_429={out['noisy']['tenant_429']} "
              f"other_429={out['noisy']['other_429']} "
              f"errors={len(out['noisy']['errors'])} over "
              f"{out['noisy_attempts']} attempts")
        if out["light"]["errors"] or out["light"]["ok"] != 8:
            failures.append(
                f"light tenant lost requests: {out['light']['errors']}")
        if p99_flood > bound:
            failures.append(
                f"light p99 {p99_flood:.0f}ms blew the bounded factor "
                f"({bound:.0f}ms) — the flood starved it")
        if out["noisy"]["tenant_429"] < 1:
            failures.append(
                "the flood never drew a per-tenant 429 — quotas/shares "
                "are not engaging")
        if out["noisy"]["other_429"]:
            failures.append(
                f"{out['noisy']['other_429']} GLOBAL 429(s) fired — "
                "shedding must be per-tenant under a tenants spec")
        if out["noisy"]["errors"]:
            failures.append(
                f"noisy tenant hit non-shed errors: "
                f"{out['noisy']['errors'][:3]}")
        with urllib.request.urlopen(url + "/loadz", timeout=10) as resp:
            loadz = _json.loads(resp.read())
        if "capacity_free" not in loadz or "tenants" not in loadz:
            failures.append(f"/loadz missing tenancy keys: "
                            f"{sorted(loadz)}")
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    if failures:
        print("fairness FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("fairness OK: light tenant kept goodput 1.0 with bounded p99 "
          "under a 3-thread flood; every shed was a per-tenant 429")
    return 0


def pipeline_check(grace_s: float = 90.0) -> int:
    """``--pipeline``: the continuous ETL→train→publish loop end to end
    on a CPU box (docs/PIPELINE.md), with the hot-swap exercised the
    way production will hit it — MID-STREAM:

    1. round 1 (in-process coordinator): ingest synthetic rows → native
       TFRecord shards + manifest generation 1 → train a few steps →
       export bundle generation 1 (no replicas yet);
    2. a BundleServer subprocess serves generation 1 (admin token set);
    3. round 2 runs with the replica configured; its publish stage
       first opens a generate STREAM against the replica and waits for
       the first token event, then fires the rolling publish — the
       swap lands with the stream in flight;
    4. require: the stream reaches an explicit terminal ([DONE], with
       either its full completion or a typed error event — never a
       hang or silent cut), /loadz advertises bundle_generation 2, a
       post-swap generate serves, and pipeline_freshness_seconds was
       recorded;
    5. a corrupt-bundle publish must FAIL the rollout while the
       replica keeps serving generation 2 (rollback contract);
    6. SIGTERM → the server drains and exits 0.
    """
    import dataclasses
    import json as _json
    import signal
    import socket
    import subprocess
    import tempfile
    import threading
    import time as _time
    import urllib.error
    import urllib.request

    from pyspark_tf_gke_tpu.obs.metrics import platform_families
    from pyspark_tf_gke_tpu.pipeline import (
        LocalPipelineConfig,
        PipelineCoordinator,
        make_local_stages,
        rolling_publish,
    )

    tmp = tempfile.mkdtemp(prefix="pipeline-smoke-")
    token = "smoke-token"
    cfg = LocalPipelineConfig(
        work_dir=tmp, rows_per_round=96, seq_len=64, num_shards=2,
        steps_per_round=3, batch_size=4, hidden_size=32, num_layers=2,
        num_heads=2, intermediate_size=64)
    state_path = os.path.join(tmp, "state.json")
    failures = []

    print("pipeline round 1: ingest -> train -> export ...")
    PipelineCoordinator(make_local_stages(cfg), state_path=state_path,
                        rounds=1).run()
    bundle1 = cfg.bundle_dir(1)
    if not os.path.exists(os.path.join(bundle1, "config.json")):
        print(f"round 1 produced no bundle at {bundle1}")
        return 1

    with socket.socket() as s:  # free port; tiny reuse race is fine here
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    env = dict(os.environ, JAX_PLATFORMS="cpu", SERVE_ADMIN_TOKEN=token)
    proc = subprocess.Popen(
        [sys.executable, "-m", "pyspark_tf_gke_tpu.train.serve",
         "--bundle", bundle1, "--host", "127.0.0.1", "--port", str(port),
         "--continuous-slots", "2", "--continuous-chunk", "2",
         "--drain-timeout", "30"],
        env=env)

    def get(path: str) -> dict:
        with urllib.request.urlopen(url + path, timeout=5) as resp:
            return _json.loads(resp.read())

    def post(payload: dict, timeout: float = 120.0) -> dict:
        req = urllib.request.Request(
            url + "/v1/generate", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return _json.loads(resp.read())

    stream_out: dict = {"events": []}
    first_event = threading.Event()

    def stream():
        """One SSE generate held open across the swap; every line
        recorded so the terminal contract is checkable."""
        req = urllib.request.Request(
            url + "/v1/generate",
            data=_json.dumps({"prompt": "pipeline smoke ",
                              "max_new_tokens": 40,
                              "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                for raw in resp:
                    line = raw.strip()
                    if line.startswith(b"data: "):
                        stream_out["events"].append(
                            line[len(b"data: "):].decode())
                        first_event.set()
        except Exception as exc:  # noqa: BLE001 — checked below
            stream_out["error"] = repr(exc)
        finally:
            first_event.set()

    try:
        deadline = _time.time() + 180
        while _time.time() < deadline:
            try:
                if get("/loadz").get("bundle_generation") == 1:
                    break
            except Exception:  # noqa: BLE001 — still booting
                if proc.poll() is not None:
                    print(f"server died during startup (rc={proc.poll()})")
                    return 1
            _time.sleep(0.5)
        else:
            print("server never became healthy")
            return 1
        post({"prompts": ["warm"], "max_new_tokens": 2})  # compile now

        # round 2: same coordinator state, replica configured — but the
        # publish stage opens the stream FIRST so the swap is provably
        # mid-flight
        cfg2 = dataclasses.replace(cfg, replicas=(url,),
                                   admin_token=token)
        stages = make_local_stages(cfg2)
        real_publish = stages["publish"]

        def publish_with_stream_in_flight(state, outputs):
            t = threading.Thread(target=stream, name="smoke-stream")
            t.start()
            if not first_event.wait(30):
                raise RuntimeError("stream never delivered its first "
                                   "event before the publish")
            out = real_publish(state, outputs)
            out["stream_thread_started"] = True
            return out

        stages["publish"] = publish_with_stream_in_flight
        print("pipeline round 2: ingest -> train -> export -> publish "
              "(hot-swap mid-stream) ...")
        PipelineCoordinator(stages, state_path=state_path, rounds=2).run()

        t = [x for x in threading.enumerate()
             if x.name == "smoke-stream"]
        if t:
            t[0].join(timeout=grace_s)
            if t[0].is_alive():
                failures.append("in-flight stream HUNG through the swap")
        events = stream_out["events"]
        if "error" in stream_out:
            failures.append(f"stream transport error: {stream_out['error']}")
        elif not events or events[-1] != "[DONE]":
            failures.append(f"stream lacks a [DONE] terminal: {events[-2:]}")
        else:
            # explicit outcome: either the assembled completion ("done")
            # or a typed error event — silence is the only failure
            bodies = [_json.loads(e) for e in events[:-1] if e != "[DONE]"]
            if not any(b.get("done") or b.get("error") for b in bodies):
                failures.append(
                    f"stream ended without an explicit outcome event "
                    f"({len(bodies)} events)")

        load = get("/loadz")
        if load.get("bundle_generation") != 2:
            failures.append(f"post-publish bundle_generation "
                            f"{load.get('bundle_generation')}, want 2")
        out = post({"prompts": ["after swap"], "max_new_tokens": 4})
        if "completions" not in out:
            failures.append(f"post-swap generate failed: {out}")
        fresh = platform_families()["pipeline_freshness_seconds"].value
        if not fresh > 0:
            failures.append(f"pipeline_freshness_seconds not recorded "
                            f"({fresh})")

        # rollback: a corrupt bundle publish must leave gen 2 serving
        bad = os.path.join(tmp, "corrupt-bundle")
        os.makedirs(bad, exist_ok=True)
        with open(os.path.join(bad, "config.json"), "w") as fh:
            fh.write("{this is not json")
        report = rolling_publish([url], bad, 3, token=token)
        if report["ok"] or report["published"]:
            failures.append(f"corrupt publish REPORTED success: {report}")
        load = get("/loadz")
        if load.get("bundle_generation") != 2:
            failures.append(
                f"corrupt publish moved bundle_generation to "
                f"{load.get('bundle_generation')} (want 2 still serving)")
        out = post({"prompts": ["still serving"], "max_new_tokens": 4})
        if "completions" not in out:
            failures.append(f"generate after corrupt publish failed: {out}")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=grace_s)
            if rc != 0:
                failures.append(f"server exited {rc} after SIGTERM, want 0")
        except subprocess.TimeoutExpired:
            failures.append(f"server still alive {grace_s}s after SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
    if failures:
        print("pipeline FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("pipeline OK: 2 rounds ingest->train->export->publish; "
          "hot-swap landed mid-stream with an explicit stream terminal; "
          "generation 2 serving; corrupt publish rolled off with the old "
          "generation intact; server drained 0")
    return 0


def trace_check(grace_s: float = 30.0) -> int:
    """``--trace``: the end-to-end tracing contract, live.

    1 CPU replica (chunked prefill on, trace sample 1.0, a metered
    tenant) behind the real router CLI (trace sample 1.0):

    1. a generate with an INJECTED ``traceparent`` routed through the
       router echoes the injected trace id back as ``X-Request-Id``,
       and ``GET /traces?trace_id=`` on BOTH processes returns spans
       under that same id — the cross-process join works on real wire
       bytes;
    2. the serve-side span's timeline carries the full slot lifecycle:
       queue-wait, admission, prefill-chunk (the prompt is longer than
       the chunk), first-token (TTFT), and terminal events;
    3. a per-tenant quota shed (429) still echoes its trace id and its
       trace records the shed verdict — the 429 a user reports is one
       /traces lookup from its reason;
    4. one in-process pipeline round's trace id is recoverable from
       the published bundle's meta — serving-generation → producing-
       round lineage."""
    import json as _json
    import tempfile
    import urllib.error
    import urllib.request

    from pyspark_tf_gke_tpu.obs.trace import (
        format_traceparent,
        new_span_id,
        new_trace_id,
    )
    from pyspark_tf_gke_tpu.router.localfleet import (
        export_tiny_bundle,
        free_port,
        launch_replica,
        launch_router,
        wait_healthy,
    )

    tmp = tempfile.mkdtemp(prefix="trace-smoke-")
    bundle = export_tiny_bundle(os.path.join(tmp, "bundle"))
    port, router_port = free_port(), free_port()
    replica_url = f"http://127.0.0.1:{port}"
    router_url = f"http://127.0.0.1:{router_port}"
    proc = launch_replica(
        bundle, port, quiet=False,
        extra_args=("--trace-sample", "1.0", "--trace-slow-ms", "0",
                    "--prefill-chunk", "32",
                    "--tenants", "smoke=1:0.5:40"))
    router_proc = None
    failures = []

    def post(base, payload, headers=None, timeout=120.0):
        """POST /v1/generate -> (status, body, response headers) —
        HTTP error verdicts are data here, not exceptions."""
        req = urllib.request.Request(
            base + "/v1/generate", data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json",
                     **(headers or {})})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, _json.loads(resp.read()), resp.headers
        except urllib.error.HTTPError as exc:
            try:
                body = _json.loads(exc.read() or b"{}")
            except ValueError:
                body = {}
            return exc.code, body, exc.headers

    def get(base, path):
        with urllib.request.urlopen(base + path, timeout=10) as resp:
            return _json.loads(resp.read())

    try:
        import time as _time

        deadline = _time.time() + 180
        wait_healthy(replica_url, deadline, proc=proc)
        router_proc = launch_router(
            [port], router_port, quiet=False,
            extra_args=("--trace-sample", "1.0", "--trace-slow-ms", "0",
                        "--no-hedge", "--drain-timeout", "1"))
        wait_healthy(router_url, deadline, proc=router_proc)
        # warm/compile on an unmetered tenant so the traced request's
        # timing (and the smoke tenant's token bucket) stay clean
        post(router_url, {"prompts": ["warm the compiled shapes"],
                          "max_new_tokens": 4})

        # -- 1+2: injected traceparent, one id across both processes --
        trace_id = new_trace_id()
        parent = format_traceparent(trace_id, new_span_id(), sampled=True)
        # > --prefill-chunk bytes (byte tokenizer), so the admission
        # takes the chunked route and the timeline gets its
        # prefill_chunk events; prompt + budget stays under max_seq_len
        prompt = "trace this request through every hop it takes"
        status, body, hdrs = post(
            router_url, {"prompts": [prompt], "max_new_tokens": 8},
            headers={"traceparent": parent})
        if status != 200 or "completions" not in body:
            failures.append(f"routed traced generate failed: {status} "
                            f"{str(body)[:200]}")
        if hdrs.get("X-Request-Id") != trace_id:
            failures.append(
                f"X-Request-Id {hdrs.get('X-Request-Id')} != injected "
                f"trace id {trace_id}")
        found_events = []
        for name, base in (("router", router_url),
                           ("serve", replica_url)):
            out = get(base, f"/traces?trace_id={trace_id}")
            spans = [s for t in out.get("traces", ())
                     for s in t["spans"]]
            if not spans:
                failures.append(
                    f"{name} /traces has NO spans under the injected "
                    f"trace id (got {len(out.get('traces', ()))} traces)")
                continue
            if name == "serve":
                found_events = sorted({e["name"] for s in spans
                                       for e in s["events"]})
        wanted = {"queue_wait", "admission", "prefill_chunk",
                  "first_token", "terminal"}
        missing = wanted - set(found_events)
        if missing:
            failures.append(
                f"serve-side timeline is missing {sorted(missing)} "
                f"(has {found_events})")
        print(f"trace: id {trace_id[:16]}… spans on router AND serve; "
              f"serve events: {found_events}")

        # -- 3: a per-tenant shed still traces + echoes the id --------
        shed_headers = {"X-Tenant": "smoke"}
        post(router_url, {"prompts": ["quota setup abcdef"],
                          "max_new_tokens": 16}, headers=shed_headers)
        status, body, hdrs = post(
            router_url, {"prompts": ["quota breaker abcde"],
                         "max_new_tokens": 16}, headers=shed_headers)
        shed_trace = hdrs.get("X-Request-Id")
        if status != 429:
            failures.append(f"quota shed expected 429, got {status} "
                            f"{str(body)[:200]}")
        elif not shed_trace:
            failures.append("429 shed carried no X-Request-Id")
        else:
            out = get(replica_url, f"/traces?trace_id={shed_trace}")
            events = {e["name"] for t in out.get("traces", ())
                      for s in t["spans"] for e in s["events"]}
            if "shed" not in events:
                failures.append(
                    f"shed trace {shed_trace[:16]}… lacks the shed "
                    f"verdict event (has {sorted(events)})")
            else:
                print(f"trace: 429 shed traced as {shed_trace[:16]}… "
                      "with its shed verdict")

        # -- 4: pipeline round trace id lands in the bundle meta ------
        from pyspark_tf_gke_tpu.pipeline import (
            LocalPipelineConfig,
            PipelineCoordinator,
            make_local_stages,
        )

        cfg = LocalPipelineConfig(
            work_dir=os.path.join(tmp, "pipe"), rows_per_round=64,
            seq_len=64, num_shards=2, steps_per_round=2, batch_size=4,
            hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64)
        coord = PipelineCoordinator(
            make_local_stages(cfg),
            state_path=os.path.join(tmp, "pipe", "state.json"), rounds=1)
        coord.run()
        with open(os.path.join(cfg.bundle_dir(1), "config.json")) as fh:
            meta = _json.load(fh)
        round_trace = meta.get("trace_id")
        ring_ids = {t["trace_id"] for t in coord.tracer.traces()}
        if not round_trace:
            failures.append(f"bundle meta carries no trace_id: "
                            f"{sorted(meta)}")
        elif round_trace not in ring_ids:
            failures.append(
                f"bundle trace_id {round_trace[:16]}… not in the "
                "coordinator's flight recorder")
        else:
            print(f"trace: pipeline round trace {round_trace[:16]}… "
                  "recoverable from the published bundle meta")
    finally:
        for p in (router_proc, proc):
            if p is not None and p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=grace_s)
                except Exception:  # noqa: BLE001
                    p.kill()
                    p.wait(timeout=10)
    if failures:
        print("trace FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("trace OK: one trace id spans router and serve, the serve "
          "timeline carries the full slot lifecycle, sheds trace too, "
          "and the pipeline round's trace id rides the bundle meta")
    return 0


def stepstats_check(grace_s: float = 30.0) -> int:
    """``--stepstats``: the step-telemetry contract, live. One CPU
    replica (continuous slots, admin token deliberately UNSET) under a
    small request burst:

    1. ``GET /stepz`` serves a non-empty ring; every record's phase
       sums reconcile with its wall (exclusive attribution: sums never
       exceed wall + epsilon, and the timed phases cover most of it),
       the busy records carry batch composition, and the served steps
       carry the ``deliver`` phase the driver loop amends on;
    2. the ``serve_step_host_overhead_ms`` histogram is populated and
       ``serve_device_idle_fraction`` is exported (``/metrics.json``);
       the async-core overlap is LIVE — the interval-derived idle
       fraction runs strictly below the same window's legacy
       host-work share (``host_work_frac``), which is what a serial
       loop would have reported on this box;
    3. ``/loadz`` advertises ``step_host_overhead_frac`` in [0, 1] —
       the value the router's autoscale block folds in;
    4. ``POST /admin/profile`` on a token-unconfigured server returns
       403 (the endpoint operationally does not exist — the same
       discipline as ``/admin/reload``)."""
    import json as _json
    import tempfile
    import urllib.error
    import urllib.request

    from pyspark_tf_gke_tpu.router.localfleet import (
        export_tiny_bundle,
        free_port,
        launch_replica,
        wait_healthy,
    )

    tmp = tempfile.mkdtemp(prefix="stepstats-smoke-")
    bundle = export_tiny_bundle(os.path.join(tmp, "bundle"))
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    # the 403-unconfigured leg is only meaningful if the replica
    # really has no token: launch_replica inherits our env, so make
    # sure a dev shell's token doesn't leak in
    os.environ.pop("SERVE_ADMIN_TOKEN", None)
    proc = launch_replica(bundle, port, quiet=False)
    failures = []

    def get(path: str) -> dict:
        with urllib.request.urlopen(base + path, timeout=10) as resp:
            return _json.loads(resp.read())

    def post(path: str, payload: dict, timeout: float = 120.0):
        req = urllib.request.Request(
            base + path, data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, _json.loads(resp.read())
        except urllib.error.HTTPError as exc:
            try:
                body = _json.loads(exc.read() or b"{}")
            except ValueError:
                body = {}
            return exc.code, body

    try:
        import time as _time

        deadline = _time.time() + 180
        wait_healthy(base, deadline, proc=proc)
        # a small burst (the first request also pays compilation):
        # enough steps that the ring, the histogram and the windowed
        # fraction are all non-vacuously populated
        for i in range(4):
            status, body = post("/v1/generate",
                                {"prompts": [f"step telemetry {i}"],
                                 "max_new_tokens": 8})
            if status != 200 or "completions" not in body:
                failures.append(f"generate {i} failed: {status} "
                                f"{str(body)[:200]}")

        # -- 1: /stepz ring + phase-sum reconciliation ---------------
        out = get("/stepz?n=64")
        steps = out.get("steps") or []
        summary = out.get("summary") or {}
        if not steps:
            failures.append("/stepz ring is EMPTY after the burst")
        bad = []
        for s in steps:
            phase_sum = sum(s["phases_ms"].values())
            # exclusive attribution: sums can't exceed wall (epsilon
            # for float rounding); the timed phases must also cover
            # the bulk of the step (generous floor — a shared CI core
            # can stall between contexts)
            if phase_sum > s["wall_ms"] + 0.5 or (
                    s["wall_ms"] > 1.0
                    and phase_sum < 0.5 * s["wall_ms"]):
                bad.append(f"seq {s['seq']}: phases {phase_sum:.3f}ms "
                           f"vs wall {s['wall_ms']:.3f}ms")
        if bad:
            failures.append("phase sums do not reconcile with step "
                            f"wall: {bad[:4]}")
        if steps and not any(s["tokens_out"] for s in steps):
            failures.append("no step record carries tokens_out despite "
                            "completed generates")
        if steps and not any("deliver" in s["phases_ms"] for s in steps):
            failures.append("no served step carries the deliver phase "
                            "(driver-loop amend broken)")
        if not (0.0 <= summary.get("host_overhead_frac", -1.0) <= 1.0):
            failures.append(f"/stepz summary host_overhead_frac out of "
                            f"range: {summary.get('host_overhead_frac')}")
        # overlap is LIVE: the replica's default engine is pipelined
        # (--continuous-pipeline 1), so the interval-derived idle
        # fraction must run strictly below the SAME window's legacy
        # host-work share (on a serial loop the two coincide — see
        # obs/stepstats.py's measurement model). Same box, same
        # process, same steps: the serial-baseline comparison with no
        # second server. Equality means the engine never fed
        # dispatch/retire intervals (derivation fell back) or the
        # pipeline never actually overlapped host work with compute.
        idle = summary.get("host_overhead_frac")
        work = summary.get("host_work_frac")
        if not isinstance(work, (int, float)):
            failures.append("/stepz summary lacks host_work_frac (the "
                            "legacy serial-formula share)")
        elif not (isinstance(idle, (int, float)) and idle < work):
            failures.append(
                f"pipeline overlap not measurable: interval-derived "
                f"idle {idle!r} is not strictly below the legacy "
                f"host-work share {work!r}")
        if not failures:
            print(f"stepstats: /stepz {len(steps)} record(s), "
                  f"host_overhead_frac "
                  f"{summary.get('host_overhead_frac')} < "
                  f"host_work_frac {work} (overlap live), phase sums "
                  "reconcile")

        # -- 2: the derived metric families are live -----------------
        metrics = get("/metrics.json")
        hist = metrics.get("serve_step_host_overhead_ms") or {}
        if not hist.get("count"):
            failures.append("serve_step_host_overhead_ms histogram is "
                            "empty after the burst")
        if "serve_device_idle_fraction" not in metrics:
            failures.append("serve_device_idle_fraction gauge missing "
                            "from /metrics.json")
        phases = metrics.get("serve_step_phase_ms") or {}
        if not any(v.get("count") for v in phases.values()
                   if isinstance(v, dict)):
            failures.append("serve_step_phase_ms has no populated "
                            "phase series")

        # -- 3: /loadz advertises the autoscale-facing fraction ------
        loadz = get("/loadz")
        frac = loadz.get("step_host_overhead_frac")
        if not (isinstance(frac, (int, float))
                and 0.0 <= frac <= 1.0):
            failures.append(f"/loadz step_host_overhead_frac bad: "
                            f"{frac!r}")
        else:
            print(f"stepstats: /loadz step_host_overhead_frac {frac}")

        # -- 4: /admin/profile 403 on an unconfigured server ---------
        status, body = post("/admin/profile", {"steps": 2})
        if status != 403:
            failures.append(f"/admin/profile without SERVE_ADMIN_TOKEN "
                            f"expected 403, got {status} "
                            f"{str(body)[:200]}")
        else:
            print("stepstats: /admin/profile 403 on the unconfigured "
                  "server")
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=grace_s)
            except Exception:  # noqa: BLE001
                proc.kill()
                proc.wait(timeout=10)
    if failures:
        print("stepstats FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("stepstats OK: /stepz reconciles, the host-overhead "
          "histogram and /loadz fraction are live, and the profile "
          "endpoint honors the admin-token gate")
    return 0


def replay_check(grace_s: float = 30.0) -> int:
    """``--replay``: the trace-replay + capacity-planning contract,
    live. A tiny synthetic flash-crowd spec replayed open-loop against
    a 2-replica CPU localfleet (1 slot each, bounded queue) behind the
    real router must reach a terminal outcome for EVERY request, its
    SLO report must evaluate and JSON-round-trip, the offline capacity
    model's prediction (on rates calibrated against the same fleet)
    must agree with the measured replay within the documented band
    (docs/REPLAY.md), and a live ``/traces?format=jsonl`` export must
    round-trip through spec extraction into a replayable spec."""
    import json

    from pyspark_tf_gke_tpu.replay.capacity import (
        FleetModel,
        calibrate_rates,
        check_agreement,
        predict,
    )
    from pyspark_tf_gke_tpu.replay.driver import replay_spec
    from pyspark_tf_gke_tpu.replay.extract import (
        parse_traces,
        spec_from_traces,
    )
    from pyspark_tf_gke_tpu.replay.generators import synth_spec
    from pyspark_tf_gke_tpu.replay.slo import evaluate_slo
    from pyspark_tf_gke_tpu.router.localfleet import LocalFleet
    import urllib.request

    from pyspark_tf_gke_tpu.replay.spec import SpecRequest, WorkloadSpec

    trace_args = ("--trace-sample", "1.0", "--trace-slow-ms", "0")
    # the routed scenario: steady base + a flash-crowd burst through
    # the real router (SLO-scored; the router's storm verdicts are
    # legitimate sheds)
    spec = synth_spec("flash_crowd", seed=5, duration_s=4.0,
                      rate_rps=1.5, prompt_tokens=20, output_tokens=16,
                      max_seq_len=64, burst_mult=16.0, burst_frac=0.25)
    # the capacity-check spec: an instantaneous WALL of simultaneous
    # arrivals replayed DIRECTLY against one replica — the model's
    # contract is the replica's /loadz admission math, which is
    # deterministic arithmetic (1 slot + 4 queue admit, the rest shed
    # queue_full); the router's Retry-After backoff amplifier under
    # simultaneous arrival is a thread race the model reproduces only
    # in expectation, so the ASSERTED band runs without it
    wall = WorkloadSpec("flash_crowd_wall", requests=[
        SpecRequest(offset_s=0.0, prompt_tokens=20, output_tokens=16)
        for _ in range(12)]).validate()
    print(f"replay check: flash-crowd spec with {len(spec.requests)} "
          "requests vs 2-replica CPU localfleet + a 12-wall capacity "
          "check vs one replica...")
    with LocalFleet(2, router_args=trace_args,
                    replica_args=(*trace_args, "--continuous-slots",
                                  "1", "--max-queue-depth",
                                  "4")) as fleet:
        fleet.warm()
        # burst-level concurrency + throughput read (see
        # calibrate_rates): the model's decode rate must be the rate
        # a replica sustains DURING the crowd, every host cost folded
        calibration = calibrate_rates(fleet.replica_urls[0],
                                      prompt_tokens=20,
                                      output_tokens=16, concurrency=4,
                                      total_slots=1)
        print(f"calibrated: prefill "
              f"{calibration['prefill_tokens_per_sec']} tok/s, decode "
              f"{calibration['decode_tokens_per_sec']} tok/s/slot")
        report = replay_spec(spec, fleet.url, speedup=2.0)

        # 1) every request terminal
        total = sum(report["outcomes"].values())
        assert total == len(spec.requests), (
            f"{len(spec.requests) - total} request(s) never reached a "
            f"terminal outcome: {report['outcomes']}")
        assert report["outcomes"]["error"] == 0, (
            f"replay saw transport/engine errors: {report['sheds']} "
            f"{report['outcomes']}")

        # 2) the SLO report parses + evaluates (machine-readable)
        verdict = evaluate_slo(report, {
            "errors_max": 0,
            "shed_reasons_allowed": ["queue_full", "no_reroute_target",
                                     "no_replicas"]})
        verdict = json.loads(json.dumps(verdict))
        assert isinstance(verdict["pass"], bool) and verdict["checks"]
        assert verdict["pass"], f"SLO failed: {verdict['checks']}"

        # 3) prediction-vs-replay band (docs/REPLAY.md: p99 within
        #    5x either way, sheds within max(5, 50%)) on the wall,
        #    direct to one replica — after the WHOLE fleet reports
        #    idle: a replica still grinding the routed crowd's tail
        #    steals the shared core, spreading the wall's submits and
        #    inflating its service times
        fleet.wait_idle()
        wall_report = replay_spec(wall, fleet.replica_urls[1],
                                  speedup=1.0)
        model = FleetModel(
            replicas=1, slots_per_replica=1, max_queue_depth=4,
            prefill_tokens_per_sec=calibration[
                "prefill_tokens_per_sec"],
            decode_tokens_per_sec=calibration[
                "decode_tokens_per_sec"])
        agreement = check_agreement(
            predict(model, wall), wall_report,
            p99_band=5.0, shed_band_abs=5, shed_band_rel=0.5)
        assert agreement["ok"], (
            f"prediction-vs-replay band broken: {agreement['checks']}")
        print(f"wall: measured {wall_report['outcomes']} "
              f"{wall_report['sheds']}")
        print(f"agreement: {agreement['checks']}")

        # 4) /traces jsonl export -> replayable spec
        with urllib.request.urlopen(
                fleet.replica_urls[0] + "/traces?format=jsonl&n=512",
                timeout=30) as resp:
            traces = parse_traces(resp.read())
        respec = spec_from_traces(traces, name="rt")
        assert respec.requests, "no requests extracted from /traces"
        respec.validate()
    print(f"replay OK: {total} requests terminal "
          f"({report['outcomes']}), SLO report machine-readable, "
          f"prediction within band, {len(respec.requests)} requests "
          "extracted from /traces into a replayable spec")
    return 0


def chaos_check(grace_s: float = 30.0) -> int:
    """``--chaos``: the chaos plane's durability contract, live. A tiny
    flash-crowd replay runs against a 2-replica CPU localfleet behind
    the real router while a chaos schedule SIGKILLs one replica
    mid-crowd and restarts it; afterwards EVERY request must have
    reached exactly one terminal outcome (the exactly-one-terminal
    invariant, client-side), the surviving/restarted replicas must
    pass the baseline invariant check (zero stuck slots, pool at
    baseline, no wedged admission), the router must be back to two
    routable replicas, and goodput must have RECOVERED in the
    post-restart window."""
    import json
    import time
    import urllib.request

    from pyspark_tf_gke_tpu.chaos.invariants import (
        check_replica,
        check_report,
        goodput_windows,
    )
    from pyspark_tf_gke_tpu.chaos.runner import ScheduleRunner
    from pyspark_tf_gke_tpu.chaos.spec import ChaosEvent, ChaosSchedule
    from pyspark_tf_gke_tpu.replay.driver import replay_spec
    from pyspark_tf_gke_tpu.replay.generators import synth_spec
    from pyspark_tf_gke_tpu.router.localfleet import LocalFleet

    duration = 9.0
    spec = synth_spec("flash_crowd", seed=7, duration_s=duration,
                      rate_rps=1.5, prompt_tokens=16, output_tokens=8,
                      max_seq_len=64, burst_mult=6.0, burst_frac=0.3)
    kill_at, restart_after = 3.0, 3.0
    schedule = ChaosSchedule("smoke-kill-one", seed=7, events=[
        ChaosEvent(offset_s=kill_at, action="kill", target="replica:1",
                   restart_s=restart_after),
    ]).validate()
    print(f"chaos check: {len(spec.requests)}-request flash crowd vs "
          "2-replica fleet + router; SIGKILL replica 1 at "
          f"{kill_at}s, restart {restart_after}s later...")
    trace_args = ("--trace-sample", "1.0", "--trace-slow-ms", "0")
    with LocalFleet(2, router_args=trace_args,
                    replica_args=(*trace_args, "--continuous-slots",
                                  "1", "--max-queue-depth", "6")) as fleet:
        fleet.warm()
        runner = ScheduleRunner(schedule, fleet)
        with runner:
            report = replay_spec(spec, fleet.url, speedup=1.0,
                                 include_requests=True)
        acted = {a["action"] for a in runner.actions}
        assert {"kill", "restart"} <= acted, (
            f"schedule was vacuous: {runner.actions}")

        # 1) exactly one terminal per request, client-side
        closure = check_report(report, len(spec.requests))
        assert closure["ok"], closure["violations"]

        # 2) the fleet quiesces and every replica is back at baseline
        assert fleet.wait_idle(timeout_s=60), "fleet never quiesced"
        for url in fleet.replica_urls:
            inv = check_replica(url)
            assert inv["ok"], f"{url}: {inv['violations']}"

        # 3) the router recovered the full fleet
        deadline = time.time() + grace_s
        routable = 0
        while time.time() < deadline:
            with urllib.request.urlopen(fleet.url + "/healthz",
                                        timeout=5) as resp:
                routable = json.loads(resp.read())["routable"]
            if routable == 2:
                break
            time.sleep(0.5)
        assert routable == 2, f"router never re-admitted: {routable}"

        # 4) goodput recovered after the restart: the final window
        #    must serve again (the kill window may legitimately shed)
        wins = goodput_windows(
            report, [0.0, kill_at, kill_at + restart_after, duration + 1])
        tail = wins[-1]
        assert tail["requests"] > 0, f"no post-restart demand: {wins}"
        assert tail["ok_rate"] and tail["ok_rate"] >= 0.5, (
            f"goodput never recovered: {wins}")
    print(f"chaos OK: outcomes {report['outcomes']}, actions "
          f"{sorted(acted)}, goodput windows "
          f"{[(w['requests'], w['ok_rate']) for w in wins]}, "
          "invariants clean, router back to 2 routable")
    return 0


def watchtower_check(grace_s: float = 30.0) -> int:
    """``--watchtower``: the fleet watchtower's chaos-native contract,
    live. A 2-replica CPU localfleet runs behind the real router with
    fast alert knobs; under steady light load the /fleetz rollups must
    populate and ZERO alerts may fire (false-positive guard); then one
    replica is SIGKILLed — the structural ``replica_down`` alert must
    FIRE within the documented detection bound (fail_threshold x
    probe_interval + probe_timeout + one sweep tick, plus scheduling
    slack on a loaded CPU box) — and after a restart it must RESOLVE
    within --alert-clear + re-admission time."""
    import json
    import time
    import urllib.request

    from pyspark_tf_gke_tpu.router.localfleet import LocalFleet

    probe_interval, probe_timeout, fail_threshold = 0.3, 1.0, 2
    clear_s = 2.0
    # probe-path detection bound (passive health is faster under
    # load): threshold sweeps + one timeout + one evaluation tick
    detect_bound = (fail_threshold * probe_interval + probe_timeout
                    + probe_interval + 5.0)  # + CPU-box slack

    def _post(url, payload):
        req = urllib.request.Request(
            url + "/v1/generate", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=20) as resp:
                return resp.status
        except Exception:  # noqa: BLE001 — shed/fail is a valid verdict
            return None

    def _alertz(url):
        with urllib.request.urlopen(url + "/alertz", timeout=5) as resp:
            return json.loads(resp.read())

    router_args = ("--probe-interval", str(probe_interval),
                   "--probe-timeout", str(probe_timeout),
                   "--fail-threshold", str(fail_threshold),
                   "--alert-for", "0", "--alert-clear", str(clear_s))
    print("watchtower check: 2-replica fleet + router "
          f"(probe {probe_interval}s, clear {clear_s}s); steady "
          "control window, then SIGKILL replica 1...")
    with LocalFleet(2, router_args=router_args,
                    replica_args=("--continuous-slots", "1",
                                  "--max-queue-depth", "6")) as fleet:
        fleet.warm()

        # 1) steady in-SLO control window: light load, no alerts
        t_ctl = time.monotonic()
        while time.monotonic() - t_ctl < 3.0:
            _post(fleet.url, {"prompts": ["steady state probe"],
                              "max_new_tokens": 4})
            time.sleep(0.2)
        a = _alertz(fleet.url)
        fired = [h for h in a["history"] if h["to"] == "firing"]
        assert not a["firing"] and not fired, (
            f"false positive during steady load: {a['firing']} "
            f"{fired}")

        # 2) /fleetz rollups populated by the riding sweeps
        with urllib.request.urlopen(fleet.url + "/fleetz",
                                    timeout=5) as resp:
            fz = json.loads(resp.read())
        assert fz["sweeps_total"] > 0 and fz["fleet"], fz
        assert fz["fleet"]["up"] == 2, fz["fleet"]
        assert len(fz["replicas"]) == 2 and fz["history"], fz

        # 3) SIGKILL replica 1 -> the structural alert fires within
        #    the detection bound
        victim = fleet.replica_urls[1]
        fleet.kill_replica(1)
        t_kill = time.monotonic()
        fired_names: list = []
        while time.monotonic() - t_kill < detect_bound:
            # keep a trickle of load flowing (passive health path)
            _post(fleet.url, {"prompts": ["post-kill probe"],
                              "max_new_tokens": 4})
            fired_names = _alertz(fleet.url)["firing"]
            if any(victim in n for n in fired_names):
                break
            time.sleep(0.2)
        detect_s = time.monotonic() - t_kill
        assert any(victim in n for n in fired_names), (
            f"replica_down:{victim} never fired within "
            f"{detect_bound}s: {fired_names}")
        print(f"  alert fired {detect_s:.2f}s after SIGKILL "
              f"(bound {detect_bound:.1f}s)")

        # 4) restart -> re-admission + clear_s -> resolved
        fleet.restart_replica(1)
        t_restart = time.monotonic()
        resolve_bound = grace_s + clear_s
        while time.monotonic() - t_restart < resolve_bound:
            a = _alertz(fleet.url)
            if not a["firing"]:
                break
            time.sleep(0.3)
        resolve_s = time.monotonic() - t_restart
        assert not a["firing"], (
            f"alert never resolved within {resolve_bound}s after "
            f"restart: {a['firing']}")
        down_alert = [x for x in a["alerts"]
                      if victim in x["name"]][0]
        assert down_alert["state"] == "resolved", down_alert
        assert down_alert["fire_count"] == 1, down_alert
    print(f"watchtower OK: zero false alerts in the control window, "
          f"fleet rollups populated ({fz['sweeps_total']} sweeps), "
          f"kill detected in {detect_s:.2f}s "
          f"(bound {detect_bound:.1f}s), resolved {resolve_s:.2f}s "
          "after restart, fire_count=1")
    return 0


def autopilot_check() -> int:
    """``--autopilot``: the closed-loop fleet controller, live. A
    2-replica CPU localfleet runs behind the real router (admin plane
    token-gated on); an :class:`Autopilot` driving a
    :class:`LocalFleetActuator` polls the router's own /fleetz +
    /alertz over HTTP. A tight flash crowd then hits the fleet:

    1. the autopilot must scale 2 -> 3 within the tick bound — a real
       third replica process boots, pre-warms, and registers through
       ``POST /admin/replicas``;
    2. every crowd request must complete HTTP 200 (zero lost — the
       scale-up and later drain are invisible to clients);
    3. after the crowd the autopilot must drain back to 2 (deregister
       first, SIGTERM drain) once the stabilization window elapses;
    4. exactly one applied scale_up and at least one applied
       scale_down in the decision ring, each carrying its rollup +
       plan provenance, and zero alerts left firing.
    """
    import json
    import os
    import tempfile
    import threading
    import time
    import urllib.request

    from pyspark_tf_gke_tpu.obs.events import EventLog
    from pyspark_tf_gke_tpu.replay.capacity import FleetModel
    from pyspark_tf_gke_tpu.router.autopilot import (Autopilot,
                                                     LocalFleetActuator)
    from pyspark_tf_gke_tpu.router.localfleet import LocalFleet

    token = "smoke-autopilot-gate"
    prompt = "autopilot crowd probe"
    tick_s, stabilization_s, cooldown_s = 1.0, 2.0, 5.0
    # a new CPU replica must boot + warm + register + be probed UP:
    # generous bound, the assertion is that it happens at all under
    # the crowd, driven by the autopilot alone
    scale_up_bound = 90.0
    drain_bound = stabilization_s + cooldown_s + 30.0
    # small capacity model so the CPU crowd's outstanding tokens
    # deterministically ask for >2 replicas: 1 slot x 4 tok/s x 5 s
    # drain target = 20 demand tokens per replica
    model = FleetModel(slots_per_replica=1, decode_tokens_per_sec=4.0)

    def _get(path):
        with urllib.request.urlopen(fleet.url + path, timeout=5) as r:
            return json.loads(r.read())

    statuses: list = []
    crowd_stop = threading.Event()

    def _crowd():
        req_body = json.dumps({"prompts": [prompt],
                               "max_new_tokens": 16}).encode()
        while not crowd_stop.is_set():
            req = urllib.request.Request(
                fleet.url + "/v1/generate", data=req_body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    statuses.append(resp.status)
            except Exception as exc:  # noqa: BLE001 — a lost request
                #   is the failure this gate exists to catch
                statuses.append(repr(exc))

    router_args = ("--admin-token", token,
                   "--probe-interval", "0.3", "--probe-timeout", "1.0",
                   "--fail-threshold", "2",
                   "--alert-for", "0", "--alert-clear", "2.0")
    replica_args = ("--continuous-slots", "1", "--prefix-cache", "8",
                    "--max-queue-depth", "64")
    print("autopilot check: 2-replica fleet + router (admin plane on), "
          "autopilot min=2 max=3 driving a LocalFleetActuator; "
          "flash crowd incoming...")
    with LocalFleet(2, router_args=router_args,
                    replica_args=replica_args) as fleet:
        fleet.warm()
        with tempfile.TemporaryDirectory() as tmp:
            ap = Autopilot(
                model,
                source=lambda: (_get("/fleetz"), _get("/alertz")),
                actuator=LocalFleetActuator(
                    fleet, admin_token=token,
                    warm_prefixes=(prompt,)),
                min_replicas=2, max_replicas=3,
                tick_s=tick_s, stabilization_s=stabilization_s,
                cooldown_s=cooldown_s,
                event_log=EventLog(os.path.join(tmp, "events.jsonl")))
            ap.start()
            crowd = [threading.Thread(target=_crowd, daemon=True)
                     for _ in range(8)]
            try:
                for t in crowd:
                    t.start()

                # 1) the autopilot scales 2 -> 3 under the crowd
                t0 = time.monotonic()
                up = 2
                while time.monotonic() - t0 < scale_up_bound:
                    up = _get("/fleetz")["fleet"]["up"]
                    if up >= 3:
                        break
                    time.sleep(0.5)
                scale_s = time.monotonic() - t0
                assert up == 3, (
                    f"never scaled to 3 within {scale_up_bound}s "
                    f"(up={up}); decisions: "
                    f"{[d['action'] for d in ap.decisions]}")
                print(f"  scaled 2 -> 3 in {scale_s:.1f}s under load")
                time.sleep(2.0)  # let the crowd exercise all 3
            finally:
                crowd_stop.set()
                for t in crowd:
                    t.join(timeout=90)

            # 2) zero lost requests through scale-up
            lost = [s for s in statuses if s != 200]
            assert statuses and not lost, (
                f"{len(lost)}/{len(statuses)} crowd requests lost: "
                f"{lost[:5]}")

            # 3) idle fleet drains back to 2 after stabilization
            t1 = time.monotonic()
            while time.monotonic() - t1 < drain_bound:
                up = _get("/fleetz")["fleet"]["up"]
                if up <= 2:
                    break
                time.sleep(0.5)
            drain_s = time.monotonic() - t1
            assert up == 2, (
                f"never drained back to 2 within {drain_bound}s "
                f"(up={up}); decisions: "
                f"{[d['action'] for d in ap.decisions]}")
            ap.stop()

            # 4) decision-ring provenance + a quiet alert plane
            ups = [d for d in ap.decisions
                   if d["action"] == "scale_up" and d["applied"]]
            downs = [d for d in ap.decisions
                     if d["action"] == "scale_down" and d["applied"]]
            assert len(ups) == 1, [d["action"] for d in ap.decisions]
            assert downs, [d["action"] for d in ap.decisions]
            for d in ups + downs:
                assert d["plan"]["replicas_needed"] == d["to"], d
                assert d["rollup"].get("up") == d["from"], d
            assert downs[0]["victim"], downs[0]
            firing = _get("/alertz")["firing"]
            assert not firing, f"alerts left firing: {firing}"
    print(f"autopilot OK: scaled 2 -> 3 in {scale_s:.1f}s under the "
          f"crowd, {len(statuses)} requests all 200 (zero lost), "
          f"drained back to 2 in {drain_s:.1f}s after it, "
          f"{len(ap.decisions)} decisions with full provenance, "
          "no alerts firing")
    return 0


def failover_stream_check(grace_s: float = 30.0) -> int:
    """``--failover-stream``: mid-stream replica death is invisible to
    the client, live. 2 tiny CPU replicas + the real router; decode is
    slowed via chaos injection (``engine.device_step:slow%1``) so a
    stream takes seconds:

    1. CONTROL: one long greedy streamed generation, uninterrupted —
       capture its token-id sequence.
    2. KILL RUN: the same request; after ≥4 tokens arrive, SIGKILL the
       replica actually holding the stream (read from the router's
       /healthz in-flight snapshot). The client's stream must still
       reach ``[DONE]`` with ZERO error terminals, and the assembled
       token ids must be token-identical to the control
       (``chaos.invariants.check_stream_tokens``) — the router's
       journal + continuation splice at work.
    3. the router's /metrics must show exactly one
       ``router_stream_resumes_total{outcome="ok"}``.
    """
    import json as _json
    import time as _time
    import urllib.request

    from pyspark_tf_gke_tpu.chaos.invariants import check_stream_tokens
    from pyspark_tf_gke_tpu.router.localfleet import LocalFleet

    prompt = "failover stream check "  # 22 byte-tokens
    max_new = 30                       # 22 + 30 < max_seq_len 64

    def stream_tokens(url, kill_after=None, fleet=None):
        """Stream one generation; returns (token_ids, saw_done,
        error_events, events). ``kill_after``: SIGKILL the replica
        holding the stream once this many tokens arrived."""
        req = urllib.request.Request(
            url + "/v1/generate",
            data=_json.dumps({"prompts": [prompt], "stream": True,
                              "max_new_tokens": max_new}).encode(),
            headers={"Content-Type": "application/json"})
        toks, done, errs, events, killed = [], False, [], [], False
        with urllib.request.urlopen(req, timeout=240) as resp:
            for raw in resp:
                line = raw.decode("utf-8", errors="replace").strip()
                if not line.startswith("data: "):
                    continue
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    done = True
                    break
                ev = _json.loads(payload)
                events.append(ev)
                if "error" in ev:
                    errs.append(ev["error"])
                toks.extend(int(t) for t in ev.get("token_ids") or [])
                if (kill_after is not None and not killed
                        and len(toks) >= kill_after):
                    killed = True
                    _kill_streaming_replica(fleet)
        return toks, done, errs, events

    def _kill_streaming_replica(fleet):
        with urllib.request.urlopen(fleet.url + "/healthz",
                                    timeout=10) as resp:
            snap = _json.loads(resp.read())["replicas"]
        busy = [r["replica"] for r in snap if r.get("inflight")]
        assert busy, f"no replica shows the stream in flight: {snap}"
        victim = fleet.replica_urls.index(busy[0])
        print(f"  SIGKILL {busy[0]} (replica {victim}) mid-stream...")
        fleet.kill_replica(victim)

    slow = ("--chaos", "engine.device_step:slow%1:0.08")
    print("failover-stream check: 2 CPU replicas + router, decode "
          "slowed 80ms/step, SIGKILL the streaming replica after "
          ">=4 tokens...")
    with LocalFleet(2, replica_args=slow, quiet=False) as fleet:
        fleet.warm()
        control, done, errs, _ = stream_tokens(fleet.url)
        assert done and not errs, (done, errs)
        assert len(control) >= 8, f"control too short: {len(control)}"
        print(f"  control run: {len(control)} tokens, [DONE] clean")

        got, done, errs, events = stream_tokens(
            fleet.url, kill_after=4, fleet=fleet)
        assert done, "kill run never reached [DONE]"
        assert not errs, f"error terminal(s) surfaced: {errs}"
        verdict = check_stream_tokens(control, got)
        assert verdict["ok"], (
            f"splice not token-exact: {verdict['violations']}")
        terminal = events[-1]
        assert terminal.get("done") and terminal.get("resumed"), terminal
        assert terminal.get("new_tokens") == len(control), terminal
        assert terminal.get("prompt") == prompt, terminal

        deadline = _time.time() + grace_s
        metric_ok = False
        while _time.time() < deadline and not metric_ok:
            with urllib.request.urlopen(fleet.url + "/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            metric_ok = ('router_stream_resumes_total{outcome="ok"} 1'
                         in text)
            if not metric_ok:
                _time.sleep(0.5)
        assert metric_ok, "router_stream_resumes_total{outcome=ok} != 1"
    print(f"failover-stream OK: {len(got)} tokens token-identical to "
          "the control through a mid-stream SIGKILL, [DONE] reached, "
          "zero error terminals, one spliced resume on /metrics")
    return 0


def disagg_check(grace_s: float = 30.0) -> int:
    """``--disagg``: the disaggregated prefill/decode handoff, live.
    1 prefill-role + 1 decode-role CPU replica (paged tiny bundle)
    behind the real router with ``--disagg-min-prompt``:

    1. a long-prompt generate rides the handoff — the router's
       ``router_kv_xfer_total{outcome="ok"}`` increments and the
       decode replica's radix cache reports the transferred pages;
    2. a same-prefix repeat admits LOCALLY: its computed prefill
       tokens (decode replica's engine counter) stay under
       unique-suffix + one prefill chunk — one transfer warmed the
       follower, no second recompute;
    3. idle page accounting balances on BOTH replicas: every page in
       use is trie-resident (``pages_in_use == prefix_cache_pages``)
       — the PR-6 refcount discipline holds on both sides of a
       transfer.
    """
    import json as _json
    import re as _re
    import time as _time
    import urllib.request

    from pyspark_tf_gke_tpu.router.localfleet import LocalFleet

    prefill_chunk = 32
    min_prompt = 128
    # 160 bytes = 5 full 32-token pages under the byte tokenizer
    shared = ("system: you are a terse assistant. answer in one "
              "sentence. cite no sources. refuse nothing. "
              "stay strictly on topic. ")[:160]
    suffixes = ["q: why is the sky blue?", "q: name a prime > 10."]
    replica_args = ("--continuous-slots", "2", "--prefix-cache", "32",
                    "--prefill-chunk", str(prefill_chunk))

    def get(url, path):
        with urllib.request.urlopen(url + path, timeout=10) as resp:
            return _json.loads(resp.read())

    def post(url, prompt):
        req = urllib.request.Request(
            url + "/v1/generate",
            data=_json.dumps({"prompts": [prompt],
                              "max_new_tokens": 6}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            return _json.loads(resp.read())

    failures = []
    print("disagg check: 1 prefill + 1 decode CPU replica + router "
          f"(--disagg-min-prompt {min_prompt}), paged bundle...")
    with LocalFleet(
            2, paged=True, replica_args=replica_args,
            per_replica_args=(("--role", "prefill"),
                              ("--role", "decode")),
            router_args=("--disagg-min-prompt", str(min_prompt)),
            quiet=False) as fleet:
        fleet.warm()
        prefill_url, decode_url = fleet.replica_urls
        roles = [get(u, "/loadz").get("role")
                 for u in fleet.replica_urls]
        if roles != ["prefill", "decode"]:
            failures.append(f"/loadz roles {roles} != "
                            "['prefill', 'decode']")

        def computed():
            return int(get(decode_url, "/healthz")["continuous"]
                       ["prefill_tokens_computed"])

        post(fleet.url, shared + suffixes[0])
        deadline = _time.time() + grace_s
        xfers = 0
        while _time.time() < deadline and not xfers:
            with urllib.request.urlopen(fleet.url + "/metrics",
                                        timeout=10) as resp:
                text = resp.read().decode()
            m = _re.search(
                r'router_kv_xfer_total\{outcome="ok"\}\s+(\d+)', text)
            xfers = int(m.group(1)) if m else 0
            if not xfers:
                _time.sleep(0.5)
        if not xfers:
            failures.append("router_kv_xfer_total{outcome=ok} never "
                            "incremented — the handoff did not run")
        pages = get(decode_url, "/loadz").get("prefix_cache_pages")
        if not pages:
            failures.append(
                f"decode replica prefix_cache_pages={pages} — the "
                "transferred pages were not adopted into the trie")
        print(f"  handoff: {xfers} ok transfer(s), decode replica "
              f"holds {pages} trie page(s)")

        # same-prefix repeat: the decode replica must admit at the
        # match boundary (ONE transfer warms all followers)
        p1 = computed()
        post(fleet.url, shared + suffixes[1])
        delta = computed() - p1
        bound = len(suffixes[1]) + prefill_chunk
        print(f"  repeat: decode replica computed {delta} prefill "
              f"tokens (bound {bound})")
        if delta >= bound:
            failures.append(
                f"same-prefix repeat computed {delta} prefill tokens "
                f"— not < suffix + one chunk ({bound}); the "
                "transferred prefix was re-prefilled")

        # refcount audit, both sides: idle fleet, every in-use page
        # trie-resident
        fleet.wait_idle()
        for name, url in (("prefill", prefill_url),
                          ("decode", decode_url)):
            loadz = get(url, "/loadz")
            total = 32
            in_use = total - int(loadz.get("kv_pages_free") or 0)
            resident = int(loadz.get("prefix_cache_pages") or 0)
            print(f"  {name}: pages_in_use={in_use} "
                  f"trie_resident={resident}")
            if in_use != resident:
                failures.append(
                    f"{name} replica leaks pages: {in_use} in use vs "
                    f"{resident} trie-resident at idle")
    if failures:
        print("disagg FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("disagg OK: long prompt rode the KV handoff, the repeat hit "
          "locally, page accounting balanced on both replicas")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--kernels-only" in argv:
        return kernel_interpret_sweep()
    if "--disagg" in argv:
        return disagg_check()
    if "--failover-stream" in argv:
        return failover_stream_check()
    if "--chaos" in argv:
        return chaos_check()
    if "--watchtower" in argv:
        return watchtower_check()
    if "--autopilot" in argv:
        return autopilot_check()
    if "--serve-lifecycle" in argv:
        return serve_lifecycle_check()
    if "--serve-tbt" in argv:
        return serve_tbt_check()
    if "--router" in argv:
        return router_check()
    if "--prefix-cache" in argv:
        return prefix_cache_check()
    if "--spec-serve" in argv:
        return spec_serve_check()
    if "--fairness" in argv:
        return fairness_check()
    if "--pipeline" in argv:
        return pipeline_check()
    if "--trace" in argv:
        return trace_check()
    if "--replay" in argv:
        return replay_check()
    if "--stepstats" in argv:
        return stepstats_check()
    if "--lint-only" not in argv:
        devices = jax.devices()
        print(f"devices: {devices}")
        assert len(devices) >= 2, "expected a 2-device virtual mesh"

        mesh = make_mesh({"dp": 2}, devices[:2])
        X, y = synthetic_classification_arrays(n=128, num_classes=4)
        it = BatchIterator({"x": X, "y": y}, 32)
        trainer = Trainer(MLPClassifier(num_classes=4),
                          TASKS["classification"](),
                          mesh, learning_rate=1e-2)
        state = trainer.init_state(make_rng(0), next(iter(it)))
        state, history = trainer.fit(state, it, epochs=2, steps_per_epoch=4)
        ok = history["loss"][-1] < history["loss"][0]
        print(f"loss {history['loss'][0]:.4f} -> {history['loss'][-1]:.4f}  "
              f"({'OK' if ok else 'NOT DECREASING'})")
        if not ok:
            return 1
    return lint_duplicate_metrics()


if __name__ == "__main__":
    sys.exit(main())
