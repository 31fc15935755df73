"""Multi-tenant overload isolation (train/continuous.py DWRR +
train/serve.py quotas + router tenant semantics): weighted fair
queueing share convergence, token-bucket charge/refund, per-tenant
429s that never touch other tenants, and the composition rules
(quota vs deadline vs drain). The slow soak at the bottom is the
noisy-neighbor + scale-up-under-load chaos proof over a real
2-replica localfleet (ROADMAP 4(c))."""

from __future__ import annotations

import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
from pyspark_tf_gke_tpu.obs.metrics import MetricsRegistry, platform_families
from pyspark_tf_gke_tpu.train.continuous import (
    ContinuousEngine,
    DwrrScheduler,
    _Request,
)
from pyspark_tf_gke_tpu.train.resilience import FaultInjector
from pyspark_tf_gke_tpu.train.serve import (
    DeadlineExceeded,
    RequestRejected,
    TokenBucket,
    _ContinuousFront,
    parse_tenant_spec,
)
from pyspark_tf_gke_tpu.utils.seeding import make_rng

TINY = dict(vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
            intermediate_size=32, max_seq_len=64, dtype=jnp.float32)


@pytest.fixture(scope="module")
def lm():
    cfg = CausalLMConfig(**TINY)
    model = CausalLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        make_rng(0), jnp.zeros((1, 8), jnp.int32))["params"])
    return model, params


def _stopped_front(model, params, **kw):
    front = _ContinuousFront(model, params, eos_id=None, **kw)
    front.stop.set()
    front.new_work.set()
    front.thread.join(timeout=10)
    assert not front.thread.is_alive()
    return front


# -- token bucket ------------------------------------------------------------


def test_token_bucket_take_refill_refund():
    b = TokenBucket(rate_per_s=100.0, burst=50.0)
    assert b.try_take(50)          # starts full
    assert not b.try_take(1)       # empty now
    b.refund(20)
    assert b.try_take(20)
    b.refund(10_000)               # refund clamps at burst
    assert b.level <= 50.0
    assert b.try_take(50)
    time.sleep(0.05)               # ~5 tokens refill at 100/s
    assert b.try_take(1)


def test_token_bucket_retry_after_tracks_refill_rate():
    b = TokenBucket(rate_per_s=10.0, burst=100.0)
    assert b.try_take(100)
    # 40 tokens at 10/s -> 4s (whole seconds, ceil)
    assert 4 <= b.retry_after_s(40) <= 5
    assert b.retry_after_s(1) == 1  # sub-second waits floor at 1
    with pytest.raises(ValueError):
        TokenBucket(rate_per_s=0, burst=10)
    with pytest.raises(ValueError):
        TokenBucket(rate_per_s=5, burst=0)


# -- tenant spec parsing -----------------------------------------------------


def test_parse_tenant_spec_compact_and_json():
    compact = parse_tenant_spec("light=3,noisy=1:200:400")
    assert compact == {
        "light": {"weight": 3.0, "rate": None, "burst": None},
        "noisy": {"weight": 1.0, "rate": 200.0, "burst": 400.0},
    }
    js = parse_tenant_spec(
        '{"light": {"weight": 3}, '
        '"noisy": {"weight": 1, "rate": 200}, "*": 2}')
    assert js["light"]["weight"] == 3.0
    assert js["noisy"]["burst"] == 400.0  # default burst = 2x rate
    assert js["*"]["weight"] == 2.0       # bare-number shorthand
    assert parse_tenant_spec("") is None
    assert parse_tenant_spec(None) is None
    with pytest.raises(ValueError):
        parse_tenant_spec("light")            # no '='
    with pytest.raises(ValueError):
        parse_tenant_spec("light=0")          # weight must be > 0
    with pytest.raises(ValueError):
        parse_tenant_spec('{"a": {"wieght": 1}}')  # unknown field


# -- DWRR share convergence (property test, pure host) -----------------------


def _mk(rid, tenant, cost):
    return _Request(rid, np.zeros(max(1, cost // 2), np.int32),
                    cost - max(1, cost // 2), tenant=tenant)


def test_dwrr_share_converges_to_weight_ratio():
    """Two tenants at weights 3:1 over a SATURATED queue: the admitted
    token shares must converge to 3:1 within tolerance, independent of
    per-request sizes (the ISSUE's share-convergence property)."""
    rng = np.random.default_rng(0)
    sched = DwrrScheduler({"light": 3, "noisy": 1}, quantum=64)
    rid = itertools.count()
    queue = []

    def refill():
        # keep both subqueues non-empty (saturation): mixed sizes
        while sum(r.tenant == "light" for r in queue) < 8:
            queue.append(_mk(next(rid), "light",
                             int(rng.integers(8, 60))))
        while sum(r.tenant == "noisy" for r in queue) < 8:
            queue.append(_mk(next(rid), "noisy",
                             int(rng.integers(8, 60))))

    for _ in range(400):
        refill()
        i = sched.pick(queue)
        sched.charge(queue[i])
        queue.pop(i)
    ratio = (sched.admitted_tokens["light"]
             / sched.admitted_tokens["noisy"])
    assert 2.4 <= ratio <= 3.6, ratio


def test_dwrr_single_tenant_is_fifo_and_idle_deficit_drops():
    sched = DwrrScheduler({"a": 5}, quantum=16)
    queue = [_mk(i, "a", 20) for i in range(4)]
    assert sched.pick(queue) == 0  # single tenant: index 0, no state
    # tenant b floods later; a's absence must have dropped its deficit
    queue2 = [_mk(10 + i, "b", 20) for i in range(4)]
    sched.pick(queue2)
    sched.charge(queue2[0])
    assert "a" not in sched._deficit
    with pytest.raises(ValueError):
        DwrrScheduler({"a": 0})
    with pytest.raises(ValueError):
        DwrrScheduler({}, quantum=0)


def test_dwrr_wildcard_weight_covers_unknown_tenants():
    sched = DwrrScheduler({"vip": 4, "*": 1})
    assert sched.weight("vip") == 4
    assert sched.weight("stranger") == 1
    assert DwrrScheduler({}).weight("anyone") == 1.0


# -- engine integration ------------------------------------------------------


def test_engine_multi_tenant_drains_correctly(lm):
    """Mixed-tenant traffic through the REAL engine: every request
    completes its budget (fairness must never change token content),
    fair mode engages only once two tenants are seen, and the stats
    expose per-tenant queue/admission state."""
    model, params = lm
    eng = ContinuousEngine(model, params, num_slots=2, chunk=2,
                           tenant_weights={"light": 3, "noisy": 1})
    assert eng.stats["fair_active"] is False
    rids = {}
    for i in range(3):
        rids[eng.submit([1, 2, 3], 4, tenant="noisy")] = 4
        rids[eng.submit([4, 5], 3, tenant="light")] = 3
    assert eng.stats["fair_active"] is True
    t = eng.stats["tenants"]
    assert t["noisy"]["queued"] == 3 and t["light"]["queued"] == 3
    assert eng.queue_depth("light") == 3
    assert eng.queued_tokens("noisy") == 3 * (3 + 4)
    assert eng.stats["queue_delay_ms"] >= 0
    done = dict(eng.run_until_drained())
    assert set(done) == set(rids)
    for rid, budget in rids.items():
        assert len(done[rid]) == budget
    t = eng.stats["tenants"]
    assert t["light"]["admitted_tokens"] == 3 * (2 + 3)
    assert t["noisy"]["admitted_tokens"] == 3 * (3 + 4)
    assert eng.stats["queue_delay_ms"] == 0.0


def test_engine_single_tenant_keeps_fifo_fast_path(lm):
    """Default-tenant traffic must never flip fair mode on: admission
    order (and therefore the path a single-tenant deployment runs) is
    bit-identical to the pre-tenancy engine."""
    model, params = lm
    eng = ContinuousEngine(model, params, num_slots=1, chunk=2)
    for _ in range(3):
        eng.submit([1, 2], 2)
    list(eng.run_until_drained())
    assert eng.stats["fair_active"] is False
    assert eng.stats["tenants"]["default"]["admitted_tokens"] == 3 * 4


# -- front: per-tenant shed / quota / refund ---------------------------------


def test_front_tenant_quota_shed_with_own_retry_after(lm):
    model, params = lm
    reg = MetricsRegistry()
    fam = platform_families(reg)
    front = _stopped_front(model, params, num_slots=1, chunk=2, obs=fam,
                           tenants="light=3,noisy=1:10:40")
    # noisy: burst 40; ask = 3 + 30 = 33 admits, next sheds on quota
    front.submit([1, 2, 3], 30, tenant="noisy")
    with pytest.raises(RequestRejected) as e:
        front.submit([1, 2, 3], 30, tenant="noisy")
    assert e.value.reason == "tenant_quota"
    assert e.value.status == 429
    assert e.value.tenant == "noisy"
    # Retry-After from the NOISY bucket's own refill: needs ~26 tokens
    # at 10/s -> >= 2s, not the global constant 1
    assert e.value.retry_after_s >= 2
    # the light tenant is untouched by noisy's quota
    front.submit([1, 2, 3], 30, tenant="light")
    assert fam["serve_tenant_rejected_total"].labels(
        tenant="noisy", reason="tenant_quota").value == 1
    assert fam["serve_tenant_requests_total"].labels(
        tenant="light").value == 1
    front.shutdown()


def test_front_tenant_queue_share_sheds_only_the_hog(lm):
    model, params = lm
    front = _stopped_front(model, params, num_slots=1, chunk=2,
                           max_queue_depth=8,
                           tenants="light=3,noisy=1")
    # noisy share = floor(8 * 1/4) = 2
    front.submit([1, 2], 4, tenant="noisy")
    front.submit([1, 2], 4, tenant="noisy")
    with pytest.raises(RequestRejected) as e:
        front.submit([1, 2], 4, tenant="noisy")
    assert e.value.reason == "tenant_queue_full"
    assert e.value.tenant == "noisy"
    # light share = floor(8 * 3/4) = 6: admits while noisy sheds
    for _ in range(6):
        front.submit([1, 2], 4, tenant="light")
    with pytest.raises(RequestRejected) as e:
        front.submit([1, 2], 4, tenant="light")
    assert e.value.reason == "tenant_queue_full"
    front.shutdown()


def test_front_without_spec_keeps_global_shed_contract(lm):
    """No --tenants: the pre-tenancy global 429 (reason queue_full, no
    tenant attribution) — the compat surface PR 3's tests pin."""
    model, params = lm
    front = _stopped_front(model, params, num_slots=1, chunk=2,
                           max_queue_depth=1)
    front.submit([1, 2, 3], 8)
    with pytest.raises(RequestRejected) as e:
        front.submit([1, 2, 3], 8)
    assert e.value.reason == "queue_full" and e.value.tenant is None
    front.shutdown()


def test_front_oversize_ask_is_terminal_400_not_429(lm):
    model, params = lm
    front = _stopped_front(model, params, num_slots=1, chunk=2,
                           tenants="noisy=1:10:20")
    # ask 33 > burst 20: can NEVER admit — terminal ValueError (400),
    # not a retry-forever 429
    with pytest.raises(ValueError, match="burst"):
        front.submit([1, 2, 3], 30, tenant="noisy")
    front.shutdown()


def test_front_refunds_unused_budget_on_deadline_expiry(lm):
    """Quota charge is prompt + max_new_tokens at admission; a deadline
    expiry hands the unused generation budget back to the tenant's
    bucket — so a dead client costs its tenant only what decoded."""
    model, params = lm
    front = _ContinuousFront(model, params, eos_id=None, num_slots=1,
                             chunk=1, tenants="t=1:1:100")
    try:
        bucket = front._buckets["t"]
        assert bucket.level == 100.0
        rid = front.submit([1, 2, 3], 60, tenant="t",
                           deadline_s=0.005)  # charge 63
        with pytest.raises(DeadlineExceeded):
            front.wait(rid, timeout_s=120)
        # refund = 60 - decoded (decoded is tiny at a 5ms deadline):
        # the bucket must recover well past the un-refunded state
        # (level was 37 + epsilon refill at 1/s)
        deadline = time.monotonic() + 10
        while bucket.level < 80 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert bucket.level >= 80
    finally:
        front.shutdown()


def test_unknown_tenants_fold_into_one_aggregate(lm):
    """Client-chosen ids not named in the spec all resolve to the ONE
    '*' aggregate: rotating fabricated names buys no extra queue share
    and mints no per-id engine/metric state — the queue stays bounded
    no matter how many ids a client invents."""
    model, params = lm
    front = _stopped_front(model, params, num_slots=1, chunk=2,
                           max_queue_depth=8,
                           tenants="light=3,noisy=1")
    assert front.resolve_tenant("light") == "light"
    assert front.resolve_tenant("made-up-7") == "*"
    assert front.resolve_tenant(None) == "*"
    # '*' share = floor(8 * 1/(3+1+1)) = 1: the SECOND fabricated id
    # already sheds — per-id shares would have admitted all of them
    front.submit([1, 2], 4, tenant="attacker-0")
    with pytest.raises(RequestRejected) as e:
        front.submit([1, 2], 4, tenant="attacker-1")
    assert e.value.reason == "tenant_queue_full"
    assert e.value.tenant == "*"
    # engine state is keyed by the aggregate, not the raw ids
    assert set(front.engine.stats["tenants"]) == {"*"}
    front.shutdown()


def test_no_spec_ignores_client_tenant_ids(lm):
    """Without --tenants, X-Tenant values must not flip the engine out
    of its single-tenant fast path or create per-id state: every
    request rides 'default'."""
    model, params = lm
    front = _stopped_front(model, params, num_slots=1, chunk=2)
    front.submit([1, 2], 4, tenant="alice")
    front.submit([1, 2], 4, tenant="bob")
    assert front.engine.stats["fair_active"] is False
    assert set(front.engine.stats["tenants"]) == {"default"}
    front.shutdown()


def test_rebuild_refunds_outstanding_quota_charges(lm):
    """A failed device step rebuilds the engine and fails the in-flight
    requests — their quota charges must refund with them, or the
    tenant pays 429s for work that was never done."""
    model, params = lm
    front = _ContinuousFront(model, params, eos_id=None, num_slots=1,
                             chunk=2, tenants="t=1:1:100",
                             chaos=FaultInjector.from_chaos_spec(
                                 "fail@1"))
    try:
        bucket = front._buckets["t"]
        rid = front.submit([1, 2, 3], 60, tenant="t")  # charge 63
        with pytest.raises(RuntimeError):
            front.wait(rid, timeout_s=120)
        # the rebuild handler settled the dead engine's outstanding
        # requests: the unused generation budget came back
        deadline = time.monotonic() + 10
        while bucket.level < 95 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert bucket.level >= 95
    finally:
        front.shutdown()


def test_score_charges_the_tenant_bucket(lm):
    """charge_tokens (the /v1/score metering hook): exact-work charge
    against the same bucket, same 429/400 taxonomy — score is not an
    unmetered side door around a generate throttle."""
    model, params = lm
    front = _stopped_front(model, params, num_slots=1, chunk=2,
                           tenants="noisy=1:10:50")
    assert front.charge_tokens("noisy", 40) == "noisy"
    with pytest.raises(RequestRejected) as e:
        front.charge_tokens("noisy", 40)  # bucket drained
    assert e.value.reason == "tenant_quota" and e.value.tenant == "noisy"
    with pytest.raises(ValueError, match="burst"):
        front.charge_tokens("noisy", 500)  # can never fit: terminal
    # unmetered tenants pass through, resolved
    assert front.charge_tokens("unlisted", 10_000) == "*"
    front.shutdown()


def test_quota_vs_drain_composition(lm):
    """Drain beats quota: once draining, every tenant's submits get the
    503 draining rejection (not a quota 429), in-flight work completes,
    and the engine drains clean."""
    model, params = lm
    front = _ContinuousFront(model, params, eos_id=None, num_slots=1,
                             chunk=1, tenants="t=1:1000:2000")
    try:
        rid = front.submit([1, 2, 3], 6, tenant="t")
        front.begin_drain()
        with pytest.raises(RequestRejected) as e:
            front.submit([1, 2], 4, tenant="t")
        assert e.value.reason == "draining" and e.value.status == 503
        assert front.wait(rid, timeout_s=120) is not None  # in-flight
        #   work survives the drain gate
        assert front.drain(timeout_s=30)
    finally:
        front.shutdown()


# -- slow: noisy-neighbor + scale-up chaos over a real localfleet ------------


@pytest.mark.slow
def test_noisy_neighbor_scale_up_under_load(tmp_path):
    """The ROADMAP 4(c) elasticity proof on CPU: a 2-replica localfleet
    behind the real router, one greedy tenant flooding. Asserts

    * light-tenant goodput 1.0 (zero lost/unserved requests),
    * light p99 within a bounded factor of its isolated-run p99,
    * every shed the flood draws is a PER-TENANT 429 (the global
      queue never rejects anyone — ``other_429 == 0``),
    * a replica started mid-flood (scale-up) is absorbed: the router
      re-admits it and traffic keeps flowing with zero stream drops,
    * a replica SIGKILLed after the soak (scale-down) doesn't lose
      the light tenant's traffic either.
    """
    import json
    import signal
    import urllib.request

    from pyspark_tf_gke_tpu.router.localfleet import (
        export_tiny_bundle,
        free_port,
        launch_replica,
        launch_router,
        percentile,
        post_tenant,
        run_noisy_neighbor,
        wait_healthy,
    )

    bundle = export_tiny_bundle(str(tmp_path / "bundle"))
    tenant_args = ("--tenants", "light=3,noisy=1:60:120",
                   "--max-queue-depth", "6")
    ports = [free_port(), free_port(), free_port()]
    router_port = free_port()
    # replicas 0+1 start now; replica 2 is the scale-up target — its
    # port is in the router's static list from the beginning (a DOWN
    # replica is probed, never pruned), so starting the process IS the
    # scale-up event
    replicas = {i: launch_replica(bundle, ports[i], quiet=True,
                                  extra_args=tenant_args)
                for i in (0, 1)}
    router_proc = None
    try:
        deadline = time.time() + 300
        for i in (0, 1):
            wait_healthy(f"http://127.0.0.1:{ports[i]}", deadline,
                         proc=replicas[i])
        router_proc = launch_router(
            ports, router_port, quiet=True,
            extra_args=("--no-hedge", "--drain-timeout", "1"))
        url = f"http://127.0.0.1:{router_port}"
        wait_healthy(url, deadline, proc=router_proc)
        # warm compiled shapes on the live replicas (direct, so the
        # isolated baseline below is steady-state)
        for i in (0, 1):
            base = f"http://127.0.0.1:{ports[i]}"
            for t in ("light", "noisy"):
                status, _, _ = post_tenant(base, "warm", t,
                                           max_new_tokens=6)
                assert status == 200
        iso = []
        for i in range(4):
            status, _, dt = post_tenant(url, f"iso {i}", "light",
                                        max_new_tokens=6)
            assert status == 200
            iso.append(dt)
        p99_iso = percentile(iso, 0.99)

        def scale_up():
            replicas[2] = launch_replica(bundle, ports[2], quiet=True,
                                         extra_args=tenant_args)

        out = run_noisy_neighbor(url, light_requests=12, light_budget=6,
                                 flood_threads=3, flood_budget=12,
                                 mid_flood_hook=scale_up)
        # goodput 1.0: the light tenant lost NOTHING to the flood or
        # the scale event
        assert out["light"]["errors"] == [], out["light"]["errors"]
        assert out["light"]["ok"] == 12
        p99_flood = percentile(out["light"]["lat_ms"], 0.99)
        bound = max(25.0 * max(p99_iso, 250.0), 5000.0)
        assert p99_flood <= bound, (p99_flood, p99_iso)
        # per-tenant shedding only: the flood drew tenant 429s and the
        # global queue rejected nobody
        assert out["noisy"]["tenant_429"] >= 1, out
        assert out["noisy"]["other_429"] == 0, out
        assert out["noisy"]["errors"] == [], out["noisy"]["errors"]
        # the scale-up replica actually joined the routable set
        deadline2 = time.time() + 60
        wait_healthy(f"http://127.0.0.1:{ports[2]}", deadline2,
                     proc=replicas[2])
        while time.time() < deadline2:
            with urllib.request.urlopen(url + "/healthz",
                                        timeout=5) as resp:
                health = json.loads(resp.read())
            if health["routable"] >= 3:
                break
            time.sleep(0.3)
        assert health["routable"] >= 3, health["routable"]
        assert health["autoscale"]["capacity_free_total"] > 0
        # scale-DOWN under load: SIGKILL replica 0 and keep serving —
        # the light tenant must not lose a request to the kill
        replicas[0].send_signal(signal.SIGKILL)
        losses = []
        for i in range(6):
            status, body, _ = post_tenant(url, f"post-kill {i}",
                                          "light", max_new_tokens=6)
            if status != 200:
                losses.append((status, str(body)[:200]))
        assert losses == [], losses
    finally:
        for p in [router_proc, *replicas.values()]:
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)
