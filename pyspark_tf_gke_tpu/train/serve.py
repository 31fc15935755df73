"""Serving deployment surface: HTTP (and stdin) serving of an exported
bundle.

The reference's terminal artifact had exactly one consumption path — a
human loads the saved Keras model and eyeballs predictions
(``workloads/raw-tf/test-model.py:13-56``). Here the terminal artifact
is a serving bundle (``train/export.py``), and this module closes the
loop from "directory on disk" to "deployed endpoint":

* ``BundleServer`` — loads a bundle (optionally tp-sharded over a mesh,
  optionally int8), serves

  - ``GET  /healthz``      → liveness/readiness (k8s probes),
  - ``POST /v1/generate``  → batch text completion,
  - ``POST /v1/score``     → per-text negative log-likelihood (the
    building block remote perplexity eval uses — evaluate/lm_eval.py
    ``--endpoint``);

* CLI: ``python -m pyspark_tf_gke_tpu.train.serve --bundle DIR
  [--port 8000] [--tp N] [--stdin]`` — the entry the k8s manifest
  (``infra/k8s/tpu/tpu-serve.yaml``) and the bastion launch script
  (``launch/serve_bundle.sh``) run.

Implementation notes (TPU-shaped, not an afterthought):

* Generation batches group prompts by token length — same-length
  prompts decode as ONE batched prefill+scan; each distinct
  (batch, prompt_len, max_new) shape hits the module-level jit cache in
  ``models/causal_lm.py``, so steady-state traffic compiles nothing.
* Scoring pads each batch up to a small set of bucket lengths
  (multiples of ``SCORE_BUCKET``) and masks the padding out of the NLL,
  so arbitrary-length texts reuse a handful of compiled shapes. Pads
  sit at the END of a causal sequence — they cannot influence the
  scored positions.
* One lock serializes device work; HTTP threads only parse/serialize.
  Single-program SPMD stays intact under a tp mesh.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pyspark_tf_gke_tpu.chaos.inject import chaos_fire
from pyspark_tf_gke_tpu.obs.events import get_event_log
from pyspark_tf_gke_tpu.obs.export import handle_obs_request
from pyspark_tf_gke_tpu.obs.metrics import get_registry, platform_families
from pyspark_tf_gke_tpu.obs.runtime import install_runtime_metrics
from pyspark_tf_gke_tpu.obs.stepstats import StepStatsRing
from pyspark_tf_gke_tpu.obs.trace import (
    TraceRecorder,
    annotate,
    annotate_request_shape,
    use_span,
)
from pyspark_tf_gke_tpu.parallel.distributed import as_host_array
from pyspark_tf_gke_tpu.utils.logging import get_logger

logger = get_logger("train.serve")

# Reject request bodies above this size with 413 before reading them —
# the handler otherwise trusts Content-Length and buffers the whole body.
MAX_BODY_BYTES = 8 << 20

SCORE_BUCKET = 64
MAX_BATCH = 64
SPEC_GAMMA = 4  # speculative draft chunk width (echoed in responses)


def _bucket(n: int, cap: int) -> int:
    return min(-(-n // SCORE_BUCKET) * SCORE_BUCKET, cap)


class RequestRejected(RuntimeError):
    """Load-shed / drain rejection BEFORE any device work: maps to HTTP
    429 (``queue_full``, ``tenant_quota``, ``tenant_queue_full``) or
    503 (``draining``) with a ``Retry-After`` header — overload
    degrades to fast rejection, not collapse. ``tenant`` is set on
    PER-TENANT sheds (quota / queue share): the handler surfaces it as
    the ``X-Tenant-Shed`` response header so the router knows the
    verdict is about one tenant, not replica health — no backoff, no
    re-route, no DOWN marking."""

    def __init__(self, reason: str, message: str, status: int,
                 retry_after_s: int = 1, tenant: Optional[str] = None):
        super().__init__(message)
        self.reason = reason
        self.status = int(status)
        self.retry_after_s = int(retry_after_s)
        self.tenant = tenant


def _draining_rejection() -> RequestRejected:
    """THE draining rejection — one definition for the front's
    admission gate, the whole-batch path, and the HTTP handler, so the
    status/message/Retry-After can never drift apart."""
    return RequestRejected(
        "draining",
        "server is draining (shutting down); retry against a live "
        "replica", status=503, retry_after_s=5)


def _reloading_rejection() -> RequestRejected:
    """Terminal handed to a request the bundle hot-swap could not drain
    within its grace window: explicit, retryable (the freshly swapped
    bundle serves the retry) — never a silent drop or a hang."""
    return RequestRejected(
        "reloading",
        "bundle hot-swap interrupted this request; retry", status=503,
        retry_after_s=1)


class ReloadInFlight(RuntimeError):
    """A bundle reload is already running (HTTP 409): reloads serialize
    — the coordinator retries after the in-flight one settles."""


class ProfileInFlight(RuntimeError):
    """A profiler capture is already running (HTTP 409): jax.profiler
    holds one process-global trace session — captures serialize, same
    contract as bundle reloads."""


class BundleReloadError(RuntimeError):
    """A reload failed (HTTP 502). ``rolled_back`` says whether the new
    bundle got as far as serving before the canary failed (True: the
    PREVIOUS generation was reinstalled and serves) or never installed
    at all (False: nothing changed). Either way the advertised
    ``bundle_generation`` did not advance."""

    def __init__(self, message: str, rolled_back: bool):
        super().__init__(message)
        self.rolled_back = bool(rolled_back)


class TokenBucket:
    """Refillable token-rate quota for ONE tenant: ``rate`` tokens/sec
    refill up to ``burst``. Admission charges the request's worst-case
    footprint (prompt + max_new_tokens) via :meth:`try_take`; the front
    refunds the UNUSED generation budget when the request delivers —
    so a quota shed can only ever happen at admission, never
    mid-stream (the charge already covers the whole generation).
    Thread-safe: handler threads take, the driver thread refunds."""

    def __init__(self, rate_per_s: float, burst: float):
        if rate_per_s <= 0:
            raise ValueError(f"rate must be > 0, got {rate_per_s}")
        if burst < 1:
            raise ValueError(f"burst must be >= 1, got {burst}")
        self.rate = float(rate_per_s)
        self.burst = float(burst)
        self._level = float(burst)  # start full: a fresh server must
        #   not 429 its first request
        self._last = time.monotonic()
        self._lock = threading.Lock()

    def _refill(self, now: float) -> None:
        self._level = min(self.burst,
                          self._level + (now - self._last) * self.rate)
        self._last = now

    def try_take(self, n: float) -> bool:
        with self._lock:
            self._refill(time.monotonic())
            if self._level >= n:
                self._level -= n
                return True
            return False

    def refund(self, n: float) -> None:
        """Return unused charge (clamped to ``burst`` — a refund can
        never bank more than the bucket holds)."""
        if n <= 0:
            return
        with self._lock:
            self._refill(time.monotonic())
            self._level = min(self.burst, self._level + float(n))

    def retry_after_s(self, n: float) -> int:
        """Whole seconds until ``n`` tokens will be available at the
        refill rate — the per-tenant ``Retry-After`` a quota shed
        carries (computed from THIS tenant's own bucket, not a global
        constant)."""
        with self._lock:
            self._refill(time.monotonic())
            if self._level >= n:
                return 1
            need = min(float(n), self.burst) - self._level
        return max(1, int(-(-need // self.rate)))

    @property
    def level(self) -> float:
        with self._lock:
            self._refill(time.monotonic())
            return self._level


def parse_tenant_spec(spec) -> Optional[Dict[str, dict]]:
    """Parse the ``--tenants`` / ``SERVE_TENANTS`` spec into
    ``{tenant: {"weight": float, "rate": float|None, "burst": float}}``.

    Two forms:

    * JSON object — ``{"light": {"weight": 3},
      "noisy": {"weight": 1, "rate": 200, "burst": 400}}``;
    * compact — ``light=3,noisy=1:200:400`` i.e.
      ``name=weight[:rate[:burst]]``.

    ``weight`` drives the engine's DWRR admission share and the
    per-tenant slice of ``--max-queue-depth`` / ``--max-queued-tokens``.
    ``rate`` (tokens/sec, absent = unmetered) + ``burst`` (default
    2x rate) build the tenant's :class:`TokenBucket`. A ``"*"`` entry
    sets the defaults for tenants not named in the spec. Empty/None
    spec -> None (tenancy off: the pre-tenancy single-queue
    behavior)."""
    if not spec:
        return None
    if isinstance(spec, dict):
        raw = spec
    else:
        spec = str(spec).strip()
        if spec.startswith("{"):
            raw = json.loads(spec)
            if not isinstance(raw, dict):
                raise ValueError(f"tenant spec must be a JSON object, "
                                 f"got {type(raw).__name__}")
        else:
            raw = {}
            for part in spec.split(","):
                part = part.strip()
                if not part:
                    continue
                name, _, rest = part.partition("=")
                if not name or not rest:
                    raise ValueError(
                        f"bad tenant spec entry {part!r} (want "
                        "name=weight[:rate[:burst]])")
                fields = rest.split(":")
                entry: dict = {"weight": float(fields[0])}
                if len(fields) > 1 and fields[1]:
                    entry["rate"] = float(fields[1])
                if len(fields) > 2 and fields[2]:
                    entry["burst"] = float(fields[2])
                if len(fields) > 3:
                    raise ValueError(
                        f"bad tenant spec entry {part!r}: too many "
                        "fields")
                raw[name.strip()] = entry
    out: Dict[str, dict] = {}
    for name, entry in raw.items():
        if not isinstance(entry, dict):
            entry = {"weight": entry}  # {"light": 3} shorthand
        weight = float(entry.get("weight", 1.0))
        if weight <= 0:
            raise ValueError(
                f"tenant {name!r} weight must be > 0, got {weight}")
        rate = entry.get("rate")
        rate = float(rate) if rate is not None else None
        if rate is not None and rate <= 0:
            raise ValueError(
                f"tenant {name!r} rate must be > 0, got {rate}")
        burst = entry.get("burst")
        burst = (float(burst) if burst is not None
                 else (2.0 * rate if rate is not None else None))
        unknown = set(entry) - {"weight", "rate", "burst"}
        if unknown:
            raise ValueError(
                f"tenant {name!r}: unknown field(s) {sorted(unknown)}")
        out[str(name)] = {"weight": weight, "rate": rate, "burst": burst}
    if not out:
        return None
    return out


class DeadlineExceeded(RuntimeError):
    """The request's client-supplied deadline passed (HTTP 504): it was
    expired in queue or cancelled in-slot at a chunk boundary, so a
    dead client never holds a KV slot."""


class EngineShutdown(RuntimeError):
    """Terminal error delivered to every pending waiter when the front
    shuts down — a waiter must fail NOW, not at its wait() timeout."""


class EngineWedged(RuntimeError):
    """Terminal error the STEP WATCHDOG delivers to every in-flight
    waiter when an engine step exceeds ``--step-timeout`` (a hung or
    pathologically slow device dispatch): the client gets an explicit
    error terminal NOW instead of riding out its full request timeout
    against a wedged loop, and the engine rebuilds the moment the
    stuck step returns."""


class _ContinuousFront:
    """Thread front for the slot engine (train/continuous.py): ONE
    driver thread owns the device loop; HTTP handler threads submit
    token prompts and block on a per-request event. Requests admitted
    into KV slots as they free up — a long completion no longer stalls
    the short ones behind it (the whole-batch path's failure mode)."""

    def __init__(self, model, params, eos_id, num_slots: int,
                 chunk: int, mesh=None, announce: bool = False,
                 prefix_cache_size: int = 0, prefill_chunk: int = 0,
                 step_token_budget: int = 0,
                 pipeline_depth: int = 0, adaptive_chunk: bool = False,
                 schedule: str = "fifo", obs=None, event_log=None,
                 max_queue_depth: int = 0, max_queued_tokens: int = 0,
                 chaos=None, heartbeat=None, tenants=None,
                 step_timeout_s: float = 0.0, spec_tokens: int = 0,
                 draft_model=None, draft_params=None,
                 step_record_ring: int = 256, peak_flops: float = 0.0,
                 tracer=None):
        # multi-tenant fairness/quotas: parsed spec (parse_tenant_spec
        # output or an equivalent dict), or None = tenancy off (every
        # request rides the "default" tenant; admission bounds stay
        # GLOBAL, exactly the pre-tenancy behavior)
        self._tenants = parse_tenant_spec(tenants)
        self._tenant_weights = ({name: cfg["weight"]
                                 for name, cfg in self._tenants.items()}
                                if self._tenants else None)
        self._buckets: Dict[str, TokenBucket] = {}
        if self._tenants:
            for name, cfg in self._tenants.items():
                if cfg["rate"] is not None:
                    self._buckets[name] = TokenBucket(cfg["rate"],
                                                      cfg["burst"])
        # the FRONT owns the step-telemetry ring and threads it through
        # every engine it builds, so GET /stepz history and the /loadz
        # host-overhead fraction survive engine rebuilds
        self.stepstats = StepStatsRing(capacity=max(1,
                                                    int(step_record_ring)))
        self._engine_args = (model, params, eos_id, num_slots, chunk,
                             mesh, announce, prefix_cache_size,
                             prefill_chunk, step_token_budget,
                             pipeline_depth, adaptive_chunk,
                             schedule, self._tenant_weights,
                             spec_tokens, draft_model, draft_params,
                             self.stepstats, float(peak_flops))
        self._announce = announce
        self._obs = obs if obs is not None else platform_families()
        self._event_log = (event_log if event_log is not None
                           else get_event_log())
        # bounded admission: 0 = unbounded (the pre-hardening behavior);
        # past either bound submit() sheds with RequestRejected instead
        # of queueing work the server cannot finish in time
        self.max_queue_depth = int(max_queue_depth)
        self.max_queued_tokens = int(max_queued_tokens)
        # serve-side chaos (resilience.FaultInjector via --chaos): fires
        # inside the driver loop so the REAL rebuild path is exercised
        self._chaos = chaos
        self._chaos_step = 0
        # liveness signal from the driver loop itself — /healthz answers
        # from an HTTP thread even when the device loop is wedged, so
        # the k8s liveness probe watches THIS file's age instead
        self._heartbeat = heartbeat
        self.draining = threading.Event()
        self.engine = self._new_engine()
        self.lock = threading.Lock()
        self.new_work = threading.Event()
        self.stop = threading.Event()
        # rid -> [done_event, tokens|Exception|None, stream_q|None].
        # The DICT is guarded by its own lock (always inner to
        # self.lock): the step watchdog must reap waiters while the
        # driver thread is stuck inside engine.step() HOLDING
        # self.lock — a single lock would let one hung device dispatch
        # wedge the reaper too.
        self._results = {}
        self._results_lock = threading.Lock()
        self._warmed = []  # token lists, replayed into rebuilt engines
        # step watchdog (chaos-plane durability): when an engine step
        # runs longer than step_timeout_s (hung/failed device
        # dispatch), every in-flight waiter gets an explicit
        # EngineWedged error terminal and the engine rebuilds the
        # moment the step returns. 0 = off. _last_loop_ts is the
        # /livez liveness signal — it stalls exactly when the driver
        # loop does.
        self.step_timeout_s = float(step_timeout_s)
        self._step_started = None  # monotonic at engine.step() entry
        self._wedged = False
        self._last_loop_ts = time.monotonic()
        # on-demand profiler capture (POST /admin/profile): the driver
        # loop starts a jax.profiler trace at the next BUSY step and
        # stops it after N busy steps, emitting profile_trace_written
        # with the covered step-seq window + recent trace ids so an
        # xprof capture, a /stepz window and a /traces slow trace all
        # cross-link. One capture at a time (jax.profiler is
        # process-global) — a second request 409s.
        self._profile_lock = threading.Lock()
        self._profile = None
        self._tracer = tracer
        self.thread = threading.Thread(
            target=self._loop, name="continuous-engine", daemon=True)
        self.thread.start()
        # the watchdog thread ALWAYS runs (idle no-op sweeps at 1 Hz
        # while step_timeout_s <= 0) so the timeout really is a live
        # attribute: a front built with the watchdog off can arm it
        # at runtime and be reaped, not silently unprotected
        threading.Thread(target=self._watch_steps,
                         name="step-watchdog", daemon=True).start()

    def _new_engine(self):
        from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine

        (model, params, eos_id, num_slots, chunk, mesh, announce,
         prefix_cache_size, prefill_chunk, step_token_budget,
         pipeline_depth, adaptive_chunk, schedule,
         tenant_weights, spec_tokens, draft_model,
         draft_params, stepstats, peak_flops) = self._engine_args
        return ContinuousEngine(model, params, num_slots=num_slots,
                                chunk=chunk, eos_token_id=eos_id,
                                mesh=mesh, announce=announce,
                                prefix_cache_size=prefix_cache_size,
                                prefill_chunk=prefill_chunk,
                                step_token_budget=step_token_budget,
                                pipeline_depth=pipeline_depth,
                                adaptive_chunk=adaptive_chunk,
                                schedule=schedule,
                                tenant_weights=tenant_weights,
                                spec_tokens=spec_tokens,
                                draft_model=draft_model,
                                draft_params=draft_params,
                                obs=self._obs,
                                stepstats=stepstats,
                                peak_flops=peak_flops)

    # -- tenancy helpers -------------------------------------------------

    def resolve_tenant(self, tenant: Optional[str]) -> str:
        """Normalize a CLIENT-SUPPLIED tenant id to the identity the
        fairness machinery runs on. No ``--tenants`` spec: always
        "default" — untrusted X-Tenant values must not be able to flip
        the engine out of its single-tenant FIFO/batch-admit fast path
        or mint unbounded metric label values on an unconfigured
        server. With a spec: ids named in it pass through; everything
        else folds into the ONE ``*`` aggregate — unlisted ids share a
        slice, a quota bucket and a label, so rotating fabricated
        tenant names gains an attacker nothing (no per-id queue share,
        no per-id state growth). Isolation is something you configure
        by naming the tenant."""
        if self._tenants is None:
            return "default"
        t = str(tenant) if tenant else "default"
        return t if (t in self._tenants and t != "*") else "*"

    def _tenant_share(self, tenant: str, bound: int) -> int:
        """This (resolved) tenant's weight-proportional slice of a
        global admission bound (``max_queue_depth`` /
        ``max_queued_tokens``). The denominator is the sum of ALL spec
        weights (an explicit ``*`` entry included); a spec without
        ``*`` gives the unlisted-tenant aggregate an implicit weight
        1.0 that widens only its OWN denominator — named tenants keep
        their natural shares, and every fabricated id shares the one
        aggregate slice, so shares sum to ~the bound regardless of how
        many ids a client invents."""
        cfgs = self._tenants
        total = sum(c["weight"] for c in cfgs.values())
        if tenant == "*" and "*" not in cfgs:
            w = 1.0
            total += w
        else:
            w = cfgs[tenant]["weight"]
        return max(1, int(bound * w / max(total, w)))

    def _bucket_for(self, tenant: str) -> Optional[TokenBucket]:
        """The (resolved) tenant's quota bucket, or None (unmetered).
        One bucket per SPEC ENTRY only — unlisted tenants were already
        folded into ``*`` by :meth:`resolve_tenant`, so the bucket map
        is bounded by the spec and the refund path can never miss a
        bucket the charge path used."""
        if not self._tenants:
            return None
        return self._buckets.get(tenant)

    def _shed_tenant(self, tenant: str, reason: str, message: str,
                     retry_after_s: int) -> None:
        self._obs["serve_requests_rejected_total"].labels(
            reason=reason).inc()
        self._obs["serve_tenant_rejected_total"].labels(
            tenant=tenant, reason=reason).inc()
        raise RequestRejected(reason, message, status=429,
                              retry_after_s=retry_after_s, tenant=tenant)

    def charge_tokens(self, tenant: Optional[str], n: int) -> str:
        """Charge ``n`` tokens of NON-ENGINE device work (the
        whole-batch /v1/score path) against the tenant's quota bucket.
        Exact work, charged up front, no refund. Returns the resolved
        tenant; raises the same per-tenant 429 / terminal-400 taxonomy
        as admission — a tenant throttled on generate must not
        saturate the device unmetered through score."""
        tenant = self.resolve_tenant(tenant)
        bucket = self._bucket_for(tenant)
        if bucket is None:
            return tenant
        if n > bucket.burst:
            raise ValueError(
                f"score batch of {n} tokens exceeds tenant {tenant!r} "
                f"quota burst {bucket.burst:g} — split the batch")
        if not bucket.try_take(n):
            self._shed_tenant(
                tenant, "tenant_quota",
                f"tenant {tenant!r} token quota exhausted (score "
                f"batch needs {n} tokens; refill {bucket.rate:g}/s)",
                retry_after_s=bucket.retry_after_s(n))
        return tenant

    def _settle(self, req) -> None:
        """One engine-delivered request's quota reconciliation: refund
        the UNUSED generation budget to its tenant's bucket (charged as
        prompt + max_new_tokens at admission, so a deadline expiry or
        early eos returns the difference) and count delivered tokens.
        Runs on the driver thread, once per delivery."""
        bucket = self._buckets.get(req.tenant)
        if bucket is not None:
            unused = int(req.max_new_tokens) - len(req.tokens)
            if unused > 0:
                bucket.refund(unused)
        if req.tokens:
            self._obs["serve_tenant_tokens_total"].labels(
                tenant=req.tenant).inc(len(req.tokens))

    def _check_admission(self, prompt_len: int, max_new_tokens: int,
                         tenant: str = "default") -> None:
        """Bounded admission + drain gate (caller holds ``self.lock``).
        Raises :class:`RequestRejected` — BEFORE the engine sees the
        request, so shedding costs no device work and no KV pages.

        Shed ordering: drain first (503 — replica lifecycle beats
        everything), then the terminal footprint check (400), then —
        with a ``--tenants`` spec — the PER-TENANT gates: queue share
        (this tenant's weight-proportional slice of the global bounds)
        and token-rate quota, each a 429 carrying the tenant and a
        Retry-After computed from that tenant's own state. Without a
        spec the global bounds apply verbatim (pre-tenancy behavior).
        A tenant over its share/quota sheds while every other tenant
        keeps admitting — the global queue never rejects a tenant that
        is inside its own share."""
        if self.draining.is_set():
            self._obs["serve_requests_rejected_total"].labels(
                reason="draining").inc()
            raise _draining_rejection()
        ask = int(prompt_len) + int(max_new_tokens)
        if self.max_queued_tokens and ask > self.max_queued_tokens:
            # the request ALONE busts the budget: no amount of
            # retrying can ever clear that — terminal 400 (caller
            # error), not a 429 retry-forever loop
            raise ValueError(
                f"request footprint {ask} tokens (prompt + budget) "
                f"exceeds max_queued_tokens {self.max_queued_tokens}")
        if self._tenants is None:
            if self.max_queue_depth:
                depth = self.engine.queue_depth()
                if depth >= self.max_queue_depth:
                    self._obs["serve_requests_rejected_total"].labels(
                        reason="queue_full").inc()
                    raise RequestRejected(
                        "queue_full",
                        f"admission queue full ({depth} waiting >= "
                        f"max_queue_depth {self.max_queue_depth})",
                        status=429, retry_after_s=1)
            if self.max_queued_tokens:
                queued = self.engine.queued_tokens()
                if queued + ask > self.max_queued_tokens:
                    self._obs["serve_requests_rejected_total"].labels(
                        reason="queue_full").inc()
                    raise RequestRejected(
                        "queue_full",
                        f"queued-token budget exhausted ({queued} queued "
                        f"+ {ask} requested > max_queued_tokens "
                        f"{self.max_queued_tokens})",
                        status=429, retry_after_s=1)
            return
        # -- per-tenant gates (tenancy configured) -----------------------
        if self.max_queue_depth:
            share = self._tenant_share(tenant, self.max_queue_depth)
            depth = self.engine.queue_depth(tenant)
            if depth >= share:
                self._shed_tenant(
                    tenant, "tenant_queue_full",
                    f"tenant {tenant!r} admission-queue share full "
                    f"({depth} waiting >= share {share} of "
                    f"max_queue_depth {self.max_queue_depth})",
                    retry_after_s=1)
        if self.max_queued_tokens:
            share = self._tenant_share(tenant, self.max_queued_tokens)
            if ask > share:
                raise ValueError(
                    f"request footprint {ask} tokens exceeds tenant "
                    f"{tenant!r} queued-token share {share}")
            queued = self.engine.queued_tokens(tenant)
            if queued + ask > share:
                self._shed_tenant(
                    tenant, "tenant_queue_full",
                    f"tenant {tenant!r} queued-token share exhausted "
                    f"({queued} queued + {ask} requested > share "
                    f"{share} of max_queued_tokens "
                    f"{self.max_queued_tokens})",
                    retry_after_s=1)
        bucket = self._bucket_for(tenant)
        if bucket is not None:
            if ask > bucket.burst:
                raise ValueError(
                    f"request footprint {ask} tokens exceeds tenant "
                    f"{tenant!r} quota burst {bucket.burst:g} — it can "
                    "never admit at any retry")
            if not bucket.try_take(ask):
                # Retry-After from THIS tenant's refill rate: the shed
                # is a quota verdict about the tenant, and the header
                # tells it exactly when its own bucket will cover the
                # request — other tenants' admission is untouched
                self._shed_tenant(
                    tenant, "tenant_quota",
                    f"tenant {tenant!r} token quota exhausted "
                    f"(request needs {ask} tokens; refill "
                    f"{bucket.rate:g}/s)",
                    retry_after_s=bucket.retry_after_s(ask))

    def submit(self, prompt_ids, max_new_tokens: int,
               temperature: float = 0.0, top_p=None,
               seed: int = 0, deadline_s=None,
               tenant: str = "default", span=None) -> int:
        """Queue a request (non-blocking); pair with ``wait``.
        ``deadline_s``: seconds from now the client still cares about
        the answer — past it the engine expires the request at the next
        chunk boundary and ``wait`` raises :class:`DeadlineExceeded`.
        ``tenant``: fairness/quota identity (header/body-extracted by
        the HTTP layer; "default" when absent) — normalized here, so
        unlisted ids fold into the ``*`` aggregate and a no-spec
        server never sees anything but "default". ``span``: the
        request's trace span (obs/trace.py) — the engine annotates its
        queue/admission/prefill/token timeline onto it."""
        tenant = self.resolve_tenant(tenant)
        # shape BEFORE the admission gates: a shed request is demand
        # the replay/capacity plane must still see on its trace
        annotate_request_shape(span, tenant=tenant,
                               prompt_tokens=len(prompt_ids),
                               max_new_tokens=max_new_tokens,
                               deadline_s=deadline_s)
        done = threading.Event()
        with self.lock:
            self._check_admission(len(prompt_ids), max_new_tokens,
                                  tenant=tenant)
            try:
                rid = self.engine.submit(prompt_ids, max_new_tokens,
                                         temperature=temperature,
                                         top_p=top_p, seed=seed,
                                         deadline_s=deadline_s,
                                         tenant=tenant, span=span)
            except BaseException:
                # the quota charge landed in _check_admission; a failed
                # engine submit must hand it back or the tenant pays
                # for a request that never queued
                bucket = self._buckets.get(tenant)
                if bucket is not None:
                    bucket.refund(len(prompt_ids) + int(max_new_tokens))
                raise
            with self._results_lock:
                self._results[rid] = [done, None, None]
        self._obs["serve_tenant_requests_total"].labels(
            tenant=tenant).inc()
        self.new_work.set()
        return rid

    def wait(self, rid: int, timeout_s: float = 600.0):
        with self._results_lock:
            entry = self._results.get(rid)
        if entry is None:
            raise KeyError(f"unknown or already-collected request {rid}")
        done = entry[0]
        if not done.wait(timeout_s):
            with self.lock:
                # free the KV slot too — an abandoned request must not
                # keep decoding tokens nobody will read (overload would
                # otherwise starve the very queue that caused the
                # timeout)
                self.engine.cancel(rid)
                with self._results_lock:
                    self._results.pop(rid, None)
            raise RuntimeError(
                f"continuous decode timed out after {timeout_s}s")
        with self._results_lock:
            # pop-if-present: the step watchdog removes reaped entries
            # itself — the captured entry's result slot was written
            # BEFORE its event was set either way
            self._results.pop(rid, None)
        result = entry[1]
        if isinstance(result, (DeadlineExceeded, EngineShutdown,
                               RequestRejected)):
            # typed: the handler maps these to 504 / 500 / the shed's
            # own status (a hot-swap 'reloading' terminal is a 503)
            raise result
        if isinstance(result, Exception):
            raise RuntimeError(
                f"continuous engine failed this request: {result}")
        return result

    def submit_and_wait(self, prompt_ids, max_new_tokens: int,
                        timeout_s: float = 600.0):
        return self.wait(self.submit(prompt_ids, max_new_tokens),
                         timeout_s)

    def warm_prefix(self, prefix_ids) -> int:
        """Prefill + cache a shared prompt prefix (serialized with the
        driver loop's device work). The token list is retained so an
        engine rebuild after a failed step re-warms automatically —
        deploy-time warms must not silently vanish on a transient
        device error."""
        with self.lock:
            n = self.engine.warm_prefix(prefix_ids)
            toks = [int(t) for t in prefix_ids]
            if toks not in self._warmed:
                self._warmed.append(toks)
                cap = self.engine.warm_capacity  # dense LRU entries,
                #   or the radix cache's fixed re-warm horizon
                del self._warmed[:-cap]
            return n

    def export_prefix_pages(self, prefix_ids):
        """Read the radix-cached KV pages covering ``prefix_ids`` back
        to the host (serialized with the driver loop's device work) —
        the prefill replica's half of a disaggregated handoff."""
        with self.lock:
            return self.engine.export_prefix_pages(prefix_ids)

    def import_prefix_pages(self, token_ids, layers) -> int:
        """Install transferred KV pages + adopt them into the radix
        trie (serialized with the driver loop's device work) — the
        decode replica's half of a disaggregated handoff."""
        with self.lock:
            return self.engine.import_prefix_pages(token_ids, layers)

    def abandon(self, rid: int) -> None:
        """Give up on a submitted request: free its KV slot / queue spot
        and drop its results entry (idempotent). BOUNDED acquire on the
        front lock: during a wedged step the driver holds it for the
        whole hang, and abandon is exactly the cleanup path the
        watchdog's bounded-latency promise routes through — when the
        lock can't be had promptly, skip the engine-side cancel (the
        rebuild that follows the wedge clears engine state anyway; on
        a merely-busy engine the request runs out its budget and its
        delivery finds no waiter) and still drop the waiter entry."""
        acquired = self.lock.acquire(timeout=1.0)
        try:
            if acquired:
                self.engine.cancel(rid)
        finally:
            if acquired:
                self.lock.release()
        with self._results_lock:
            self._results.pop(rid, None)

    def submit_internal(self, prompt_ids, max_new_tokens: int) -> int:
        """Engine submit that BYPASSES the admission/quota/drain gates —
        for server-internal probes only (the bundle hot-swap canary): a
        canary shed by overload or a drained tenant bucket would roll
        back a perfectly good bundle exactly when the fleet is busiest.
        The reserved tenant name keeps it out of every client bucket
        (no charge, so no refund at delivery either)."""
        done = threading.Event()
        with self.lock:
            rid = self.engine.submit(prompt_ids, max_new_tokens,
                                     tenant="__internal__")
            with self._results_lock:
                self._results[rid] = [done, None, None]
        self.new_work.set()
        return rid

    def submit_stream(self, prompt_ids, max_new_tokens: int,
                      deadline_s=None, tenant: str = "default",
                      span=None):
        """Streaming variant: returns (rid, queue). The queue receives
        token-id lists as they decode, then a terminal item — [] on
        completion, an Exception on engine failure / deadline expiry /
        shutdown. The consumer must drain it (bounded: max_new_tokens
        items + terminal). Quota note: the tenant charge covers the
        FULL budget at admission, so a stream can never be
        quota-killed mid-flight — the unused remainder refunds at the
        terminal delivery."""
        import queue as _queue

        tenant = self.resolve_tenant(tenant)
        annotate_request_shape(span, tenant=tenant,
                               prompt_tokens=len(prompt_ids),
                               max_new_tokens=max_new_tokens,
                               deadline_s=deadline_s)
        q = _queue.Queue()
        done = threading.Event()
        with self.lock:
            self._check_admission(len(prompt_ids), max_new_tokens,
                                  tenant=tenant)
            try:
                rid = self.engine.submit(prompt_ids, max_new_tokens,
                                         on_tokens=q.put,
                                         deadline_s=deadline_s,
                                         tenant=tenant, span=span)
            except BaseException:
                bucket = self._buckets.get(tenant)
                if bucket is not None:
                    bucket.refund(len(prompt_ids) + int(max_new_tokens))
                raise
            with self._results_lock:
                self._results[rid] = [done, None, q]  # same shape as
                #                                       submit
        self._obs["serve_tenant_requests_total"].labels(
            tenant=tenant).inc()
        self.new_work.set()
        return rid, q

    def _deliver_finished(self, finished) -> None:
        """Deliver one settled step's finished requests to their
        waiters: quota refund + per-tenant token accounting for every
        delivery (completion AND expiry — a deadline-expired request
        hands its unused generation budget back to its tenant's
        bucket), then the result/terminal. Caller holds ``self.lock``
        (the driver loop and the hot-swap drain both run it).

        The results lock is taken ONCE per settled step, not once per
        request: on the pipelined engine delivery is the host work
        that must fit inside the in-flight chunk's compute, and N
        lock round-trips per step (vs the submit path and the
        watchdog) were measurable on the 1-vCPU box. Per-token waiter
        wakeups are unaffected — token streaming rides the engine's
        ``on_tokens`` queues; this path only writes terminals."""
        if not finished:
            return
        for req in finished:
            # quota settlement needs no waiter state — keep it outside
            # the results lock
            self._settle(req)
        # (the terminal span event is emitted by the ENGINE at the
        # state transition itself — one emitter for served and
        # direct callers alike; the HTTP layer still stamps the
        # status code it maps the outcome to)
        with self._results_lock:
            for req in finished:
                # delivery happens UNDER the lock, and only if nobody
                # delivered first: a step returning right at the
                # watchdog timeout races the reaper, and a waiter must
                # get exactly ONE terminal — whichever side claims the
                # still-empty slot inside the lock wins, the other
                # skips (the reaper also removes entries, so the get
                # below usually misses outright)
                slot = self._results.get(req.rid)
                if slot is None or slot[1] is not None \
                        or slot[0].is_set():
                    continue
                if req.expired:
                    err = DeadlineExceeded(
                        f"request deadline exceeded after "
                        f"{len(req.tokens)} decoded token(s)")
                    slot[1] = err
                    slot[0].set()
                    if slot[2] is not None:
                        slot[2].put(err)
                    continue
                slot[1] = req.tokens
                slot[0].set()
                if slot[2] is not None:  # streaming terminal
                    slot[2].put([])

    def swap_model(self, model, params, eos_id, drain_s: float = 30.0):
        """Bundle hot-swap: replace the engine's model/params/eos.

        Holds the front lock end to end, so HTTP submits (and the
        driver loop) WAIT rather than race the swap. The OLD engine is
        stepped to completion right here — in-flight requests and open
        streams keep delivering tokens and finish on the weights they
        started on — bounded by ``drain_s``; anything still unfinished
        past the bound gets an explicit retryable 'reloading' terminal
        (503 + Retry-After), the same contract as every other shed:
        zero hangs, zero silent drops. The NEW engine then starts
        empty; warmed prefixes are dropped (they were tokenized and
        prefilled under the old bundle)."""
        with self.lock:
            args = list(self._engine_args)
            args[0], args[1], args[2] = model, params, eos_id
            self._engine_args = tuple(args)
            deadline = time.monotonic() + float(drain_s)
            try:
                while time.monotonic() < deadline:
                    if not self.engine.busy:
                        break
                    self._deliver_finished(self.engine.step())
                # quiesce the pipeline even when the drain deadline
                # cut the loop short: settle every in-flight chunk
                # (bounded — at most pipeline_depth collects) so no
                # speculative chunk is abandoned mid-flight with its
                # tokens undelivered and its page refs held when the
                # engine below is replaced
                self._deliver_finished(self.engine.quiesce())
            except Exception:  # noqa: BLE001 — drain is best-effort;
                # the explicit-terminal sweep below covers the leftovers
                logger.exception(
                    "old engine failed while draining for a bundle swap")
            try:
                # accepted-but-undelivered requests: terminal span
                # verdict (a reload past its drain bound is a SHED) +
                # refund their quota charges before the old engine is
                # dropped
                for req in self.engine.fail_outstanding("shed"):
                    self._settle(req)
            except Exception:  # noqa: BLE001 — refunds must not block
                pass           # the swap
            err = _reloading_rejection()
            with self._results_lock:
                # claim-and-write under the lock (same exactly-one-
                # terminal discipline as _deliver_finished: the step
                # watchdog may race this sweep)
                for slot in self._results.values():
                    if slot[1] is None and not slot[0].is_set():
                        self._obs["serve_requests_rejected_total"].labels(
                            reason="reloading").inc()
                        slot[1] = err
                        slot[0].set()
                        if slot[2] is not None:
                            slot[2].put(err)
            self.engine = self._new_engine()
            self._warmed.clear()

    def _watch_steps(self):
        """Watchdog thread: reap waiters stuck behind a hung engine
        step. Touches ONLY ``_results_lock`` — the driver holds
        ``self.lock`` for the whole stuck step, so the reaper must
        never want it."""
        while not self.stop.is_set():
            timeout = self.step_timeout_s
            started = self._step_started
            if (timeout > 0 and started is not None
                    and time.monotonic() - started > timeout):
                self._reap_wedged(time.monotonic() - started)
            # poll re-derived each sweep: the timeout is a plain
            # attribute so operators/tests may retune it live (e.g.
            # generous through warmup compiles, tight at steady state;
            # 0 = disarmed — the thread idles at 1 Hz)
            self.stop.wait(max(0.05, min(1.0, timeout / 4))
                           if timeout > 0 else 1.0)

    def _reap_wedged(self, stuck_s: float) -> None:
        """One watchdog intervention: flag the wedge (the driver loop
        rebuilds the engine when the stuck step returns; /livez
        reports it meanwhile) and fail every pending waiter with an
        explicit EngineWedged error terminal — exactly one terminal
        per request, delivered NOW, instead of a silent hang into each
        client's own timeout. Re-fires each poll while the step stays
        stuck, so waiters that were mid-submit when the wedge began
        are caught on the next sweep."""
        first = not self._wedged
        self._wedged = True
        err = EngineWedged(
            f"engine step exceeded step_timeout {self.step_timeout_s:g}s "
            f"(stuck {stuck_s:.1f}s); the step watchdog failed this "
            "request")
        reaped = 0
        with self._results_lock:
            # entries stay in the table (wait() pops them and surfaces
            # the TYPED EngineWedged — deleting here made a rid reaped
            # between submit() and wait() raise a generic KeyError);
            # the slot[1]-is-None claim prevents re-reaping, and the
            # delivery path's own claim check prevents a returning
            # step from double-terminating a reaped waiter
            for slot in self._results.values():
                if slot[1] is None and not slot[0].is_set():
                    slot[1] = err
                    slot[0].set()
                    if slot[2] is not None:
                        slot[2].put(err)
                    reaped += 1
        if first or reaped:
            self._obs["serve_step_watchdog_reaps_total"].inc()
            self._event_log.emit("engine_watchdog_reap", reaped=reaped,
                                 stuck_s=round(stuck_s, 3),
                                 step_timeout_s=self.step_timeout_s)
            logger.error(
                "step watchdog: engine step stuck %.1fs (> %gs); "
                "failed %d in-flight request(s); engine rebuilds when "
                "the step returns", stuck_s, self.step_timeout_s, reaped)

    def start_profile(self, output_dir: str, steps: int) -> dict:
        """Arm an on-demand ``jax.profiler`` capture: the driver loop
        starts the trace at the next BUSY step and stops it after
        ``steps`` busy steps, emitting ``profile_trace_written``.
        Raises :class:`ProfileInFlight` while one is armed/running
        (HTTP 409 — jax.profiler holds one process-global session).
        The capture waits for real traffic: an idle engine holds the
        armed capture until work arrives."""
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"profile steps must be >= 1, got {steps}")
        with self._profile_lock:
            if self._profile is not None:
                raise ProfileInFlight(
                    "a profiler capture is already in flight")
            self._profile = {"dir": str(output_dir), "steps": steps,
                             "remaining": steps, "started": False,
                             "seq_first": None, "seq_last": None}
        return {"output_dir": str(output_dir), "steps": steps,
                "armed": True}

    def profile_in_flight(self) -> bool:
        with self._profile_lock:
            return self._profile is not None

    def _profile_maybe_start(self) -> None:
        """Driver-loop hook, just before a busy step: start the armed
        capture (once)."""
        p = self._profile
        if p is None or p["started"]:
            return
        try:
            jax.profiler.start_trace(p["dir"])
            p["started"] = True
            logger.info("profiler capture started -> %s (%d steps)",
                        p["dir"], p["steps"])
        except Exception:  # noqa: BLE001 — a broken profiler session
            # must not take the driver loop down; disarm and report
            logger.exception("jax.profiler.start_trace failed; "
                             "capture disarmed")
            with self._profile_lock:
                self._profile = None

    def _profile_note_step(self, seq: int) -> None:
        """Driver-loop hook, after a step that CLOSED a record (no-op
        spins don't advance a capture): count it and stop the capture
        at zero, stamping the covered step-seq window and the
        recorder's recent trace ids into the event — the cross-links
        that let an xprof capture, a /stepz window and a /traces slow
        trace name each other. ``seq`` is the just-closed record's
        seq: first/last counted seqs bound the window, so both name
        records that actually entered the ring (a discarded no-op
        step's consumed seq never appears)."""
        p = self._profile
        if p is None or not p["started"]:
            return
        if p["seq_first"] is None:
            p["seq_first"] = seq
        p["seq_last"] = seq
        p["remaining"] -= 1
        if p["remaining"] > 0:
            return
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            logger.exception("jax.profiler.stop_trace failed")
        trace_ids = []
        if self._tracer is not None:
            try:
                trace_ids = [t.get("trace_id")
                             for t in self._tracer.traces(limit=8)]
            except Exception:  # noqa: BLE001 — best-effort cross-link
                pass
        self._event_log.emit(
            "profile_trace_written", output_dir=p["dir"],
            steps=p["steps"], step_seq_first=p["seq_first"],
            step_seq_last=p["seq_last"], trace_ids=trace_ids)
        logger.info("profiler capture written to %s (steps %s..%s)",
                    p["dir"], p["seq_first"], p["seq_last"])
        with self._profile_lock:
            self._profile = None

    def _loop(self):
        beat = 0
        while not self.stop.is_set():
            beat += 1
            self._last_loop_ts = time.monotonic()  # /livez signal
            if self._heartbeat is not None:
                try:
                    self._heartbeat.beat(beat)
                except OSError:  # liveness signal must never take the
                    pass         # driver loop down with it
            busy = False
            seq0 = None  # first seq this iteration's step could close
            with self.lock:
                try:
                    busy = self.engine.busy
                    if busy and self._chaos is not None:
                        # counted on BUSY iterations only (deterministic
                        # against idle-spin timing); a raise here lands
                        # in the rebuild handler below — the exact path
                        # a real failed device step takes
                        self._chaos_step += 1
                        self._chaos.maybe_slow(self._chaos_step)
                        self._chaos.maybe_fail(self._chaos_step)
                    if busy:
                        self._profile_maybe_start()
                        seq0 = self.engine.stepstats.next_seq
                        self._step_started = time.monotonic()
                    try:
                        finished = self.engine.step() if busy else []
                    finally:
                        self._step_started = None
                    t_deliver = time.monotonic()
                    # the sixth phase runs outside engine.step() and so
                    # outside StepRecord.phase: annotated here
                    with annotate("engine.deliver"):
                        self._deliver_finished(finished)
                    if busy:
                        # retire sweep after delivery: the in-flight
                        # chunk often goes ready while the host
                        # delivers — observe it here so the delivery
                        # time stays out of its device-busy interval
                        self.engine.poll_retire()
                        # the one step phase that runs OUTSIDE
                        # engine.step(): amend delivery time onto the
                        # just-closed record (wall grows with it, so
                        # the phase-sum invariant holds). seq-guarded:
                        # a step that discarded its record (nothing to
                        # do) must not smear delivery onto an OLD one.
                        rec = self.engine.stepstats.last_record
                        if (rec is not None and rec.closed
                                and rec.seq >= seq0):
                            self.engine.stepstats.add_deliver(
                                rec, (time.monotonic() - t_deliver)
                                * 1000.0)
                            if self._wedged:
                                # the watchdog reaped this step's
                                # waiters while it hung: relabel the
                                # record (amend-in-place — it was
                                # closed exactly once above)
                                self.engine.stepstats.mark_reaped(rec)
                            # capture progress counts CLOSED step
                            # records only: a busy iteration whose
                            # step discarded its record (blocked
                            # admission no-op spin) must not complete
                            # the profile over zero device work — the
                            # emitted step-seq window has to name
                            # records that exist
                            self._profile_note_step(rec.seq)
                    if self._wedged:
                        # the stuck step RETURNED: its waiters were
                        # already reaped (completions among `finished`
                        # settled above; their waiter entries are gone
                        # so nothing double-delivers) — the engine
                        # state is untrustworthy, rebuild through the
                        # one failed-step path below
                        self._wedged = False
                        raise RuntimeError(
                            "engine step exceeded the watchdog timeout; "
                            "rebuilding")
                except Exception as exc:  # noqa: BLE001 — driver thread
                    # One failed step must not brick serving: the engine
                    # state may be mid-chunk garbage, so fail every
                    # in-flight request LOUDLY and rebuild the engine —
                    # later requests get a fresh slot pool.
                    logger.exception(
                        "continuous engine step failed; failing %d "
                        "in-flight request(s) and rebuilding the engine",
                        len(self._results))
                    self._obs["serve_engine_rebuilds_total"].inc()
                    self._event_log.emit(
                        "engine_rebuilt", inflight=len(self._results),
                        error=f"{type(exc).__name__}: {exc}"[:500])
                    # a failed step still closed a record (outcome=
                    # error) into the ring: advance any armed capture
                    # or a persistently failing engine would leave the
                    # process-global jax trace open forever (every
                    # later /admin/profile 409s with no disarm path)
                    rec = self.engine.stepstats.last_record
                    if (seq0 is not None and rec is not None
                            and rec.closed and rec.seq >= seq0):
                        self._profile_note_step(rec.seq)
                    try:
                        # the dead engine's accepted-but-undelivered
                        # requests never reach step()'s delivery path:
                        # mark them terminally failed (exactly one
                        # terminal span verdict each) and settle them
                        # HERE or their quota charges leak and the
                        # tenant pays 429s for work that was never done
                        for req in self.engine.fail_outstanding("error"):
                            self._settle(req)
                    except Exception:  # noqa: BLE001 — refunds must
                        pass           # not block the rebuild
                    with self._results_lock:
                        for slot in self._results.values():
                            if slot[1] is None:
                                slot[1] = exc
                                slot[0].set()
                                if slot[2] is not None:
                                    slot[2].put(exc)
                    if self._announce:
                        # workers must restart from zeros WITH us: their
                        # replica may hold the half-mutated state of the
                        # op that just failed
                        from pyspark_tf_gke_tpu.train import serving

                        with serving.mh_lock():
                            serving.announce_cb_reset()
                    self.engine = self._new_engine()
                    for toks in self._warmed:
                        try:
                            self.engine.warm_prefix(toks)
                        except Exception:  # noqa: BLE001
                            logger.exception(
                                "re-warm of a cached prefix failed "
                                "after engine rebuild")
                    busy = False
            if not busy:
                # idle: park until a submit wakes us (short timeout so
                # shutdown stays prompt)
                self.new_work.wait(0.05)
                self.new_work.clear()

    def begin_drain(self) -> None:
        """Stop admission: every later submit is rejected 503. Requests
        already queued or in slots keep decoding to completion."""
        self.draining.set()
        self._obs["serve_draining"].set(1)

    def drain(self, timeout_s: float) -> bool:
        """Block until every accepted request has delivered its result
        (completion, deadline expiry, or error) and the engine is idle,
        or ``timeout_s`` elapses. Returns True when fully drained.
        Call :meth:`begin_drain` first or new work keeps arriving."""
        deadline = time.monotonic() + timeout_s
        while True:
            with self.lock:
                busy = self.engine.busy
                with self._results_lock:
                    pending = any(
                        slot[1] is None and not slot[0].is_set()
                        for slot in self._results.values())
            if not pending and not busy:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.05)

    def shutdown(self):
        self.stop.set()
        self.new_work.set()
        self.thread.join(timeout=10)
        with self._profile_lock:
            p, self._profile = self._profile, None
        if p is not None and p.get("started"):
            try:  # don't leave a process-global trace session dangling
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass
        # Fail every still-pending waiter NOW with a terminal shutdown
        # error — before this, a waiter blocked in wait() sat out its
        # FULL timeout (600s default) against a driver thread that was
        # already gone, and a streaming consumer hung on its queue.
        err = EngineShutdown(
            "serving front shut down while the request was in flight")
        with self.lock:
            with self._results_lock:
                for slot in self._results.values():
                    if slot[1] is None and not slot[0].is_set():
                        slot[1] = err
                        slot[0].set()
                        if slot[2] is not None:
                            slot[2].put(err)


class BundleServer:
    """Loads a serving bundle and answers generate/score requests.

    ``mesh`` (optional): a tp mesh — params are placed with
    ``shard_params_for_serving`` and every call runs under the mesh
    context (XLA inserts the collectives)."""

    def __init__(self, bundle_dir: str, mesh=None, int8_kv: bool = False,
                 draft_bundle_dir: str = "", continuous_slots: int = 0,
                 continuous_chunk: int = 8, prefix_cache_size: int = 0,
                 prefill_chunk: int = 0, step_token_budget: int = 0,
                 continuous_pipeline: int = 1,
                 adaptive_chunk: bool = False, schedule: str = "fifo",
                 registry=None, event_log=None,
                 max_queue_depth: int = 0, max_queued_tokens: int = 0,
                 chaos_spec: str = "", heartbeat_file: str = "",
                 tenants_spec: str = "", admin_token: str = "",
                 trace_sample: float = 0.01,
                 trace_slow_ms: float = 1000.0,
                 step_timeout_s: float = 0.0,
                 live_stall_s: float = 120.0,
                 spec_tokens: int = 0,
                 step_record_ring: int = 256,
                 peak_flops: float = 0.0,
                 role: str = "mixed"):
        from pyspark_tf_gke_tpu.train.resilience import retry_with_backoff

        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"role must be mixed, prefill or decode, got {role!r}")
        # disaggregated serving role, advertised on /loadz: the router
        # sends long-prompt admissions to `prefill` replicas and keeps
        # ordinary generate traffic on `decode`/`mixed` ones. ADVISORY
        # — every role still serves every endpoint, so a degraded
        # fleet (all prefill replicas down) falls back to the normal
        # path instead of erroring.
        self.role = role
        self.mesh = mesh
        self._int8_kv = bool(int8_kv)
        self.draft_model = self.draft_params = None
        self.draft_bundle_dir = draft_bundle_dir
        self.model, self.params, self.meta, self.tokenizer = (
            self._load_and_verify(bundle_dir))
        if draft_bundle_dir:
            # speculative decoding: single-prompt greedy requests verify
            # a cheap draft's proposals in chunk forwards — same tokens,
            # fewer target steps (models/speculative.py)
            _permanent = (FileNotFoundError, ValueError, KeyError,
                          TypeError)
            from pyspark_tf_gke_tpu.train.export import (
                load_serving_bundle,
            )

            self.draft_model, self.draft_params, _ = retry_with_backoff(
                lambda: load_serving_bundle(draft_bundle_dir),
                op="bundle_load", give_up_on=_permanent)
            if (self.draft_model.cfg.vocab_size
                    != self.model.cfg.vocab_size):
                raise ValueError(
                    f"draft bundle vocab {self.draft_model.cfg.vocab_size} "
                    f"!= target vocab {self.model.cfg.vocab_size}")
            if mesh is not None:
                from pyspark_tf_gke_tpu.models import CausalLM
                from pyspark_tf_gke_tpu.train.serving import (
                    shard_params_for_serving,
                )

                # the draft rides the same mesh — unsharded draft arrays
                # would forfeit its tp memory/latency win and break on
                # multi-host meshes
                self.draft_model = CausalLM(self.draft_model.cfg, mesh=mesh)
                self.draft_params = shard_params_for_serving(
                    self.draft_model, self.draft_params, mesh)
        self.bundle_dir = bundle_dir
        # bundle hot-swap (the pipeline plane's publish path): one
        # reload at a time; the generation only advances after a
        # successful swap + canary, and rides /healthz + /loadz so the
        # coordinator (and the router's prober) can confirm a rollout
        self.admin_token = admin_token
        self._reload_lock = threading.Lock()
        self.bundle_generation = int(
            self.meta.get("pipeline_generation", 1))
        self.multi_host = jax.process_count() > 1
        if self.multi_host and mesh is None:
            raise ValueError("multi-host serving needs a mesh spanning "
                             "all processes (set --tp / SERVE_TP)")
        self._lock = threading.Lock()  # one model, one device queue
        # Operational metrics live on the SHARED obs registry (obs/):
        # one /metrics scrape correlates serve counters with the train
        # plane (same-process trainers) and the runtime collectors —
        # what the reference world's kubectl-top/metrics-server loop
        # becomes when the server itself is first-party. The legacy
        # pyspark_tf_gke_tpu_serve_* exposition names stay as aliases
        # (metrics_text) so serve_bundle.sh-era scrape configs keep
        # working.
        self.registry = registry if registry is not None else get_registry()
        self._obs = platform_families(self.registry)
        install_runtime_metrics(self.registry)
        self._obs["serve_bundle_generation"].set(self.bundle_generation)
        self.event_log = (event_log if event_log is not None
                          else get_event_log())
        # request tracing (obs/trace.py): every HTTP request gets a
        # span that adopts the client's traceparent (or mints a root);
        # the engine annotates the request's queue/admission/prefill/
        # token timeline onto it, GET /traces serves the retained ring.
        # sample 0 + slow 0 short-circuits to id-propagation only.
        self.tracer = TraceRecorder(
            sample=trace_sample, slow_ms=trace_slow_ms,
            counter=self._obs["serve_traces_recorded_total"])
        # drain lifecycle: SIGTERM (or begin_drain) flips this, /healthz
        # starts answering 503 draining, admission stops, and drain()
        # waits out the in-flight work
        self._draining = threading.Event()
        self._inflight_lock = threading.Lock()
        self._inflight_http = 0
        self._front = None
        if prefill_chunk and not continuous_slots:
            raise ValueError(
                "--prefill-chunk requires --continuous-slots (chunked "
                "prefill is a slot-engine feature)")
        # in-engine speculative decoding: k draft proposals per slot
        # per round, one multi-query verify — greedy token-exact vs the
        # plain engine. With no --draft-bundle the target SELF-drafts
        # (zero-config but allocates a dense draft shadow cache and
        # saves nothing — deploy a small companion bundle for speed).
        self.spec_tokens = int(spec_tokens)
        if self.spec_tokens and not continuous_slots:
            raise ValueError(
                "--spec-tokens requires --continuous-slots (in-engine "
                "speculation is a slot-engine feature; single-prompt "
                "whole-batch speculation rides --draft-bundle alone)")
        if self.spec_tokens and not draft_bundle_dir:
            logger.warning(
                "--spec-tokens %d without --draft-bundle: SELF-draft "
                "mode (correctness/testing — the dense draft shadow "
                "cache costs memory and the draft forwards cost as "
                "much as the verify; deploy a small draft bundle for "
                "the speedup)", self.spec_tokens)
        # liveness signal thresholds for GET /livez (no engine lock):
        # the driver loop's last-iteration age past live_stall_s flips
        # /livez to 503 — the cheap httpGet form of the heartbeat-age
        # exec probe
        self._live_stall_s = float(live_stall_s)
        # chaos spec: named-point tokens (POINT:ACTION@N / %P — see
        # chaos/inject.FAULT_POINTS) install the process-global
        # ChaosInjector, covering the request front and engine device
        # points on ANY serving mode; legacy fail@N / slow@N:S tokens
        # keep driving the engine DRIVER LOOP via FaultInjector below
        chaos = None
        if chaos_spec:
            from pyspark_tf_gke_tpu.chaos.inject import (
                install as chaos_install,
                split_serve_chaos_spec,
            )

            chaos, named = split_serve_chaos_spec(chaos_spec)
            if named is not None:
                chaos_install(named)
                logger.warning("named-point chaos injection ACTIVE: %s",
                               named.describe())
        if continuous_slots:
            heartbeat = None
            if heartbeat_file:
                from pyspark_tf_gke_tpu.train.resilience import Heartbeat

                # every_steps throttles the idle spin (~20 Hz) to a few
                # writes/sec; a busy loop beats once per engine chunk
                heartbeat = Heartbeat(heartbeat_file, every_steps=5)
            # multi-host: the engine announces each device op over the
            # serving wire (OP_CB_*) and the worker loops replay it into
            # their own SlotDeviceState replicas
            self._front = _ContinuousFront(
                self.model, self.params,
                eos_id=getattr(self.tokenizer, "eos_id", None),
                num_slots=continuous_slots, chunk=continuous_chunk,
                mesh=mesh, announce=self.multi_host,
                prefix_cache_size=prefix_cache_size,
                prefill_chunk=prefill_chunk,
                step_token_budget=step_token_budget,
                pipeline_depth=continuous_pipeline,
                adaptive_chunk=adaptive_chunk,
                schedule=schedule, obs=self._obs,
                event_log=self.event_log,
                max_queue_depth=max_queue_depth,
                max_queued_tokens=max_queued_tokens,
                chaos=chaos, heartbeat=heartbeat,
                tenants=tenants_spec,
                step_timeout_s=step_timeout_s,
                spec_tokens=self.spec_tokens,
                draft_model=self.draft_model,
                draft_params=self.draft_params,
                step_record_ring=step_record_ring,
                peak_flops=peak_flops,
                tracer=self.tracer)

    # -- bundle loading / hot-swap ---------------------------------------

    def _load_and_verify(self, bundle_dir: str):
        """Load + verify one serving bundle into ``(model, params,
        meta, tokenizer)`` — ONE path shared by construction and
        :meth:`reload_bundle`, so a hot-swapped bundle passes exactly
        the checks a boot-time bundle does.

        Loads retry with backoff: a GCS blip or a bundle mid-upload
        should cost seconds, not a CrashLoopBackOff cycle.
        Deterministic config errors fail FAST instead of masquerading
        as storage outages: a mistyped path (FileNotFoundError), a
        corrupt/unsupported config.json (ValueError incl.
        JSONDecodeError, KeyError/TypeError from missing fields)."""
        from pyspark_tf_gke_tpu.data.text import get_tokenizer
        from pyspark_tf_gke_tpu.train.export import load_serving_bundle
        from pyspark_tf_gke_tpu.train.resilience import retry_with_backoff

        _permanent = (FileNotFoundError, ValueError, KeyError, TypeError)

        def _load():
            # chaos: bundle-load fault point inside the retried closure
            # (boot AND hot-swap reload ride this one path)
            chaos_fire("bundle.load", bundle=bundle_dir)
            return load_serving_bundle(bundle_dir)

        model, params, meta = retry_with_backoff(
            _load, op="bundle_load", give_up_on=_permanent)
        cfg = model.cfg
        if self._int8_kv and not cfg.kv_cache_quant:
            # cache layout is a serving-time choice (params unchanged) —
            # allow turning it on for bundles exported without the flag
            import dataclasses

            cfg = dataclasses.replace(cfg, kv_cache_quant=True)
        if cfg is not model.cfg or self.mesh is not None:
            from pyspark_tf_gke_tpu.models import CausalLM

            # the model carries the mesh it runs under, as in the
            # trainer: on the TPU its Pallas calls (layernorm, flash,
            # paged attention) must sit in a shard_map inside any
            # multi-device jit — Mosaic kernels are never partitioned
            # automatically
            model = CausalLM(cfg, mesh=self.mesh)
        tokenizer = get_tokenizer(meta.get("tokenizer", "byte"))
        if tokenizer.vocab_size > model.cfg.vocab_size:
            raise ValueError(
                f"bundle tokenizer vocab {tokenizer.vocab_size} exceeds "
                f"model vocab {model.cfg.vocab_size}")
        if (self.draft_model is not None
                and self.draft_model.cfg.vocab_size
                != model.cfg.vocab_size):
            raise ValueError(
                f"bundle vocab {model.cfg.vocab_size} != configured "
                f"draft bundle vocab {self.draft_model.cfg.vocab_size}")
        if self.mesh is not None:
            from pyspark_tf_gke_tpu.train.serving import (
                shard_params_for_serving,
            )

            params = shard_params_for_serving(model, params, self.mesh)
        return model, params, meta, tokenizer

    def _check_swap_compat(self, meta: dict, model) -> None:
        """Hot-swap compatibility: the new bundle must speak the SAME
        request contract as the one serving — tokenizer spec and vocab
        pinned (a request racing the swap may encode under one bundle
        and decode under the other; with these pinned that race is
        harmless). Architecture/size changes within the same contract
        (layers, heads, max_seq_len, kv layout) are fine — the engine
        is rebuilt around the new config. Bigger migrations are a
        blue/green fleet swap, not a hot reload."""
        old_spec = self.meta.get("tokenizer", "byte")
        new_spec = meta.get("tokenizer", "byte")
        if new_spec != old_spec:
            raise ValueError(
                f"incompatible bundle: tokenizer {new_spec!r} != "
                f"serving tokenizer {old_spec!r}")
        if model.cfg.vocab_size != self.model.cfg.vocab_size:
            raise ValueError(
                f"incompatible bundle: vocab {model.cfg.vocab_size} != "
                f"serving vocab {self.model.cfg.vocab_size}")

    def _install_bundle(self, model, params, meta, tokenizer,
                        bundle_dir: str, drain_s: float = 30.0) -> None:
        """Point the serving surfaces at a (verified) bundle. The
        whole-batch path swaps under the device lock; the slot engine
        swaps through :meth:`_ContinuousFront.swap_model` (drains
        in-flight work on the OLD weights, explicit terminals past the
        grace bound, fresh engine after)."""
        with self._lock:
            self.model = model
            self.params = params
            self.meta = meta
            self.tokenizer = tokenizer
            self.bundle_dir = bundle_dir
        if self._front is not None:
            self._front.swap_model(
                model, params, getattr(tokenizer, "eos_id", None),
                drain_s=drain_s)

    def _canary(self) -> None:
        """One tiny generate through the freshly swapped bundle — the
        gate between 'loaded' and 'serving': only after it returns does
        the advertised generation advance. Slot-engine servers probe
        through :meth:`_ContinuousFront.submit_internal`, bypassing the
        admission/quota gates — a canary 429'd by overload would roll
        back a good bundle precisely when the system is busiest."""
        ids = self.tokenizer.encode("canary")
        if self._front is not None:
            rid = self._front.submit_internal(ids, 2)
            self._front.wait(rid, timeout_s=120)
            return
        out = self.generate(["canary"], max_new_tokens=2)
        if not out or "completion" not in out[0]:
            raise RuntimeError(f"canary generate returned {out!r}")

    def reload_bundle(self, bundle_dir: str, generation=None,
                      canary: bool = True,
                      drain_s: float = 30.0) -> dict:
        """Hot-swap to the bundle at ``bundle_dir`` (the pipeline
        coordinator's publish path; ``POST /admin/reload``).

        Sequence: load+verify off the driver thread (same retried path
        as boot) → compat check → swap in (in-flight work drains on the
        old weights) → canary generate → advance the advertised
        ``bundle_generation``. A load/compat failure swaps NOTHING; a
        canary failure reinstalls the previous bundle — either way the
        old generation keeps serving and the error is typed
        (:class:`BundleReloadError`, HTTP 502). One reload at a time
        (:class:`ReloadInFlight`, HTTP 409). Single-host only: a
        multi-host swap needs the params re-announced to every worker
        replica — roll the pods instead."""
        if self.multi_host:
            raise ValueError(
                "bundle hot-swap is single-host only — multi-host "
                "fleets roll pods through the k8s rolling update")
        if generation is not None:
            # coerce BEFORE any swap: a malformed generation failing
            # after the canary would leave the new bundle serving with
            # the advertised generation never advanced
            try:
                generation = int(generation)
            except (TypeError, ValueError):
                raise ValueError(
                    f"'generation' must be an integer, got "
                    f"{generation!r}") from None
        if not self._reload_lock.acquire(blocking=False):
            raise ReloadInFlight(
                "a bundle reload is already in flight; retry after it "
                "settles")
        try:
            self.event_log.emit("bundle_reload_started",
                                bundle=bundle_dir,
                                current_generation=self.bundle_generation)
            old = (self.model, self.params, self.meta, self.tokenizer,
                   self.bundle_dir)
            try:
                model, params, meta, tokenizer = (
                    self._load_and_verify(bundle_dir))
                self._check_swap_compat(meta, model)
            except Exception as exc:
                self._obs["serve_bundle_reloads_total"].labels(
                    outcome="rejected").inc()
                self.event_log.emit(
                    "bundle_reload_failed", bundle=bundle_dir,
                    rolled_back=False,
                    error=f"{type(exc).__name__}: {exc}"[:500])
                raise BundleReloadError(
                    f"bundle rejected before swap: {exc}",
                    rolled_back=False) from exc
            self._install_bundle(model, params, meta, tokenizer,
                                 bundle_dir, drain_s=drain_s)
            if canary:
                try:
                    self._canary()
                except Exception as exc:  # noqa: BLE001 — any canary
                    # failure must leave the OLD generation serving
                    logger.exception(
                        "canary generate failed after bundle swap; "
                        "rolling back to %s", old[4])
                    self._install_bundle(*old, drain_s=drain_s)
                    self._obs["serve_bundle_reloads_total"].labels(
                        outcome="rolled_back").inc()
                    self.event_log.emit(
                        "bundle_reload_rolled_back", bundle=bundle_dir,
                        restored=old[4],
                        error=f"{type(exc).__name__}: {exc}"[:500])
                    raise BundleReloadError(
                        f"canary generate failed (previous bundle "
                        f"restored): {exc}", rolled_back=True) from exc
            gen = (generation if generation is not None
                   else int(meta.get("pipeline_generation",
                                     self.bundle_generation + 1)))
            self.bundle_generation = gen
            self._obs["serve_bundle_generation"].set(gen)
            self._obs["serve_bundle_reloads_total"].labels(
                outcome="ok").inc()
            self.event_log.emit("bundle_reload_succeeded",
                                bundle=bundle_dir, generation=gen,
                                canary=bool(canary))
            logger.info("bundle hot-swapped: %s (generation %d)",
                        bundle_dir, gen)
            return {"ok": True, "bundle": bundle_dir,
                    "bundle_generation": gen, "canary": bool(canary)}
        finally:
            self._reload_lock.release()

    # -- drain lifecycle -------------------------------------------------

    def start_profile(self, output_dir: Optional[str],
                      steps: int = 8) -> dict:
        """On-demand profiler capture (``POST /admin/profile``, admin-
        token-gated like ``/admin/reload``): arm a ``jax.profiler``
        trace over the next ``steps`` BUSY engine steps, written to
        ``output_dir`` (a fresh temp dir when omitted — the response
        says where). Asynchronous: returns as soon as the capture is
        armed; completion lands on the event trail as
        ``profile_trace_written`` with the covered step-seq window and
        recent trace ids. Raises :class:`ProfileInFlight` (409) while
        a capture is armed/running, :class:`ValueError` (400) on a
        whole-batch server (no step loop to profile)."""
        if self._front is None:
            raise ValueError(
                "profiling requires --continuous-slots (the capture "
                "spans engine steps; whole-batch serving has no step "
                "loop)")
        # validate + in-flight precheck BEFORE touching the filesystem
        # (a client polling the endpoint while a capture runs must not
        # leak one orphan temp dir per 409); the front's LOCKED check
        # stays authoritative — if two arms race past the precheck,
        # the loser's fresh temp dir is removed again below
        if int(steps) < 1:
            raise ValueError(f"profile steps must be >= 1, got {steps}")
        if self._front.profile_in_flight():
            raise ProfileInFlight(
                "a profiler capture is already in flight")
        created = None
        if not output_dir:
            import tempfile

            output_dir = tempfile.mkdtemp(prefix="stepprof-")
            created = output_dir
        else:
            os.makedirs(output_dir, exist_ok=True)
        try:
            return self._front.start_profile(output_dir, steps)
        except ProfileInFlight:
            if created is not None:
                import contextlib

                with contextlib.suppress(OSError):
                    os.rmdir(created)
            raise

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def begin_drain(self) -> None:
        """Flip to draining: /healthz readiness goes 503 (k8s stops
        routing), admission stops (new requests get 503 + Retry-After),
        in-flight requests keep decoding. Idempotent."""
        if self._draining.is_set():
            return
        self._draining.set()
        self._obs["serve_draining"].set(1)
        self.event_log.emit("serve_drain_started", bundle=self.bundle_dir)
        if self._front is not None:
            self._front.begin_drain()

    def _http_enter(self) -> None:
        with self._inflight_lock:
            self._inflight_http += 1

    def _http_exit(self) -> None:
        with self._inflight_lock:
            self._inflight_http -= 1

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Wait for every in-flight HTTP request AND the slot engine to
        finish, up to ``timeout_s``. Returns True when fully drained —
        the CLI then exits 0; False means the grace window expired with
        work still in flight (k8s SIGKILL follows; the trail records
        it)."""
        deadline = time.monotonic() + timeout_s
        while True:
            with self._inflight_lock:
                busy_http = self._inflight_http
            front_idle = (self._front is None
                          or self._front.drain(timeout_s=0))
            if not busy_http and front_idle:
                self.event_log.emit("serve_drain_finished", drained=True)
                return True
            if time.monotonic() >= deadline:
                self.event_log.emit(
                    "serve_drain_finished", drained=False,
                    inflight_http=busy_http)
                return False
            time.sleep(0.05)

    # -- health ----------------------------------------------------------

    def health(self) -> dict:
        devices = jax.devices()
        return {
            "status": "draining" if self.draining else "ok",
            "bundle": self.bundle_dir,
            "bundle_generation": self.bundle_generation,
            "model": self.meta.get("model"),
            "quantized": bool(self.meta.get("quantized")),
            "vocab_size": self.model.cfg.vocab_size,
            "max_seq_len": self.model.cfg.max_seq_len,
            "tokenizer": self.meta.get("tokenizer", "byte"),
            "n_devices": len(devices),
            # what the replica runs on, for a parent that must stay off
            # JAX itself (chip_smoke.py; one process per chip)
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "processes": jax.process_count(),
            "tp": dict(self.mesh.shape).get("tp", 1) if self.mesh else 1,
            "speculative_draft": self.draft_bundle_dir or None,
            "draining": self.draining,
            "admission": ({"max_queue_depth": self._front.max_queue_depth,
                           "max_queued_tokens":
                               self._front.max_queued_tokens}
                          if self._front is not None else None),
            "continuous": (self._front.engine.stats
                           if self._front is not None else None),
        }

    def livez(self) -> dict:
        """Pure LIVENESS (``GET /livez``): is this PROCESS worth
        keeping, independent of readiness/load. Touches NO engine
        state and takes NO lock — a wedged engine must not wedge the
        probe that exists to detect it. ``live`` goes false only when
        the slot engine's driver loop has not completed an iteration
        for ``live_stall_s`` (a hung device dispatch the watchdog
        couldn't clear) — draining, zero capacity, or a dead backend
        are readiness verdicts (/healthz, /loadz), never liveness.
        Whole-batch servers (no driver loop) are always live."""
        out = {"live": True, "draining": self.draining}
        front = self._front
        if front is not None:
            age = time.monotonic() - front._last_loop_ts
            out["driver_loop_age_s"] = round(age, 3)
            out["wedged"] = bool(front._wedged)
            out["step_timeout_s"] = front.step_timeout_s
            if self._live_stall_s and age > self._live_stall_s:
                out["live"] = False
        return out

    def loadz(self) -> dict:
        """One cheap JSON load snapshot (``GET /loadz``): what the
        replica router's prober polls instead of scraping Prometheus
        text. The key set is a STABLE contract (tests pin it) — the
        router scores replicas by ``queued_tokens``/``active`` and
        gates on ``draining``; whole-batch servers (no slot engine)
        report zeros so the router can still rank them by in-flight
        HTTP load. ``capacity_free`` (routable token headroom, the
        tightest of the admission-token budget and the KV page pool),
        ``queue_delay_ms`` (oldest queued request's age) and the
        per-tenant ``tenants`` map feed the router's closed-loop
        autoscale signal and per-tenant dashboards."""
        with self._inflight_lock:
            inflight_http = self._inflight_http
        out = {
            "queued": 0,
            "queued_tokens": 0,
            "active": 0,
            "slots_total": 0,
            "kv_pages_free": None,
            "inflight_http": inflight_http,
            "draining": self.draining,
            # hot-swap rollout signal: advances only after a successful
            # swap + canary, so the coordinator's publish confirmation
            # and the router's prober read the SERVING generation
            "bundle_generation": self.bundle_generation,
            # disaggregated serving role (--role / SERVE_ROLE): the
            # router's role-split policy keys off this — prefill
            # replicas take long-prompt handoffs, decode/mixed take
            # generate traffic
            "role": self.role,
            # radix prefix cache: ACTUAL cache contents + measured hit
            # rate, so the router's affinity can score on what the
            # replica really holds instead of hashed ownership alone
            "prefix_cache_pages": 0,
            "prefix_hit_rate": 0.0,
            # autoscale/tenancy terms (zeros for whole-batch servers:
            # no admission queue to have headroom or delay in)
            "capacity_free": 0,
            "queue_delay_ms": 0.0,
            "tenants": {},
            # in-engine speculative decoding: windowed draft acceptance
            # (0.0 when --spec-tokens is off) — speculation quality a
            # router/capacity model can score on
            "spec_accept_rate": 0.0,
            # step telemetry (obs/stepstats.py): windowed DEVICE-IDLE
            # fraction of the engine step loop, derived from per-chunk
            # dispatch/retire timestamps (1 - union(device-busy)/span;
            # on a serial loop this matches the historical
            # host-work-share formula, which rides the same summary as
            # step_phases.host_work_frac) — the router's autoscale
            # block takes the fleet max, replay/capacity calibration
            # records it next to the measured service rates, and the
            # async engine core is A/B'd against it (0.0 for
            # whole-batch servers / before the first step)
            "step_host_overhead_frac": 0.0,
            # windowed engine throughput from the same /stepz summary —
            # the router watchtower's fleet rollup sums it
            # (step_tokens_per_sec_total on GET /fleetz) without a
            # second probe round-trip
            "step_tokens_per_sec": 0.0,
        }
        if self._front is not None:
            stats = self._front.engine.stats
            out["queued"] = stats["queued"]
            out["queued_tokens"] = stats["queued_tokens"]
            out["active"] = stats["active"]
            out["slots_total"] = stats["num_slots"]
            out["queue_delay_ms"] = stats.get("queue_delay_ms", 0.0)
            paged = stats.get("paged")
            if paged:
                out["kv_pages_free"] = (paged["pages_total"]
                                        - paged["pages_in_use"])
            cache = stats.get("prefix_cache")
            if cache:
                out["prefix_cache_pages"] = int(
                    cache.get("resident_pages", 0))
                out["prefix_hit_rate"] = float(
                    cache.get("recent_hit_rate", 0.0))
            # routable token headroom: how many more prompt+budget
            # tokens this replica would ADMIT right now — the tightest
            # of the bounded-admission budget and (paged engines) the
            # free KV pages' token extent; an unbounded dense engine
            # falls back to free slots x max_seq_len (crude but
            # monotone in real headroom)
            caps = []
            if self._front.max_queued_tokens:
                caps.append(self._front.max_queued_tokens
                            - stats["queued_tokens"])
            if paged:
                caps.append((paged["pages_total"]
                             - paged["pages_in_use"])
                            * paged["page_size"])
            if not caps:
                caps.append((stats["num_slots"] - stats["active"])
                            * self.model.cfg.max_seq_len)
            out["capacity_free"] = max(0, min(caps))
            self._obs["serve_capacity_free_tokens"].set(
                out["capacity_free"])
            if self.spec_tokens:
                out["spec_accept_rate"] = round(
                    self._front.engine.spec_accept_rate(), 4)
            # from the stats snapshot already in hand (summary() pre-
            # rounds it) — no second ring-lock pass per /loadz probe
            out["step_host_overhead_frac"] = (
                stats["step_phases"]["host_overhead_frac"])
            out["step_tokens_per_sec"] = (
                stats["step_phases"].get("tokens_per_sec") or 0.0)
            tenants = {}
            for name, t in (stats.get("tenants") or {}).items():
                tenants[name] = {"queued": t["queued"],
                                 "queued_tokens": t["queued_tokens"]}
                self._obs["serve_tenant_queue_depth"].labels(
                    tenant=name).set(t["queued"])
            out["tenants"] = tenants
        return out

    # -- generation ------------------------------------------------------

    def generate(self, prompts, max_new_tokens: int = 64,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 num_beams: int = 0, repetition_penalty=None,
                 deadline_s=None, tenant: str = "default",
                 seed=None, span=None) -> list:
        """Batch completion. Prompts are grouped by token length so each
        group decodes as one batched call; the batch dimension pads up
        to power-of-2 buckets (repeating the first row) so mixed traffic
        reuses a handful of compiled shapes instead of recompiling per
        group size; results return in input order. Sampling requests get
        a fresh per-request PRNG key — a fixed server-side seed would
        hand every client the same 'random' completion — unless the
        CLIENT pins ``seed`` (the ``/v1/generate`` body field): on the
        slot-engine path each prompt's sampling lane draws from its own
        ``seed + index`` key, so the completion is deterministic per
        (prompt, seed) pair — what makes idempotent retries,
        record/replay and sampled-lane continuations reproducible. The
        whole-batch fallback (beams/top-k/repetition-penalty, or no
        --continuous-slots) shares ONE ``PRNGKey(seed)`` across the
        padded batch: deterministic per (batch, seed), but a prompt's
        draws there depend on its batch composition. Greedy requests
        ignore ``seed`` entirely (byte-identical with or without it).

        ``deadline_s``: seconds from now the client still wants the
        answer (HTTP ``deadline_ms`` / 1000). The slot engine enforces
        it at chunk boundaries (queued requests expire before admission,
        in-slot ones free their KV slot); the whole-batch path checks
        between length groups — both raise :class:`DeadlineExceeded`."""
        from pyspark_tf_gke_tpu.models.causal_lm import generate
        from pyspark_tf_gke_tpu.train.serving import serve_generate

        if self.draining:
            self._obs["serve_requests_rejected_total"].labels(
                reason="draining").inc()
            raise _draining_rejection()
        t_deadline = None
        if deadline_s is not None:
            if deadline_s <= 0:
                self._obs["serve_request_deadline_exceeded_total"].inc()
                raise DeadlineExceeded(
                    f"deadline of {deadline_s * 1000.0:.0f}ms already "
                    "expired at submission")
            t_deadline = time.monotonic() + float(deadline_s)
        if not prompts:
            return []
        if len(prompts) > MAX_BATCH:
            raise ValueError(f"batch of {len(prompts)} exceeds "
                             f"max batch {MAX_BATCH}")
        rng = (jax.random.PRNGKey(
            int(seed) if seed is not None
            else int.from_bytes(os.urandom(4), "little"))
            if temperature and temperature > 0 else None)
        cfg = self.model.cfg
        eos_id = getattr(self.tokenizer, "eos_id", None)
        encoded = []
        for i, text in enumerate(prompts):
            ids = self.tokenizer.encode(text)
            if not ids:
                raise ValueError(f"prompt {i} tokenized to zero tokens")
            if len(ids) + max_new_tokens > cfg.max_seq_len:
                raise ValueError(
                    f"prompt {i}: {len(ids)} tokens + {max_new_tokens} new "
                    f"exceeds max_seq_len {cfg.max_seq_len}")
            encoded.append((i, ids))

        plain_greedy = (not (temperature and temperature > 0)
                        and not num_beams and repetition_penalty is None
                        and top_k is None and top_p is None)
        # the slot engine also serves temperature/top-p sampling (each
        # slot draws with its own per-request key); beams, top-k and
        # repetition penalty stay on the whole-batch path
        engine_ok = (not num_beams and repetition_penalty is None
                     and top_k is None)
        # Routing order for plain-greedy traffic: speculative (when a
        # draft is configured AND its context fits this request) →
        # continuous slot engine → whole-batch. The draft-context check
        # lives HERE so a too-long-for-the-draft request still gets the
        # slot engine instead of a solo whole-batch call.
        # a deadline-bearing request skips speculation: the spec loop
        # has no chunk boundary to cancel at, so it would decode its
        # full budget past a dead client — the slot engine (or the
        # group-checked whole-batch path) enforces deadlines instead
        # --spec-tokens > 0: the SLOT ENGINE speculates in-slot for
        # every request (batched draft/verify with fairness, deadlines
        # and streaming intact), so the standalone single-prompt spec
        # route stands down — it would serialize the pool behind one
        # whole-batch-style call for no extra speed.
        could_spec = (self.draft_model is not None and len(prompts) == 1
                      and plain_greedy and deadline_s is None
                      and not (self.spec_tokens and self._front
                               is not None)
                      and len(encoded[0][1]) + max_new_tokens
                      <= self.draft_model.cfg.max_seq_len)
        if self._front is not None and engine_ok and not could_spec:
            # slot engine: each prompt is its own request — they share
            # KV slots with every OTHER in-flight HTTP request, and a
            # short completion returns without waiting for a long one.
            t0 = time.perf_counter()
            # submit everything first (non-blocking — they co-occupy
            # slots), then collect in order; no thread pool needed to
            # block on events.
            temp = float(temperature or 0.0)
            rids = []
            try:
                for i, ids in encoded:
                    rids.append((i, self._front.submit(
                        ids, max_new_tokens, temperature=temp,
                        top_p=top_p,
                        # client-pinned seed (per-prompt: seed + index)
                        # makes the slot's sampling lane deterministic
                        # end to end — it rides the OP_CB_ADMIT wire as
                        # its own int64, so record/replay and worker
                        # replicas draw the identical stream
                        seed=(int(seed) + i if seed is not None
                              else int.from_bytes(os.urandom(4),
                                                  "little")),
                        deadline_s=deadline_s, tenant=tenant,
                        span=span)))
            except Exception:
                # a mid-batch rejection (queue filled between rows) must
                # not strand the rows already submitted
                for _, rid in rids:
                    self._front.abandon(rid)
                raise
            toks = {}
            try:
                for i, rid in rids:
                    toks[i] = self._front.wait(rid)
            except Exception:
                # one failed wait must not leak its siblings: cancel
                # every uncollected request (frees KV slots + results
                # entries) before surfacing the error as this HTTP 500
                for i, rid in rids:
                    if i not in toks:
                        self._front.abandon(rid)
                raise
            dt = (time.perf_counter() - t0) * 1000.0
            return [self._entry(prompts[i], toks[i], dt, eos_id)
                    for i, _ in rids]

        if could_spec:
            _, ids = encoded[0]
            from pyspark_tf_gke_tpu.train.serving import mh_speculative

            with self._lock:
                t0 = time.perf_counter()
                # mh_speculative owns single-vs-multi-host dispatch (the
                # announce header rides OP_SPECULATIVE; workers replay
                # the same accept/rollback loop in lockstep)
                out, stats = mh_speculative(
                    self.model, self.params, self.draft_model,
                    self.draft_params, jnp.asarray([ids], jnp.int32),
                    self.mesh, max_new_tokens=max_new_tokens,
                    gamma=SPEC_GAMMA, eos_token_id=eos_id)
                dt = (time.perf_counter() - t0) * 1000.0
            return [self._entry(
                prompts[0], np.asarray(as_host_array(out)[0, len(ids):]).tolist(), dt,
                eos_id,
                speculative={
                    "gamma": SPEC_GAMMA,
                    "acceptance_rate": round(
                        stats["accepted"] / max(stats["proposed"], 1), 3),
                    "tokens_per_round": round(stats["tokens_per_round"], 2),
                })]

        groups = {}
        for i, ids in encoded:
            groups.setdefault(len(ids), []).append((i, ids))

        results = [None] * len(prompts)
        with self._lock:
            for length, members in sorted(groups.items()):
                if t_deadline is not None and time.monotonic() > t_deadline:
                    # whole-batch granularity: between length groups (a
                    # dispatched group runs to completion — the compiled
                    # scan has no host re-entry to cancel at)
                    self._obs["serve_request_deadline_exceeded_total"].inc()
                    raise DeadlineExceeded(
                        "request deadline exceeded before the batch "
                        "finished decoding")
                rows = [ids for _, ids in members]
                n_real = len(rows)
                bucket = 1 << (n_real - 1).bit_length()  # next power of 2
                rows = rows + [rows[0]] * (bucket - n_real)
                batch = jnp.asarray(rows, jnp.int32)
                t0 = time.perf_counter()
                if num_beams and num_beams > 1:
                    from pyspark_tf_gke_tpu.train.serving import mh_generate

                    # mh_generate owns single-vs-multi-host dispatch and
                    # the shared serve_beam gather sequence
                    out, scores = mh_generate(
                        self.model, self.params, batch, self.mesh,
                        max_new_tokens=max_new_tokens, eos_token_id=eos_id,
                        num_beams=num_beams)
                    scores = np.asarray(scores)
                elif self.multi_host:
                    from pyspark_tf_gke_tpu.train.serving import mh_generate

                    # everything (incl. the rng key for sampling) rides
                    # the announce/replay wire — see train/serving.py
                    out = mh_generate(self.model, self.params, batch,
                                      self.mesh,
                                      max_new_tokens=max_new_tokens,
                                      eos_token_id=eos_id,
                                      temperature=temperature,
                                      top_k=top_k, top_p=top_p,
                                      repetition_penalty=repetition_penalty,
                                      rng=rng)
                    scores = None
                else:
                    gen_fn = generate if self.mesh is None else serve_generate
                    kwargs = {} if self.mesh is None else {"mesh": self.mesh}
                    out = gen_fn(
                        self.model, self.params, batch,
                        max_new_tokens=max_new_tokens,
                        temperature=temperature, rng=rng, top_k=top_k,
                        top_p=top_p, eos_token_id=eos_id,
                        repetition_penalty=repetition_penalty, **kwargs)
                    scores = None
                toks = np.asarray(as_host_array(out))[:n_real, length:]
                dt = (time.perf_counter() - t0) * 1000.0
                for row, (i, _) in enumerate(members):
                    extra = ({"beam_score": float(scores[row])}
                             if scores is not None else {})
                    results[i] = self._entry(prompts[i], toks[row].tolist(),
                                             dt, eos_id, **extra)
        return results

    def warm_prefix(self, prefix: str) -> dict:
        """Tokenize + prefill a shared prompt prefix into the slot
        engine's prefix cache (the /v1/warm endpoint). Later greedy
        requests whose prompt starts with it skip that prefill."""
        if self._front is None:
            raise ValueError("warming requires --continuous-slots")
        ids = self.tokenizer.encode(prefix)
        if not ids:
            raise ValueError("prefix tokenized to zero tokens")
        n = self._front.warm_prefix(ids)
        return {"prefix_tokens": n,
                "prefix_cache": self._front.engine.stats.get(
                    "prefix_cache")}

    # -- disaggregated prefill/decode (docs/SERVING.md) ------------------

    def prefill_export(self, prompt: str) -> dict:
        """``POST /v1/prefill``: chunked-prefill the prompt into the
        radix cache and export the finished KV pages as one base64
        ``.npz`` page blob — the prefill replica's half of a
        disaggregated handoff. The caller (the router) ships the blob
        to a decode replica's ``/v1/kv_import``; only FULL pages
        travel, the decode-side admission re-prefills the tail
        remainder exactly like a local radix hit. A repeat prompt is
        already cached, so the export is the only device work."""
        import base64

        from pyspark_tf_gke_tpu.train.kv_transfer import pack_kv_export

        if self._front is None:
            raise ValueError("KV export requires --continuous-slots")
        ids = self.tokenizer.encode(prompt)
        if not ids:
            raise ValueError("prompt tokenized to zero tokens")
        warmed = self._front.warm_prefix(ids)
        export = self._front.export_prefix_pages(ids)
        if export is None:
            # prompt shorter than one KV page: nothing transferable —
            # the router falls back to the normal (RECOMPUTE) path
            return {"prefix_tokens": warmed, "page_size": 0,
                    "pages": 0, "blob": None}
        blob = pack_kv_export(export)
        self._obs["serve_kv_xfer_bytes_total"].inc(len(blob))
        return {
            "prefix_tokens": warmed,
            "page_size": export["page_size"],
            "pages": len(export["token_ids"]) // export["page_size"],
            "blob": base64.b64encode(blob).decode("ascii"),
        }

    def kv_import(self, blob_b64: str) -> dict:
        """``POST /v1/kv_import``: install a transferred KV page blob
        into this replica's pool and adopt it into the radix trie —
        the decode replica's half of a disaggregated handoff. One
        import warms every follower of the prefix; re-imports are
        idempotent (resident pages are reused, not re-written)."""
        import base64

        from pyspark_tf_gke_tpu.train.kv_transfer import unpack_kv_blob

        if self._front is None:
            raise ValueError("KV import requires --continuous-slots")
        data = base64.b64decode(blob_b64.encode("ascii"),
                                validate=True)
        self._obs["serve_kv_xfer_bytes_total"].inc(len(data))
        transfer = unpack_kv_blob(data)
        ps = getattr(self.model.cfg, "kv_page_size", None)
        if ps is None or transfer["page_size"] != ps:
            raise ValueError(
                f"KV transfer page_size {transfer['page_size']} does "
                f"not match this replica's kv_page_size {ps} — "
                "role-split fleets must serve one bundle shape")
        imported = self._front.import_prefix_pages(
            transfer["token_ids"], transfer["layers"])
        return {"imported_tokens": imported,
                "pages": imported // ps if ps else 0}

    def generate_stream(self, prompt: str, max_new_tokens: int = 64,
                        deadline_s=None, tenant: str = "default",
                        continuation=None, span=None):
        """Greedy streaming completion through the slot engine: yields
        one event dict per decoded token group (``token_ids`` plus the
        full ``text`` so far — full text, not a delta, so multibyte
        tokenizer sequences can't tear), then a terminal event with the
        assembled completion. Requires --continuous-slots.

        ``continuation`` (``{"emitted_ids": [int, ...]}``): the
        router's mid-stream failover splice. ``prompt`` is the
        ORIGINAL prompt and ``emitted_ids`` the token ids a dead
        replica already delivered: the engine prefills
        ``encode(prompt) + emitted_ids`` (token-EXACT — text-level
        re-tokenization would be lossy for non-UTF-8 byte runs) and
        greedy decode continues precisely where the dead stream
        stopped. Events and the terminal entry frame text/counts
        CUMULATIVELY (``text`` = prompt + decode(emitted + new),
        ``new_tokens`` = emitted + generated), so a client splicing
        this leg after the originals sees one uninterrupted run."""
        if self._front is None:
            raise ValueError(
                "streaming requires --continuous-slots (the slot engine "
                "is what yields tokens as they decode)")
        if deadline_s is not None and deadline_s <= 0:
            # same contract as the blocking path: an already-dead
            # deadline is a 504 + the deadline counter, not a 400
            # leaking the internal parameter name
            self._obs["serve_request_deadline_exceeded_total"].inc()
            raise DeadlineExceeded(
                f"deadline of {deadline_s * 1000.0:.0f}ms already "
                "expired at submission")
        ids = self.tokenizer.encode(prompt)
        if not ids:
            raise ValueError("prompt tokenized to zero tokens")
        prior_ids: list = []
        if continuation is not None:
            # token-id splice point: prefill = prompt ids + the ids the
            # dead replica already delivered (NOT re-tokenized text —
            # decode→encode is lossy for non-UTF-8 byte runs)
            prior_ids = [int(t) for t in continuation["emitted_ids"]]
            ids = ids + prior_ids
            if span is not None:
                # the resume crosses replicas inside ONE trace: the
                # router's `resume` event names the dead leg, this one
                # marks where the continuation picked up
                span.event("continuation",
                           emitted_tokens=len(prior_ids))
        cfg = self.model.cfg
        if len(ids) + max_new_tokens > cfg.max_seq_len:
            raise ValueError(
                f"{len(ids)} tokens + {max_new_tokens} new exceeds "
                f"max_seq_len {cfg.max_seq_len}")
        eos_id = getattr(self.tokenizer, "eos_id", None)
        t0 = time.perf_counter()
        rid, q = self._front.submit_stream(ids, max_new_tokens,
                                           deadline_s=deadline_s,
                                           tenant=tenant, span=span)
        toks, finished, yielded = [], False, False
        try:
            while True:
                item = q.get(timeout=600)
                if isinstance(item, Exception):
                    if isinstance(item, (DeadlineExceeded,
                                         EngineShutdown,
                                         RequestRejected)):
                        raise item
                    raise RuntimeError(
                        f"continuous engine failed this request: {item}")
                if item == []:
                    break
                if eos_id is not None and eos_id in item:
                    item = item[:item.index(eos_id)]
                    toks.extend(item)
                    if item:
                        yielded = True
                        yield {"token_ids": item,
                               "text": prompt + self.tokenizer.decode(
                                   prior_ids + toks)}
                    break
                toks.extend(item)
                yielded = True
                yield {"token_ids": item,
                       "text": prompt + self.tokenizer.decode(
                           prior_ids + toks)}
            # collect + release the results entry (event already set by
            # the time the terminal item arrives; short timeout)
            self._front.wait(rid, timeout_s=60)
            finished = True
        finally:
            if not finished:
                self._front.abandon(rid)
                exc_type = sys.exc_info()[0]
                if (not yielded and exc_type is not None and issubclass(
                        exc_type, (DeadlineExceeded, RequestRejected))):
                    # expired/rejected BEFORE the first event: the
                    # exception propagates to the HTTP handler, which
                    # does this request's accounting (504/503 + the
                    # dedicated counters) — counting here too would
                    # double-book serve_requests_total and brand a shed
                    # request as a server failure
                    pass
                else:
                    # engine failure or client disconnect mid-stream:
                    # the 200 is already committed, so /metrics is the
                    # only place this failure can still be seen
                    self.record_metrics(failed=True)
        entry = {
            "prompt": prompt,
            "completion": prompt + self.tokenizer.decode(
                prior_ids + toks),
            "new_tokens": len(prior_ids) + len(toks),
            "latency_ms": round((time.perf_counter() - t0) * 1000.0, 2),
            "done": True,
        }
        if continuation is not None:
            entry["resumed"] = True
        # metrics count what THIS replica generated (a continuation's
        # prior tokens were another replica's work — counting them here
        # would double-book serve_generate_tokens_total fleet-wide)
        self.record_metrics(generate_entries=[
            {**entry, "new_tokens": len(toks)}],
            trace_id=(span.trace_id
                      if span is not None else None))
        yield entry

    def record_metrics(self, *, generate_entries=None, score: bool = False,
                       failed: bool = False,
                       trace_id: Optional[str] = None) -> None:
        """Fold one request into the shared registry (handler-thread
        safe — every metric holds its own lock). ``trace_id`` rides the
        latency histogram as the bucket's exemplar: the JSON snapshot
        links each latency bucket to a concrete trace in /traces."""
        m = self._obs
        m["serve_requests_total"].inc()
        if failed:
            m["serve_requests_failed_total"].inc()
        if score:
            m["serve_score_requests_total"].inc()
        if generate_entries:
            m["serve_generate_requests_total"].inc()
            m["serve_generate_tokens_total"].inc(sum(
                e.get("new_tokens", 0) for e in generate_entries))
            m["serve_generate_latency_ms"].observe(max(
                (e.get("latency_ms", 0.0) for e in generate_entries),
                default=0.0), exemplar=trace_id)

    def _legacy_metrics_text(self) -> str:
        """The pre-obs exposition names, aliased onto registry values —
        a strict superset guarantee for existing scrape configs. New
        dashboards should use the canonical ``serve_*`` families."""
        m = self._obs
        alias = [
            ("requests_total", "counter", m["serve_requests_total"].value),
            ("requests_failed_total", "counter",
             m["serve_requests_failed_total"].value),
            ("generate_tokens_total", "counter",
             m["serve_generate_tokens_total"].value),
            ("generate_latency_ms_sum", "counter",
             m["serve_generate_latency_ms"].sum),
            ("generate_requests_total", "counter",
             m["serve_generate_requests_total"].value),
            ("score_requests_total", "counter",
             m["serve_score_requests_total"].value),
        ]
        lines = []
        for key, kind, val in alias:
            name = f"pyspark_tf_gke_tpu_serve_{key}"
            lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name} "
                         f"{int(val) if float(val).is_integer() else val}")
        if self._front is not None:
            stats = self._front.engine.stats
            for key in ("queued", "active", "finished", "num_slots"):
                name = f"pyspark_tf_gke_tpu_serve_continuous_{key}"
                kind = "counter" if key == "finished" else "gauge"
                lines.append(f"# TYPE {name} {kind}")
                lines.append(f"{name} {stats[key]}")
            for key, val in (stats.get("prefix_cache") or {}).items():
                if not isinstance(val, (int, float)):
                    continue  # the radix stats carry a "kind" tag —
                    #           not a number, not exposable
                name = ("pyspark_tf_gke_tpu_serve_continuous_"
                        f"prefix_cache_{key}")
                kind = ("counter" if key in ("hits", "misses",
                                             "hit_tokens", "evictions")
                        else "gauge")
                lines.append(f"# TYPE {name} {kind}")
                lines.append(f"{name} {val}")
        return "\n".join(lines) + "\n"

    def _refresh_engine_gauges(self) -> None:
        """Pull-model scrape prep: the engine only updates its gauges
        at collect boundaries, so re-read them at exposition time."""
        if self._front is not None:
            stats = self._front.engine.stats
            self._obs["serve_slots_total"].set(stats["num_slots"])
            self._obs["serve_slots_active"].set(stats["active"])
            self._obs["serve_queue_depth"].set(stats["queued"])
            for name, t in (stats.get("tenants") or {}).items():
                self._obs["serve_tenant_queue_depth"].labels(
                    tenant=name).set(t["queued"])

    def metrics_text(self) -> str:
        """Prometheus exposition text: the full shared registry
        (train_/serve_/runtime_ families) plus the legacy alias block."""
        self._refresh_engine_gauges()
        return self.registry.exposition() + self._legacy_metrics_text()

    def _entry(self, prompt, new_tokens, dt_ms, eos_id, **extra) -> dict:
        """Shared response assembly: eos truncation + decode back to
        text (one definition for the batched and speculative paths)."""
        if eos_id is not None and eos_id in new_tokens:
            new_tokens = new_tokens[:new_tokens.index(eos_id)]
        return {
            "prompt": prompt,
            "completion": prompt + self.tokenizer.decode(new_tokens),
            "new_tokens": len(new_tokens),
            "latency_ms": round(dt_ms, 2),
            **extra,
        }

    # -- scoring ---------------------------------------------------------

    def score(self, texts, tenant: str = "default") -> list:
        """Per-text total NLL in nats + scored token count. Texts longer
        than max_seq_len are truncated (reported via ``truncated``);
        texts shorter than 2 tokens have no next-token NLL and come back
        ``{"skipped": true, "tokens": 0}`` rather than failing the
        batch (remote perplexity eval feeds arbitrary documents).
        With a ``--tenants`` spec, the batch's scored-token total is
        charged against the tenant's quota bucket up front (exact
        work, no refund) — score is not an unmetered side door around
        a generate throttle."""
        if not texts:
            return []
        if len(texts) > MAX_BATCH:
            raise ValueError(f"batch of {len(texts)} exceeds "
                             f"max batch {MAX_BATCH}")
        cap = self.model.cfg.max_seq_len
        results = [None] * len(texts)
        rows = []  # (result index, ids, truncated)
        for i, text in enumerate(texts):
            ids = self.tokenizer.encode(text)
            if len(ids) < 2:
                results[i] = {"nll": 0.0, "tokens": 0, "truncated": False,
                              "skipped": True}
                continue
            rows.append((i, ids[:cap], len(ids) > cap))
        if rows and self._front is not None:
            self._front.charge_tokens(
                tenant, sum(len(ids) for _, ids, _ in rows))
        if rows:
            lengths = [len(ids) for _, ids, _ in rows]
            seq_len = _bucket(max(lengths), cap)
            # batch dim pads to a power-of-2 bucket too (dummy rows get
            # length 0 → fully masked), bounding compiled shapes
            n_real = len(rows)
            n_bucket = 1 << (n_real - 1).bit_length()
            padded = np.zeros((n_bucket, seq_len), np.int32)
            for r, (_, ids, _) in enumerate(rows):
                padded[r, :len(ids)] = ids
            lengths = lengths + [0] * (n_bucket - n_real)
            from pyspark_tf_gke_tpu.train.serving import mh_score

            with self._lock:
                # mh_score owns the single-vs-multi-host dispatch: it
                # announces for workers to replay when processes > 1 and
                # degrades to plain serve_score otherwise
                nlls = np.asarray(mh_score(
                    self.model, self.params, padded, lengths, self.mesh))
            for r, (i, ids, trunc) in enumerate(rows):
                results[i] = {"nll": float(nlls[r]), "tokens": len(ids) - 1,
                              "truncated": trunc}
        return results


# -- HTTP plumbing -----------------------------------------------------------


def _span_shed_event(span, exc: "RequestRejected") -> None:
    """The shed VERDICT on the request's span — skipped when the span
    already carries a terminal event: a hot-swap drained past its
    bound delivers a 'reloading' RequestRejected to an ADMITTED
    request whose ``terminal(outcome=shed)`` the engine's
    ``fail_outstanding`` already stamped, and a second verdict would
    read as a double delivery to the exactly-one-terminal checker
    (chaos/invariants.py). Admission-gate sheds never reach the
    engine, so they always emit here."""
    if span is None:
        return
    if any(e.get("name") == "terminal" for e in span.events):
        return
    span.event("shed", reason=exc.reason,
               **({"tenant": exc.tenant}
                  if getattr(exc, "tenant", None) else {}))


def _shed_headers(exc: RequestRejected):
    """Response headers for one shed: Retry-After always; per-tenant
    sheds also carry ``X-Tenant-Shed`` so the router can tell a tenant
    verdict (surface it, keep the replica in rotation) from replica
    overload (back the replica off)."""
    hdrs = [("Retry-After", str(exc.retry_after_s))]
    if getattr(exc, "tenant", None):
        hdrs.append(("X-Tenant-Shed", str(exc.tenant)))
    return tuple(hdrs)


def _shed_body(exc: RequestRejected) -> dict:
    body = {"error": str(exc), "reason": exc.reason}
    if getattr(exc, "tenant", None):
        body["tenant"] = exc.tenant
    return body


def _admin_token_error(server: BundleServer, headers):
    """THE admin-endpoint token gate, shared by ``/admin/reload`` and
    ``/admin/profile`` so the 403/401 discipline cannot drift between
    them: no ``SERVE_ADMIN_TOKEN`` on the server → the endpoint does
    not exist operationally (403); configured → the caller must
    present it in ``X-Admin-Token``, compared constant-time
    (hmac.compare_digest — a byte-wise ``!=`` would leak the token
    prefix-by-prefix through response timing). Returns ``(status,
    body)`` to reply with, or ``None`` when authorized."""
    if not server.admin_token:
        return 403, {"error": "admin endpoint disabled (set "
                              "SERVE_ADMIN_TOKEN to enable)"}
    import hmac

    if not hmac.compare_digest(headers.get("X-Admin-Token") or "",
                               server.admin_token):
        return 401, {"error": "bad or missing X-Admin-Token"}
    return None


def _make_handler(server: BundleServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        _span = None  # the request's trace span (POST paths set it)

        def log_message(self, fmt, *args):  # route through our logger
            logger.info("%s %s", self.address_string(), fmt % args)

        def _reply(self, code: int, payload: dict, headers=()):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if self._span is not None:
                # EVERY response (successes and 429/503/504 sheds
                # alike) echoes the trace id — a user report quoting
                # X-Request-Id joins straight to GET /traces
                self.send_header("X-Request-Id", self._span.trace_id)
                self._span.set("http.status", code)
            for name, value in headers:
                self.send_header(name, value)
            if self.close_connection:
                # advertise the close (http.server's send_error does the
                # same) so pooling clients don't reuse a dying socket
                self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)

        def _stream_generate(self, req, prompts, tenant="default"):
            """Server-sent events: one ``data:`` line per token group,
            a terminal entry with the assembled completion, then
            ``data: [DONE]``. Greedy single-prompt only (that's the
            slot-engine path tokens stream FROM); the connection closes
            at the end — no Content-Length on a stream."""
            if len(prompts) != 1:
                server.record_metrics(failed=True)
                return self._reply(
                    400, {"error": "streaming takes exactly one prompt"})
            if (float(req.get("temperature", 0.0) or 0.0) > 0
                    or req.get("num_beams") or req.get("top_k")
                    or req.get("top_p") or req.get("repetition_penalty")):
                server.record_metrics(failed=True)
                return self._reply(
                    400, {"error": "streaming is greedy-only (no "
                                   "sampling/beam parameters)"})
            deadline_ms = req.get("deadline_ms")
            continuation = req.get("continuation")
            if continuation is not None:
                # the router's mid-stream failover splice: the ORIGINAL
                # prompt plus the token ids a dead replica already
                # delivered — ids must be sane non-negative ints (the
                # length budget is checked with the full prefill in
                # generate_stream)
                try:
                    emitted = [int(t)
                               for t in continuation["emitted_ids"]]
                    if not emitted or any(t < 0 for t in emitted):
                        raise ValueError
                    continuation = {"emitted_ids": emitted}
                except (TypeError, KeyError, ValueError):
                    server.record_metrics(failed=True)
                    return self._reply(
                        400, {"error": "'continuation' must carry "
                                       "emitted_ids: a non-empty list "
                                       "of non-negative token ids"})
            try:
                events = server.generate_stream(
                    prompts[0],
                    max_new_tokens=int(req.get("max_new_tokens", 64)),
                    deadline_s=(float(deadline_ms) / 1000.0
                                if deadline_ms is not None else None),
                    tenant=tenant, continuation=continuation,
                    span=self._span)
                first = next(events)  # validation errors surface BEFORE
                #   the 200 status line is committed
            except RequestRejected as exc:
                _span_shed_event(self._span, exc)
                server.record_metrics()
                return self._reply(exc.status, _shed_body(exc),
                                   headers=_shed_headers(exc))
            except (TypeError, ValueError) as exc:
                server.record_metrics(failed=True)
                return self._reply(400, {"error": str(exc)})
            self.close_connection = True
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            if self._span is not None:
                self.send_header("X-Request-Id", self._span.trace_id)
                self._span.set("http.status", 200)
            self.end_headers()
            try:
                if self._span is not None:
                    # first SSE line: a comment carrying the trace id,
                    # so stream consumers (which never see response
                    # headers through some SSE clients) can still join
                    # the stream to /traces
                    self.wfile.write(
                        f": trace_id={self._span.trace_id}\n\n".encode())
            except OSError:
                pass
            try:
                for event in itertools.chain([first], events):
                    self.wfile.write(
                        f"data: {json.dumps(event)}\n\n".encode())
                    self.wfile.flush()
                self.wfile.write(b"data: [DONE]\n\n")
            except Exception as exc:  # noqa: BLE001 — mid-stream: the
                # status line is gone; emit an error event if the socket
                # still listens, else just drop (client sees the cut)
                logger.exception("stream failed mid-flight")
                try:
                    self.wfile.write(
                        f"data: {json.dumps({'error': str(exc)})}"
                        "\n\n".encode())
                    self.wfile.write(b"data: [DONE]\n\n")
                except OSError:
                    pass

        def do_GET(self):
            route = self.path.partition("?")[0]  # scrape configs may
            # append query params; routing must ignore them
            if route in ("/healthz", "/health", "/"):
                # draining → 503: the k8s readiness probe fails and the
                # Service stops routing here, while /metrics and /events
                # below keep answering (drain is exactly when you want
                # to watch the queue empty)
                return self._reply(503 if server.draining else 200,
                                   server.health())
            if route == "/livez":
                # LIVENESS, distinct from readiness: no engine lock,
                # no load math — 503 only when the driver loop itself
                # has stalled past live_stall_s (the k8s livenessProbe
                # target; draining answers 200 live)
                out = server.livez()
                return self._reply(200 if out["live"] else 503, out)
            if route == "/loadz":
                # the router's prober polls this every second per
                # replica: one dict assembly, no registry walk, no
                # Prometheus text parse on the other end. Draining
                # answers 200 — the field carries the state; the 503
                # convention stays on /healthz (readiness)
                return self._reply(200, server.loadz())
            # /metrics, /metrics.json, /events — the obs package owns
            # the response assembly; this server contributes the live
            # engine-gauge refresh and its legacy alias block
            extra = ""
            if route == "/metrics":
                server._refresh_engine_gauges()
                extra = server._legacy_metrics_text()
            front = getattr(server, "_front", None)
            out = handle_obs_request(self.path, server.registry,
                                     server.event_log,
                                     extra_exposition=extra,
                                     tracer=getattr(server, "tracer",
                                                    None),
                                     stepstats=(front.stepstats
                                                if front is not None
                                                else None))
            if out is None:
                return self._reply(404,
                                   {"error": f"unknown path {self.path}"})
            code, ctype, body = out
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            server._http_enter()  # drain() waits for this to reach zero
            tracer = getattr(server, "tracer", None)
            if tracer is not None:
                # adopt the caller's traceparent (the router's, or an
                # end client's) or mint a new root; malformed input
                # degrades to a fresh trace, never an error
                self._span = tracer.start_span(
                    "serve.request",
                    parent=self.headers.get("traceparent"),
                    attrs={"path": self.path.partition("?")[0]})
            try:
                with use_span(self._span):
                    self._do_POST()
            finally:
                if self._span is not None:
                    self._span.finish()
                # handler instances live per keep-alive CONNECTION, not
                # per request: a later GET on the same socket must not
                # echo (or stamp onto) this finished span
                self._span = None
                server._http_exit()

        def _do_POST(self):
            if server.draining:
                # shed BEFORE reading the body — the connection is
                # closing anyway, so the keep-alive desync the 413 path
                # guards against doesn't apply
                self.close_connection = True
                server.record_metrics()
                server._obs["serve_requests_rejected_total"].labels(
                    reason="draining").inc()
                exc = _draining_rejection()
                if self._span is not None:
                    self._span.event("shed", reason=exc.reason)
                return self._reply(
                    exc.status, {"error": str(exc), "reason": exc.reason},
                    headers=(("Retry-After", str(exc.retry_after_s)),))
            try:
                n = int(self.headers.get("Content-Length", 0))
                if n > MAX_BODY_BYTES:
                    # Replying without reading the body desyncs an
                    # HTTP/1.1 keep-alive stream (the unread bytes would
                    # parse as the next request) — drop the connection.
                    self.close_connection = True
                    server.record_metrics(failed=True)
                    return self._reply(413, {
                        "error": f"body too large ({n} bytes > "
                                 f"{MAX_BODY_BYTES})"})
                req = json.loads(self.rfile.read(n) or b"{}")
            except (ValueError, json.JSONDecodeError) as exc:
                server.record_metrics(failed=True)
                return self._reply(400, {"error": f"bad JSON body: {exc}"})
            try:
                # chaos: the BundleServer request-front fault point — a
                # fail rule lands in the generic handler below as an
                # explicit 500 error terminal (counted, never a hang);
                # a slow rule injects scheduled front latency
                chaos_fire("serve.request")
                deadline_ms = req.get("deadline_ms") if isinstance(
                    req, dict) else None
                deadline_s = (float(deadline_ms) / 1000.0
                              if deadline_ms is not None else None)
                # tenant identity: X-Tenant header wins, then the body
                # field, then "default" — one extraction point shared
                # by the blocking and streaming generate paths (the
                # router forwards the same header)
                tenant = self.headers.get("X-Tenant") or (
                    req.get("tenant") if isinstance(req, dict)
                    else None) or "default"
                if not isinstance(tenant, str):
                    server.record_metrics(failed=True)
                    return self._reply(
                        400, {"error": "'tenant' must be a string"})
                if self.path == "/v1/generate":
                    prompts = req.get("prompts")
                    if prompts is None and "prompt" in req:
                        prompts = [req["prompt"]]
                    if not isinstance(prompts, list) or not all(
                            isinstance(p, str) for p in prompts or [None]):
                        server.record_metrics(failed=True)
                        return self._reply(
                            400, {"error": "'prompts' must be a list of "
                                           "strings (or 'prompt': str)"})
                    seed = req.get("seed")
                    if seed is not None:
                        try:
                            seed = int(seed)
                        except (TypeError, ValueError):
                            server.record_metrics(failed=True)
                            return self._reply(
                                400, {"error": "'seed' must be an "
                                               "integer"})
                    if req.get("stream"):
                        return self._stream_generate(req, prompts,
                                                     tenant=tenant)
                    out = server.generate(
                        prompts,
                        max_new_tokens=int(req.get("max_new_tokens", 64)),
                        temperature=float(req.get("temperature", 0.0)),
                        top_k=req.get("top_k"),
                        top_p=req.get("top_p"),
                        num_beams=int(req.get("num_beams", 0)),
                        repetition_penalty=req.get("repetition_penalty"),
                        deadline_s=deadline_s, tenant=tenant,
                        seed=seed, span=self._span)
                    server.record_metrics(
                        generate_entries=out,
                        trace_id=(self._span.trace_id
                                  if self._span is not None else None))
                    self._reply(200, {"completions": out})
                elif self.path == "/v1/warm":
                    prefix = req.get("prefix")
                    if not isinstance(prefix, str):
                        server.record_metrics(failed=True)
                        return self._reply(
                            400, {"error": "'prefix' must be a string"})
                    out = server.warm_prefix(prefix)
                    server.record_metrics()
                    self._reply(200, out)
                elif self.path == "/admin/reload":
                    # bundle hot-swap (the coordinator's publish path).
                    # Token gate shared with /admin/profile
                    # (_admin_token_error): 403 unconfigured, 401
                    # mismatch. The reload itself serializes (409
                    # while one is in flight) and rolls back on failure.
                    err = _admin_token_error(server, self.headers)
                    if err is not None:
                        server.record_metrics()
                        server._obs["serve_bundle_reloads_total"].labels(
                            outcome="rejected").inc()
                        return self._reply(err[0], err[1])
                    bundle = req.get("bundle")
                    if not isinstance(bundle, str) or not bundle:
                        server.record_metrics(failed=True)
                        return self._reply(
                            400, {"error": "'bundle' must be a bundle "
                                           "directory path"})
                    generation = req.get("generation")
                    out = server.reload_bundle(
                        _resolve_bundle(bundle),
                        generation=generation,
                        canary=bool(req.get("canary", True)))
                    server.record_metrics()
                    self._reply(200, out)
                elif self.path == "/admin/profile":
                    # on-demand xprof capture over the next N busy
                    # engine steps — same token gate (403/401) and
                    # one-at-a-time 409 discipline as /admin/reload;
                    # 202: the capture is ARMED, completion lands on
                    # /events as profile_trace_written
                    err = _admin_token_error(server, self.headers)
                    if err is not None:
                        server.record_metrics()
                        return self._reply(err[0], err[1])
                    out = server.start_profile(
                        req.get("output_dir"),
                        steps=int(req.get("steps", 8)))
                    server.record_metrics()
                    self._reply(202, out)
                elif self.path == "/v1/prefill":
                    # disaggregated handoff, prefill side: warm +
                    # export the prompt's KV pages as one page blob
                    prompt = req.get("prompt")
                    if not isinstance(prompt, str):
                        server.record_metrics(failed=True)
                        return self._reply(
                            400, {"error": "'prompt' must be a string"})
                    out = server.prefill_export(prompt)
                    server.record_metrics()
                    self._reply(200, out)
                elif self.path == "/v1/kv_import":
                    # disaggregated handoff, decode side: install a
                    # transferred page blob + adopt it into the trie
                    blob = req.get("blob")
                    if not isinstance(blob, str):
                        server.record_metrics(failed=True)
                        return self._reply(
                            400, {"error": "'blob' must be a base64 "
                                           "string"})
                    out = server.kv_import(blob)
                    server.record_metrics()
                    self._reply(200, out)
                elif self.path == "/v1/score":
                    texts = req.get("texts")
                    if not isinstance(texts, list) or not all(
                            isinstance(t, str) for t in texts or [None]):
                        server.record_metrics(failed=True)
                        return self._reply(
                            400, {"error": "'texts' must be a list of "
                                           "strings"})
                    scores = server.score(texts, tenant=tenant)
                    server.record_metrics(score=True)
                    self._reply(200, {"scores": scores})
                else:
                    server.record_metrics(failed=True)
                    self._reply(404, {"error": f"unknown path {self.path}"})
            except RequestRejected as exc:
                # load shedding is not a server fault: counted in the
                # rejected{reason} family (incremented at the raise
                # site), not in requests_failed. Per-tenant sheds carry
                # the tenant in body + X-Tenant-Shed header; the shed
                # VERDICT lands on the trace (reason + whose quota) —
                # unless the engine already stamped the terminal
                server.record_metrics()
                _span_shed_event(self._span, exc)
                self._reply(exc.status, _shed_body(exc),
                            headers=_shed_headers(exc))
            except DeadlineExceeded as exc:
                # the dedicated deadline counter (incremented where the
                # expiry was detected) carries the signal
                server.record_metrics()
                self._reply(504, {"error": str(exc)})
            except ReloadInFlight as exc:
                server.record_metrics()
                server._obs["serve_bundle_reloads_total"].labels(
                    outcome="rejected").inc()
                self._reply(409, {"error": str(exc)})
            except ProfileInFlight as exc:
                server.record_metrics()
                self._reply(409, {"error": str(exc)})
            except BundleReloadError as exc:
                # the old generation is serving either way; the body
                # says whether a swap happened and was rolled back
                server.record_metrics(failed=True)
                self._reply(502, {
                    "error": str(exc),
                    "rolled_back": exc.rolled_back,
                    "bundle_generation": server.bundle_generation})
            except (TypeError, ValueError) as exc:
                # TypeError too: int(None)/float([]) from JSON null/list
                # field values is caller error, not a server fault
                server.record_metrics(failed=True)
                self._reply(400, {"error": str(exc)})
            except Exception as exc:  # noqa: BLE001 — keep the server up
                logger.exception("request failed")
                server.record_metrics(failed=True)
                self._reply(500, {"error": f"{type(exc).__name__}: {exc}"})

    return Handler


def start_http_server(server: BundleServer, host: str = "0.0.0.0",
                      port: int = 8000) -> ThreadingHTTPServer:
    """Bind and return the HTTP server (``port=0`` → ephemeral; read the
    bound port from ``.server_address[1]``). Caller runs
    ``serve_forever`` (the CLI) or a daemon thread (tests)."""
    httpd = ThreadingHTTPServer((host, port), _make_handler(server))
    return httpd


# -- CLI ---------------------------------------------------------------------


def parse_args(argv=None) -> argparse.Namespace:
    e = os.environ.get
    p = argparse.ArgumentParser(
        description="Serve an exported bundle over HTTP (or stdin)")
    p.add_argument("--bundle", default=e("BUNDLE_DIR"), required=e("BUNDLE_DIR") is None,
                   help="directory written by train/export.py (local or gs://)")
    p.add_argument("--host", default=e("SERVE_HOST", "0.0.0.0"))
    p.add_argument("--port", type=int, default=int(e("SERVE_PORT", "8000")))
    p.add_argument("--tp", type=int, default=int(e("SERVE_TP", "0")),
                   help="tensor-parallel ways (0/1 = single device)")
    p.add_argument("--int8-kv", action="store_true",
                   default=e("SERVE_INT8_KV", "") == "1",
                   help="serve with an int8 KV cache even if the bundle "
                        "wasn't exported with one")
    p.add_argument("--draft-bundle", default=e("DRAFT_BUNDLE_DIR", ""),
                   help="a smaller bundle (same tokenizer/vocab) used as "
                        "the speculative-decoding draft for single-prompt "
                        "greedy requests — identical tokens, lower latency")
    p.add_argument("--continuous-slots", type=int,
                   default=int(e("CONTINUOUS_SLOTS", "0")),
                   help="enable continuous batching with this many KV "
                        "slots (0 = whole-batch serving). Greedy "
                        "requests from ALL connections share the slot "
                        "pool; composes with --tp and multi-host "
                        "(device ops replayed over the announce wire)")
    p.add_argument("--prefix-cache", type=int,
                   default=int(e("PREFIX_CACHE", "0")),
                   help="prefix caching (0 = off; requires "
                        "--continuous-slots). PAGED bundles get the "
                        "engine-level radix cache over the KV page "
                        "pool — completed prompts stay resident as "
                        "refcounted pages, same-prefix admissions "
                        "share them copy-on-write and prefill only "
                        "the suffix; the value caps the cache's "
                        "RESIDENT pages (use the pool size for "
                        "whole-pool caching; composes with "
                        "multi-host). Dense bundles keep the batch-1 "
                        "LRU with this many entries (POST /v1/warm; "
                        "single-host)")
    p.add_argument("--prefill-chunk", "--prefill-chunk-tokens",
                   dest="prefill_chunk", type=int,
                   default=int(e("PREFILL_CHUNK", "0")),
                   help="chunked prefill: admit prompts longer than "
                        "this in bounded pieces with decode chunks "
                        "interleaved (0 = whole-prompt prefill; "
                        "requires --continuous-slots; paged engines "
                        "write pieces straight into the page pool and "
                        "replay chunk progress over the multi-host "
                        "wire; dense engines are single-host)")
    p.add_argument("--step-token-budget", type=int,
                   default=int(e("STEP_TOKEN_BUDGET", "0")),
                   help="cap the tokens one engine step dispatches, "
                        "split between one prefill piece and the "
                        "decode chunk (live_slots x steps) — bounds "
                        "time-between-tokens under long-prompt "
                        "arrivals (0 = off; pair with "
                        "--prefill-chunk)")
    p.add_argument("--continuous-chunk", type=int,
                   default=int(e("CONTINUOUS_CHUNK", "8")),
                   help="decode steps per engine dispatch between "
                        "admission points")
    p.add_argument("--spec-tokens", type=int,
                   default=int(e("SERVE_SPEC_TOKENS", "0")),
                   help="in-engine speculative decoding: draft k "
                        "tokens per slot per round, verify all k+1 in "
                        "ONE multi-query forward — greedy token-exact, "
                        ">1 token per verify when the draft agrees "
                        "(0 = off; requires --continuous-slots; uses "
                        "--draft-bundle as the draft, else the target "
                        "SELF-drafts, which is correctness-only; "
                        "draft+verify tokens count against "
                        "--step-token-budget; accept rate on /loadz "
                        "spec_accept_rate)")
    def _pipeline_depth(v: str) -> int:
        n = int(v)
        if not 0 <= n <= 4:
            # fail fast at argparse time, not after the bundle loads;
            # depth beyond a few chunks only adds token latency and
            # discarded post-eos decode work
            raise argparse.ArgumentTypeError(
                f"--continuous-pipeline must be 0..4, got {n}")
        return n

    p.add_argument("--continuous-pipeline", type=_pipeline_depth,
                   default=int(e("CONTINUOUS_PIPELINE", "1")),
                   help="decode-ahead depth: keep up to N dispatched "
                        "chunks un-collected so step N's host work "
                        "(scheduling, collect bookkeeping, delivery) "
                        "overlaps the in-flight chunk's compute "
                        "(default 1 — the async engine core; 0 = the "
                        "serial A/B reference loop; the gain on a "
                        "local chip is not measured; depth >=2 is "
                        "single-host only — the engine enforces it; "
                        "multi-host: "
                        "the chunk is announced dispatch-only and the "
                        "gathers replay at OP_CB_COLLECT)")
    p.add_argument("--schedule", choices=("fifo", "longest"),
                   default=e("CB_SCHEDULE", "fifo"),
                   help="slot admission policy: fifo (arrival order) or "
                        "longest (LPT: longest remaining budget first — "
                        "smaller makespan / higher chip utilization, at "
                        "the cost of short-request queueing latency)")
    p.add_argument("--adaptive-chunk", action="store_true",
                   default=e("ADAPTIVE_CHUNK", "") not in ("", "0"),
                   help="budget-aligned chunking: size each engine "
                        "dispatch to the minimum remaining token budget "
                        "over the active slots (bucketed powers of two "
                        "down to 8), so a slot whose request ends at its "
                        "budget frees at the earliest collect instead of "
                        "decoding dead rows to the end of a fixed chunk")
    p.add_argument("--max-queue-depth", type=int,
                   default=int(e("MAX_QUEUE_DEPTH", "0")),
                   help="bounded admission: shed (HTTP 429 + "
                        "Retry-After) once this many requests wait for "
                        "a KV slot (0 = unbounded); overload degrades "
                        "to fast rejection instead of collapse")
    p.add_argument("--max-queued-tokens", type=int,
                   default=int(e("MAX_QUEUED_TOKENS", "0")),
                   help="bounded admission by token budget: shed when "
                        "queued prompt+budget tokens would exceed this "
                        "(0 = unbounded)")
    p.add_argument("--tenants", default=e("SERVE_TENANTS", ""),
                   help="multi-tenant fairness/quota spec: JSON "
                        "('{\"light\": {\"weight\": 3}, \"noisy\": "
                        "{\"weight\": 1, \"rate\": 200, \"burst\": "
                        "400}}') or compact "
                        "name=weight[:rate[:burst]],... — weights "
                        "drive DWRR admission shares and each "
                        "tenant's slice of --max-queue-depth/"
                        "--max-queued-tokens; rate (tokens/sec) + "
                        "burst build per-tenant token buckets "
                        "(429 + Retry-After from the tenant's own "
                        "refill; other tenants keep admitting). A "
                        "'*' entry configures unlisted tenants. "
                        "Empty = tenancy off (global bounds)")
    p.add_argument("--trace-sample", type=float,
                   default=float(e("TRACE_SAMPLE", "0.01")),
                   help="fraction of requests whose traces are "
                        "RETAINED in the /traces flight recorder "
                        "(0..1). Ids always propagate (traceparent "
                        "in, X-Request-Id out) regardless; 0 with "
                        "--trace-slow-ms 0 disables recording "
                        "entirely (id propagation only)")
    p.add_argument("--trace-slow-ms", type=float,
                   default=float(e("TRACE_SLOW_MS", "1000")),
                   help="always-on slow capture: any request slower "
                        "than this is retained in /traces even when "
                        "the sampler skipped it — tail latency is "
                        "never lost to sampling (0 = off)")
    p.add_argument("--drain-timeout", type=float,
                   default=float(e("DRAIN_TIMEOUT", "30")),
                   help="seconds SIGTERM waits for in-flight requests "
                        "before exiting; pair with a k8s "
                        "terminationGracePeriodSeconds comfortably "
                        "above it (see infra/k8s/tpu/tpu-serve.yaml)")
    p.add_argument("--chaos", default=e("SERVE_CHAOS", ""),
                   help="serve-side fault injection: legacy driver-"
                        "loop tokens (fail@STEP / slow@STEP:SECONDS, "
                        "e.g. 'fail@50,slow@80:0.5' — the engine-"
                        "rebuild path) and/or NAMED fault points "
                        "(POINT:ACTION@N / POINT:ACTION%%P, e.g. "
                        "'engine.device_step:hang@3:2,"
                        "serve.request:fail%%0.05,seed=7' — see "
                        "docs/CHAOS.md for the point catalog); "
                        "NEVER set in production")
    p.add_argument("--step-record-ring", type=int,
                   default=int(e("SERVE_STEP_RECORD_RING", "256")),
                   help="step telemetry: keep the last N engine-step "
                        "records (per-phase timing + batch "
                        "composition) in the GET /stepz ring; the "
                        "windowed host-overhead fraction rides /loadz "
                        "as step_host_overhead_frac (continuous-slots "
                        "mode only)")
    p.add_argument("--role", choices=("mixed", "prefill", "decode"),
                   default=e("SERVE_ROLE", "mixed"),
                   help="disaggregated serving role, advertised on "
                        "/loadz: the router sends long-prompt "
                        "admissions to 'prefill' replicas (chunked "
                        "prefill + KV-page export) and generate "
                        "traffic to 'decode'/'mixed' ones. Advisory — "
                        "every role serves every endpoint, so a "
                        "degraded fleet falls back cleanly")
    p.add_argument("--peak-flops", type=float,
                   default=float(e("SERVE_PEAK_FLOPS", "0")),
                   help="per-chip peak FLOPs/sec for the serve_mfu "
                        "gauge (e.g. 1.97e14 for v5e bf16); 0 = MFU "
                        "disabled — the CPU default, where a peak "
                        "number would be meaningless")
    p.add_argument("--step-timeout", type=float,
                   default=float(e("SERVE_STEP_TIMEOUT", "0")),
                   help="step watchdog: when one engine step (device "
                        "dispatch) runs longer than this many "
                        "seconds, every in-flight request is failed "
                        "with an explicit error terminal and the "
                        "engine rebuilds when the step returns — a "
                        "hung device step costs bounded client "
                        "latency instead of a wedged loop (0 = off; "
                        "size WELL above worst-case compile + chunk "
                        "time)")
    p.add_argument("--live-stall", type=float,
                   default=float(e("SERVE_LIVE_STALL", "120")),
                   help="GET /livez answers 503 once the engine "
                        "driver loop has not completed an iteration "
                        "for this many seconds (the k8s livenessProbe "
                        "target; 0 disables the stall check)")
    p.add_argument("--heartbeat-file", default=e("HEARTBEAT_FILE", ""),
                   help="node-local path the engine DRIVER LOOP beats "
                        "(train/resilience.Heartbeat); the k8s liveness "
                        "probe watches its age, catching a wedged "
                        "device loop that /healthz (answered from an "
                        "HTTP thread) cannot see. Continuous-slots "
                        "mode only")
    p.add_argument("--metrics-textfile", default=e("METRICS_TEXTFILE", ""),
                   help="also export the metrics registry to this .prom "
                        "file every --metrics-interval seconds (atomic "
                        "rename; point node-exporter's textfile collector "
                        "at the directory — scraping without a Service)")
    p.add_argument("--metrics-interval", type=float,
                   default=float(e("METRICS_INTERVAL", "15")))
    p.add_argument("--stdin", action="store_true",
                   help="serve stdin lines instead of HTTP: each input "
                        "line is a prompt, each output line a JSON result")
    p.add_argument("--max-new-tokens", type=int,
                   default=int(e("MAX_NEW_TOKENS", "64")))
    p.add_argument("--temperature", type=float,
                   default=float(e("TEMPERATURE", "0.0")))
    # multi-host: same bootstrap flags as the trainers. Process 0 runs
    # the HTTP server; the rest replay announced requests
    # (train/serving.py serve_worker_loop). Greedy decode only.
    p.add_argument("--num-processes", type=int,
                   default=int(e("NUM_PROCESSES", "1")))
    p.add_argument("--process-id", type=int,
                   default=int(e("PROCESS_ID", "-1")))
    p.add_argument("--coordinator-addr", default=e("COORDINATOR_ADDR", ""))
    p.add_argument("--coordinator-port", type=int,
                   default=int(e("COORDINATOR_PORT", "8476")))
    return p.parse_args(argv)


def _resolve_bundle(path: str) -> str:
    """gs:// bundles are pulled to a local spool first (orbax restores
    from a directory tree; the CSV/TFRecord loaders stream, but a
    one-time bundle pull is the right trade for serving)."""
    if "://" not in path:
        return path
    import tempfile

    from pyspark_tf_gke_tpu.utils.fs import fs_copy_tree

    local = tempfile.mkdtemp(prefix="bundle-")
    logger.info("pulling %s -> %s", path, local)
    fs_copy_tree(path, local)
    return local


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.num_processes > 1:
        from pyspark_tf_gke_tpu.parallel.distributed import (
            initialize_distributed,
        )

        initialize_distributed(
            num_processes=args.num_processes,
            process_id=args.process_id,
            coordinator_addr=args.coordinator_addr,
            coordinator_port=args.coordinator_port)
    mesh = None
    if jax.process_count() > 1:
        # one mesh over ALL global devices: tp as asked, dp on the rest
        # (the -1 wildcard gives a clear divisibility error for bad --tp)
        from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"dp": -1, "tp": max(args.tp, 1)}, jax.devices())
    elif args.tp and args.tp > 1:
        from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"tp": args.tp}, jax.devices()[:args.tp])
    server = BundleServer(
        _resolve_bundle(args.bundle), mesh=mesh, int8_kv=args.int8_kv,
        draft_bundle_dir=(_resolve_bundle(args.draft_bundle)
                          if args.draft_bundle else ""),
        continuous_slots=args.continuous_slots,
        continuous_chunk=args.continuous_chunk,
        prefix_cache_size=args.prefix_cache,
        prefill_chunk=args.prefill_chunk,
        step_token_budget=args.step_token_budget,
        continuous_pipeline=args.continuous_pipeline,
        adaptive_chunk=args.adaptive_chunk,
        schedule=args.schedule,
        max_queue_depth=args.max_queue_depth,
        max_queued_tokens=args.max_queued_tokens,
        chaos_spec=args.chaos,
        heartbeat_file=args.heartbeat_file,
        tenants_spec=args.tenants,
        trace_sample=args.trace_sample,
        trace_slow_ms=args.trace_slow_ms,
        step_timeout_s=args.step_timeout,
        live_stall_s=args.live_stall,
        spec_tokens=args.spec_tokens,
        step_record_ring=args.step_record_ring,
        peak_flops=args.peak_flops,
        role=args.role,
        # env-only by design: a token flag would leak into ps output
        # and pod specs; the k8s manifest mounts it from a Secret
        admin_token=os.environ.get("SERVE_ADMIN_TOKEN", ""))
    if args.chaos:
        logger.warning("serve-side chaos injection ACTIVE: %s", args.chaos)
    logger.info("bundle loaded: %s", server.health())
    exporter = None
    if args.metrics_textfile:
        from pyspark_tf_gke_tpu.obs.export import TextfileExporter

        exporter = TextfileExporter(server.registry, args.metrics_textfile,
                                    args.metrics_interval).start()
    if jax.process_count() > 1:
        # fail a misdeploy (draft bundle on some processes only) at
        # startup, not mid-collective on the first speculative request
        from pyspark_tf_gke_tpu.train.serving import sync_serving_config

        sync_serving_config(server.draft_model is not None)

    if jax.process_count() > 1 and jax.process_index() != 0:
        # workers: no HTTP socket — replay every announced request until
        # process 0 shuts the job down
        from pyspark_tf_gke_tpu.train.serving import serve_worker_loop

        if threading.current_thread() is threading.main_thread():
            import signal

            # a rolling restart SIGTERMs EVERY pod: a worker dying
            # immediately would sever the announce wire while pod 0 is
            # still draining, failing the very in-flight requests the
            # grace window protects. Ignore it — the loop ends when
            # process 0 announces shutdown (end of its drain), and the
            # k8s SIGKILL at the end of the grace period is the
            # backstop for a wedged drain.
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
        served = serve_worker_loop(server.model, server.params, server.mesh,
                                   draft_model=server.draft_model,
                                   draft_params=server.draft_params)
        logger.info("worker loop done after %d requests", served)
        return 0

    try:
        # ONE finally covers everything process 0 does from here: a
        # failure anywhere (port already bound, broken stdin pipe, ...)
        # must still release the worker loops, or a local error becomes
        # a pod-wide jax.distributed fatal cascade.
        if args.stdin:
            for line in sys.stdin:
                prompt = line.rstrip("\n")
                if not prompt:
                    continue
                try:
                    out = server.generate(
                        [prompt], max_new_tokens=args.max_new_tokens,
                        temperature=args.temperature)[0]
                except ValueError as exc:
                    # a bad line (over-long, zero tokens) must not take
                    # the loaded model down with it — mirror the HTTP
                    # 400 path
                    out = {"prompt": prompt, "error": str(exc)}
                print(json.dumps(out), flush=True)
            return 0

        httpd = start_http_server(server, args.host, args.port)
        logger.info(
            "serving on http://%s:%d (healthz, /v1/generate, /v1/score)",
            *httpd.server_address[:2])

        def _drain_then_stop():
            # graceful drain (the k8s rolling-restart contract):
            # readiness flips to draining → admission stops → in-flight
            # requests finish (bounded by --drain-timeout) → the accept
            # loop stops → main() falls through its finally and exits 0
            server.begin_drain()
            drained = server.drain(args.drain_timeout)
            logger.info("drain %s after SIGTERM; stopping HTTP server",
                        "complete" if drained else
                        f"TIMED OUT at {args.drain_timeout}s")
            httpd.shutdown()

        if threading.current_thread() is threading.main_thread():
            import signal

            signal.signal(
                signal.SIGTERM,
                lambda signum, frame: threading.Thread(
                    target=_drain_then_stop, name="drain",
                    daemon=True).start())
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            logger.info("shutting down")
            httpd.shutdown()
        return 0
    finally:
        if exporter is not None:
            exporter.stop()  # final write captures the shutdown state
        if server._front is not None:
            server._front.shutdown()
        if jax.process_count() > 1:
            from pyspark_tf_gke_tpu.train.serving import announce_shutdown

            announce_shutdown()  # release the worker loops


if __name__ == "__main__":
    from pyspark_tf_gke_tpu.obs.compiles import install_compile_listener
    from pyspark_tf_gke_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    install_compile_listener()
    sys.exit(main())
