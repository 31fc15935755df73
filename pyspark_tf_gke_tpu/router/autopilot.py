"""Autopilot: the closed-loop, chaos-hardened fleet controller.

The watchtower (``router/watchtower.py``) already measures everything
an autoscaler needs — per-sweep fleet rollups (demand tokens, queue
delay, prefix hit rates, bundle generations) and a burn-rate alert
plane — and the capacity model (``replay/capacity.py``) already turns
demand into a replica count. This module closes the loop: a control
thread that reads ``/fleetz`` + ``/alertz`` shaped snapshots, runs
:func:`plan_replicas` over the CALIBRATED model, and actuates scale
decisions through a pluggable :class:`Actuator`.

Robustness is the design center, not an afterthought:

* **Rails** — ``min_replicas``/``max_replicas`` clamp every ask; the
  clamp is visible (a ``rails`` veto) rather than silent.
* **Hysteresis** — scale-down needs ``desired < up`` to hold
  CONTINUOUSLY for ``stabilization_s`` (default 300 s, mirroring the
  HPA's ``stabilizationWindowSeconds`` so the two controllers never
  fight); scale-up is immediate — under-capacity hurts now,
  over-capacity only costs money.
* **Cooldown** — after any applied action the loop holds for
  ``cooldown_s`` so it observes the fleet it just changed before
  changing it again.
* **Do-no-harm vetoes** — scale-down is refused outright while any
  SLO alert is pending/firing (shrinking a burning fleet converts an
  alert into an outage) or while a rollout is mid-publish (mixed
  ``bundle_generations``: eviction would fight the coordinator).
* **Prefix-affinity-aware placement** — scale-down evicts the replica
  whose radix cache is doing the least good (lowest measured
  ``prefix_hit_rate``) and DRAINS it (SIGTERM path: in-flight work
  finishes) instead of killing it; scale-up pre-warms the new replica
  (``/v1/warm``) before registering it so its first routed request
  doesn't pay the cold prefill.
* **Exactly-once actuation** — every actuation attempt passes the
  ``autopilot.actuate`` chaos point and is retried with exponential
  backoff on transient failure; applied work is tracked PER STEP
  (``applied_steps``/``added``) so a retry finishes the remainder and
  an already-applied decision id is never applied twice.
* **Provenance** — every decision carries the rollup snapshot and the
  capacity plan that justified it, emitted as an ``autopilot_decision``
  event and an ``autopilot.tick`` span; a postmortem can replay WHY
  the fleet changed size, not just that it did.

Deployment shapes: in-process on the router (``--autopilot recommend``
— dry-run decisions as events/metrics, the k8s HPA remains the
degraded fallback and operators A/B the two), or driving a
:class:`LocalFleetActuator` in tests/benches where the decisions
actually start and drain replica processes.

Stdlib-only and jax-free, like the rest of the router tier.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
import urllib.request
from collections import deque
from typing import Callable, List, Optional, Sequence, Tuple

from pyspark_tf_gke_tpu.chaos.inject import chaos_fire
from pyspark_tf_gke_tpu.obs.events import get_event_log
from pyspark_tf_gke_tpu.obs.metrics import autopilot_families
from pyspark_tf_gke_tpu.replay.capacity import FleetModel, plan_replicas
from pyspark_tf_gke_tpu.router.discovery import UP
from pyspark_tf_gke_tpu.router.watchtower import FIRING, PENDING
from pyspark_tf_gke_tpu.utils.logging import get_logger

logger = get_logger("router.autopilot")

# every decision record's key set, in order (tests pin this — the
# provenance contract: docs/AUTOPILOT.md "Decision vocabulary")
DECISION_KEYS = (
    "kind", "id", "t_s", "action", "from", "to", "victim", "added",
    "applied_steps", "applied", "vetoes", "reason", "plan", "rollup",
    "alerts_active",
)

# the veto vocabulary (autopilot_vetoes_total's reason label)
VETO_REASONS = ("alerts_active", "rollout_in_progress", "stabilization",
                "cooldown", "rails", "no_victim")

ACTIONS = ("none", "scale_up", "scale_down")


def load_fleet_model(spec: str = "") -> FleetModel:
    """Build the capacity :class:`FleetModel` from a CLI/env spec:
    empty = the conservative defaults, else inline JSON or ``@path``
    (e.g. a ``calibrate_rates`` dump — keys that aren't FleetModel
    fields, like the dump's measurement metadata, are dropped)."""
    if not spec:
        return FleetModel().validate()
    if spec.startswith("@"):
        with open(spec[1:]) as fh:
            data = json.load(fh)
    else:
        data = json.loads(spec)
    if not isinstance(data, dict):
        raise ValueError("FleetModel spec must be a JSON object")
    fields = {f.name for f in dataclasses.fields(FleetModel)}
    return FleetModel(
        **{k: v for k, v in data.items() if k in fields}).validate()


# -- actuators ---------------------------------------------------------------


class Actuator:
    """The actuation contract. ``scale_up`` provisions + pre-warms +
    registers ONE replica and returns its URL (``None`` when nothing
    concrete was provisioned — the dry-run case); ``scale_down``
    deregisters + drains ``victim`` and returns once it can take no
    new work. Both must tolerate being re-invoked after a mid-flight
    failure (the autopilot retries with per-step tracking)."""

    name = "noop"

    def scale_up(self, decision: dict) -> Optional[str]:
        return None

    def scale_down(self, decision: dict, victim: str) -> bool:
        return True


class RecommendActuator(Actuator):
    """Dry-run actuation: the decision is PUBLISHED (an
    ``autopilot_recommendation`` event per step, and the in-memory
    ``recommendations`` list for tests), never applied. This is the
    k8s shape — the HPA keeps actuating as the degraded fallback
    while operators A/B its moves against the autopilot's."""

    name = "recommend"

    def __init__(self, event_log=None):
        self.event_log = (event_log if event_log is not None
                          else get_event_log())
        self.recommendations: List[dict] = []

    def _emit(self, decision: dict, **extra) -> None:
        rec = {"id": decision["id"], "action": decision["action"],
               "from": decision["from"], "to": decision["to"], **extra}
        self.recommendations.append(rec)
        self.event_log.emit("autopilot_recommendation", **rec)

    def scale_up(self, decision: dict) -> Optional[str]:
        self._emit(decision)
        return None

    def scale_down(self, decision: dict, victim: str) -> bool:
        self._emit(decision, victim=victim)
        return True


def _post_json(url: str, body: dict, headers: Optional[dict] = None,
               timeout_s: float = 60.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read())


class LocalFleetActuator(Actuator):
    """Real actuation against a :class:`router.localfleet.LocalFleet`
    and its router's admin plane — the shape every scale test drives.

    Scale-up: boot a fresh replica process, pre-warm it DIRECTLY
    (``/v1/warm`` with the configured hot prefixes — the warm happens
    before registration so the first routed request finds a hot radix
    cache and no cold JIT), then register it with the router (token-
    gated ``POST /admin/replicas``). Scale-down: deregister FIRST (no
    new work routes to it), then SIGTERM-drain; a drain that hangs
    past ``drain_timeout_s`` escalates to SIGKILL — a stuck eviction
    must not wedge the control loop."""

    name = "localfleet"

    def __init__(self, fleet, *, admin_token: str,
                 router_url: Optional[str] = None,
                 warm_prefixes: Sequence[str] = (),
                 drain_timeout_s: float = 30.0,
                 timeout_s: float = 120.0):
        self.fleet = fleet
        self.router_url = (router_url or fleet.url).rstrip("/")
        self.admin_token = admin_token
        self.warm_prefixes = tuple(warm_prefixes)
        self.drain_timeout_s = float(drain_timeout_s)
        self.timeout_s = float(timeout_s)

    def _admin(self, body: dict) -> dict:
        return _post_json(self.router_url + "/admin/replicas", body,
                          headers={"X-Admin-Token": self.admin_token},
                          timeout_s=self.timeout_s)

    def scale_up(self, decision: dict) -> Optional[str]:
        url = self.fleet.start_replica()
        for prefix in (decision.get("warm_prefixes")
                       or self.warm_prefixes):
            try:
                _post_json(url + "/v1/warm", {"prefix": prefix},
                           timeout_s=self.timeout_s)
            except Exception as exc:  # noqa: BLE001 — warm is advisory
                # a failed pre-warm costs one cold prefill, not the
                # scale-up: register the replica anyway
                logger.warning("pre-warm of %s failed: %s", url, exc)
                break
        self._admin({"add": [url]})
        return url

    def scale_down(self, decision: dict, victim: str) -> bool:
        self._admin({"remove": [victim]})
        try:
            i = self.fleet.replica_urls.index(victim)
        except ValueError:
            return True  # already gone: a retried step stays idempotent
        if not self.fleet.drain_replica(i,
                                        timeout_s=self.drain_timeout_s):
            logger.warning("drain of %s hung > %.0fs; escalating to "
                           "SIGKILL", victim, self.drain_timeout_s)
            self.fleet.kill_replica(i)
        return True


# -- the control loop --------------------------------------------------------


class Autopilot:
    """One decision pass per tick: measure -> plan -> guard -> actuate.

    ``source`` is a zero-arg callable returning ``(fleetz, alertz)``
    dicts in the watchtower's wire shapes (in-process:
    ``lambda: (wt.fleetz(n=1), wt.alertz())``; remote: two HTTP GETs).
    Tests drive :meth:`tick` directly with scripted snapshots and an
    injected ``clock``."""

    def __init__(self, model: FleetModel, *,
                 source: Callable[[], Tuple[dict, dict]],
                 actuator: Actuator,
                 min_replicas: int = 1, max_replicas: int = 8,
                 tick_s: float = 15.0,
                 stabilization_s: float = 300.0,
                 cooldown_s: float = 60.0,
                 drain_target_s: float = 5.0,
                 queue_delay_target_ms: float = 500.0,
                 actuate_retries: int = 3,
                 retry_backoff_s: float = 0.5,
                 registry=None, event_log=None, tracer=None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Optional[Callable[[float], None]] = None):
        self.model = model.validate()
        self.source = source
        self.actuator = actuator
        if not 1 <= int(min_replicas) <= int(max_replicas):
            raise ValueError("need 1 <= min_replicas <= max_replicas")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.tick_s = max(0.1, float(tick_s))
        self.stabilization_s = max(0.0, float(stabilization_s))
        self.cooldown_s = max(0.0, float(cooldown_s))
        self.drain_target_s = float(drain_target_s)
        self.queue_delay_target_ms = float(queue_delay_target_ms)
        self.actuate_retries = max(0, int(actuate_retries))
        self.retry_backoff_s = max(0.0, float(retry_backoff_s))
        self._obs = autopilot_families(registry)
        self.event_log = (event_log if event_log is not None
                          else get_event_log())
        self.tracer = tracer
        self.clock = clock
        self._stop = threading.Event()
        self._sleep = sleep if sleep is not None else self._stop.wait
        self._thread: Optional[threading.Thread] = None
        self._seq = 0
        self._below_since: Optional[float] = None  # hysteresis anchor
        self._last_action_t: Optional[float] = None
        self._applied: set = set()      # decision ids actuated, ever
        self._applied_ring: deque = deque(maxlen=256)
        self.decisions: deque = deque(maxlen=256)  # provenance ring

    # -- decision engine -------------------------------------------------

    @staticmethod
    def _active_alerts(alertz: dict) -> List[str]:
        return [a.get("name", "?") for a in (alertz or {}).get(
            "alerts", []) if a.get("state") in (PENDING, FIRING)]

    @staticmethod
    def _coldest(replicas: dict) -> Optional[str]:
        """Scale-down placement: among UP replicas, the one whose
        radix cache is doing the least good — lowest measured
        ``prefix_hit_rate``, ties broken by least outstanding work
        (its eviction strands the fewest in-flight tokens)."""
        up = [(rid, snap) for rid, snap in (replicas or {}).items()
              if snap.get("state") == UP]
        if not up:
            return None
        return min(up, key=lambda kv: (
            float(kv[1].get("prefix_hit_rate") or 0.0),
            int(kv[1].get("queued") or 0) + int(kv[1].get("active")
                                                or 0)))[0]

    def decide(self, fleetz: dict, alertz: dict) -> dict:
        """One closed-form decision over one snapshot pair. Pure with
        respect to the FLEET (no actuation) but it advances the
        hysteresis clock — call once per tick."""
        now = self.clock()
        rollup = (fleetz or {}).get("fleet") or {}
        replicas = (fleetz or {}).get("replicas") or {}
        up = int(rollup.get("up") or 0)
        plan = plan_replicas(
            self.model,
            demand_tokens=float(rollup.get("demand_tokens_total")
                                or 0.0),
            queue_delay_ms=rollup.get("queue_delay_ms_max"),
            replicas_up=up,
            min_replicas=self.min_replicas,
            max_replicas=self.max_replicas,
            drain_target_s=self.drain_target_s,
            queue_delay_target_ms=self.queue_delay_target_ms)
        desired = plan["replicas_needed"]
        self._obs["autopilot_replicas_desired"].set(desired)

        # hysteresis anchor: when did desired first drop below up and
        # STAY there? Any tick at/above up resets the window.
        if desired < up:
            if self._below_since is None:
                self._below_since = now
        else:
            self._below_since = None

        active = self._active_alerts(alertz)
        gens = rollup.get("bundle_generations") or []
        in_cooldown = (self._last_action_t is not None
                       and now - self._last_action_t < self.cooldown_s)

        action, victim, target = "none", None, up
        vetoes: List[str] = []
        reason = (f"demand {plan['demand_tokens']} tok / queue delay "
                  f"{plan['queue_delay_ms']} ms -> {desired} replicas "
                  f"(up: {up})")
        if desired > up:
            if in_cooldown:
                vetoes.append("cooldown")
            else:
                action, target = "scale_up", desired
        elif desired < up:
            # do-no-harm gauntlet, every blocked guard recorded (a
            # scale-down that waited on 3 guards shows all 3)
            if active:
                vetoes.append("alerts_active")
            if len(gens) > 1:
                vetoes.append("rollout_in_progress")
            if self._below_since is None or \
                    now - self._below_since < self.stabilization_s:
                vetoes.append("stabilization")
            if in_cooldown:
                vetoes.append("cooldown")
            if not vetoes:
                victim = self._coldest(replicas)
                if victim is None:
                    vetoes.append("no_victim")
                else:
                    # one replica per decision: eviction is the risky
                    # direction, so converge in observed steps
                    action, target = "scale_down", up - 1
        elif plan["replicas_unclamped"] != desired:
            # the rails absorbed the whole ask (e.g. demand wants 12,
            # max is 8, fleet is at 8): visible, not silent
            vetoes.append("rails")

        self._seq += 1
        return {
            "kind": "autopilot_decision",
            "id": f"d{self._seq}",
            "t_s": round(now, 3),
            "action": action,
            "from": up,
            "to": target,
            "victim": victim,
            "added": [],
            "applied_steps": 0,
            "applied": False,
            "vetoes": vetoes,
            "reason": reason,
            "plan": plan,
            "rollup": rollup,
            "alerts_active": active,
        }

    # -- actuation (retry + exactly-once) --------------------------------

    def _apply(self, decision: dict) -> None:
        """One actuation attempt. Progress is tracked per STEP inside
        the decision (``applied_steps``/``added``), so an attempt that
        fails midway leaves a resumable record — the retry finishes
        the remainder instead of re-running completed steps."""
        action = decision["action"]
        if action == "scale_up":
            want = decision["to"] - decision["from"]
            while decision["applied_steps"] < want:
                chaos_fire("autopilot.actuate", action=action,
                           decision_id=decision["id"],
                           step=decision["applied_steps"])
                url = self.actuator.scale_up(decision)
                decision["applied_steps"] += 1
                if url:
                    decision["added"].append(url)
        elif action == "scale_down":
            if decision["applied_steps"] < 1:
                chaos_fire("autopilot.actuate", action=action,
                           decision_id=decision["id"], step=0)
                self.actuator.scale_down(decision, decision["victim"])
                decision["applied_steps"] = 1

    def _actuate(self, decision: dict) -> bool:
        """Apply one decision exactly once, retrying transient
        actuator failures with exponential backoff. Exhausting the
        retries DROPS the decision (counted + evented) — the next
        tick re-measures and re-decides against the fleet's actual
        state, which beats blindly re-driving a stale plan."""
        if decision["id"] in self._applied:
            return True  # never double-apply (replayed tick/decision)
        action, attempts = decision["action"], 0
        while True:
            try:
                self._apply(decision)
            except Exception as exc:  # noqa: BLE001 — actuators raise
                #   anything (subprocess, urllib, chaos)
                attempts += 1
                if attempts > self.actuate_retries:
                    self._obs["autopilot_actuations_total"].labels(
                        action=action, outcome="failed").inc()
                    self.event_log.emit(
                        "autopilot_actuation_failed", id=decision["id"],
                        action=action, attempts=attempts,
                        error=str(exc)[:200])
                    logger.warning("actuation %s (%s) failed after %d "
                                   "attempts: %s", decision["id"],
                                   action, attempts, exc)
                    return False
                self._obs["autopilot_actuation_retries_total"].inc()
                self.event_log.emit(
                    "autopilot_actuation_retry", id=decision["id"],
                    action=action, attempt=attempts,
                    error=str(exc)[:200])
                self._sleep(self.retry_backoff_s * (2 ** (attempts - 1)))
                continue
            if len(self._applied_ring) == self._applied_ring.maxlen:
                self._applied.discard(self._applied_ring[0])
            self._applied_ring.append(decision["id"])
            self._applied.add(decision["id"])
            self._obs["autopilot_actuations_total"].labels(
                action=action, outcome="ok").inc()
            return True

    # -- the tick --------------------------------------------------------

    def tick(self) -> dict:
        """One measure -> plan -> guard -> actuate pass. Always
        returns the decision record (no-ops included); the record is
        also kept in the bounded ``decisions`` ring."""
        span = (self.tracer.start_span("autopilot.tick")
                if self.tracer is not None else None)
        try:
            fleetz, alertz = self.source()
            decision = self.decide(fleetz, alertz)
            self._obs["autopilot_ticks_total"].inc()
            self._obs["autopilot_decisions_total"].labels(
                action=decision["action"]).inc()
            for veto in decision["vetoes"]:
                self._obs["autopilot_vetoes_total"].labels(
                    reason=veto).inc()
            if span is not None:
                span.event("decision", id=decision["id"],
                           action=decision["action"],
                           replicas_from=decision["from"],
                           to=decision["to"],
                           vetoes=decision["vetoes"],
                           desired=decision["plan"]["replicas_needed"])
            if decision["action"] != "none" or decision["vetoes"]:
                # full provenance on anything non-trivial: the rollup
                # + plan that justified (or blocked) the move ride the
                # event, so the trail alone reconstructs the WHY
                self.event_log.emit("autopilot_decision", **{
                    k: decision[k] for k in DECISION_KEYS
                    if k not in ("kind",)})
            if decision["action"] != "none":
                decision["applied"] = self._actuate(decision)
                if decision["applied"]:
                    self._last_action_t = self.clock()
                    self._below_since = None
                    logger.info(
                        "autopilot %s: %s %d -> %d%s", decision["id"],
                        decision["action"], decision["from"],
                        decision["to"],
                        f" (victim {decision['victim']})"
                        if decision["victim"] else "")
                    if span is not None:
                        span.event("actuated", id=decision["id"],
                                   added=decision["added"],
                                   victim=decision["victim"])
            self.decisions.append(decision)
            return decision
        finally:
            if span is not None:
                span.finish()

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "Autopilot":
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    self.tick()
                except Exception:  # noqa: BLE001 — the loop survives
                    #   a torn snapshot or a dead source; next tick
                    #   re-reads
                    logger.exception("autopilot tick failed")
                self._stop.wait(self.tick_s)

        self._thread = threading.Thread(target=loop, name="autopilot",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
