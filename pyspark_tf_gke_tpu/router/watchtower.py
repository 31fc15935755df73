"""Fleet watchtower: continuous SLO evaluation + burn-rate alerting.

The replay plane can already say "that run was out of SLO" — after the
run ends (``replay/slo.py``). Nothing in the live path ever said "the
fleet is out of SLO *right now*". This module is that sensor plane,
router-side and stdlib-only like the rest of ``router/``:

* **Fleet snapshot ring** — every :class:`~pyspark_tf_gke_tpu.router
  .discovery.HealthProber` sweep folds the replicas' ``/loadz``
  snapshots (which already carry the ``/stepz`` summary's windowed
  ``step_host_overhead_frac`` + ``step_tokens_per_sec``) into a
  time-bucketed, bounded ring of per-replica records and fleet
  rollups: capacity/demand, worst queue delay, prefix hit + spec
  accept rates, host-overhead max, throughput sum, and the distinct
  ``bundle_generation`` set (a mixed-generation fleet mid-publish is
  one ``/fleetz`` read).
* **Sliding-window SLO evaluation** — the gateway feeds every routed
  request's latency/outcome/tenant, first-event TTFT, inter-token
  gaps, shed reasons and stream-resume verdicts in; the watchtower
  builds an ``evaluate_slo``-shaped report over each window and
  evaluates the UNCHANGED ``replay/slo.py`` vocabulary (``SLO_KEYS``
  is imported, not forked — one SLO language offline and live).
* **Multi-window burn-rate alerting** (Google SRE workbook shape) —
  per-SLO error-budget accounting over short/long window pairs with
  hysteresis and a pending -> firing -> resolved state machine,
  emitting ``router_alert`` events plus the
  ``router_slo_burn_rate{slo,window}`` / ``router_alerts_firing``
  metric families. A structural ``replica_down:<rid>`` alert (always
  on, no SLO spec needed) covers the chaos-native case: a replica
  that was UP and is now DOWN.

Burn-rate semantics, pinned here because tests assert them in closed
form:

* a percentile bound ``latency_p99_ms: B`` budgets ``1 - 0.99`` of
  requests above ``B``; the burn rate over a window is
  ``(fraction of samples > B) / budget`` — 1.0 means "spending the
  budget exactly as fast as allowed", the classic 14.4x/6x fast/slow
  thresholds mean what the SRE workbook says;
* ``goodput_min: G`` budgets ``1 - G`` bad requests (floored at
  ``MIN_BUDGET`` so ``G = 1.0`` stays finite);
* ``tenant_ok_rate_ratio_min: R`` burns ``(1 - ratio) / (1 - R)``;
* count-style keys (``sheds_max`` / ``errors_max`` /
  ``shed_reasons_allowed``) are hard bounds, not budgets: the
  condition is ``value > bound`` in the LONG window while the SHORT
  window still shows activity (so the alert resolves when the burst
  stops), and the exported "burn" is ``value / max(bound, 1)`` for
  dashboard visibility only.

An alert (one per SLO key, plus the structural ones) fires when ANY
configured window pair trips its condition for ``for_s`` consecutive
seconds, and resolves only after ``clear_s`` seconds of quiet —
flapping input produces ONE firing, not a firestorm. Detection bound
for a replica kill: passive health marks DOWN on the first failed
request, so ``<= eval_interval + for_s`` under load; probe-only
detection adds ``fail_threshold x probe_interval + probe_timeout``.

``GET /fleetz`` and ``GET /alertz`` (mounted via
``obs/export.handle_obs_request``) expose all of it with PINNED key
sets — the documented input contract for ROADMAP item 5's autopilot
and the HPA adapter docs.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from pyspark_tf_gke_tpu.replay.slo import SLO_KEYS, evaluate_slo
from pyspark_tf_gke_tpu.replay.stats import summary
from pyspark_tf_gke_tpu.utils.logging import get_logger

logger = get_logger("router.watchtower")

# -- pinned key sets (tests assert these exactly) ----------------------------

# fleet rollup: one dict per ring bucket (and the newest one on /fleetz)
FLEET_ROLLUP_KEYS = (
    "t_s", "wall", "replicas", "up", "draining", "down",
    "capacity_free_total", "demand_tokens_total", "queue_delay_ms_max",
    "step_host_overhead_frac_max", "prefix_hit_rate_mean",
    "spec_accept_rate_mean", "step_tokens_per_sec_total",
    "queued_total", "active_total", "bundle_generations",
    "replica_minutes", "roles",
)

# per-replica record inside a bucket / the /fleetz replicas map
REPLICA_SNAPSHOT_KEYS = (
    "state", "capacity_free", "queue_delay_ms", "prefix_hit_rate",
    "spec_accept_rate", "step_host_overhead_frac", "step_tokens_per_sec",
    "bundle_generation", "queued", "active", "inflight", "role",
)

FLEETZ_KEYS = ("bucket_s", "ring_max", "buckets", "sweeps_total",
               "fleet", "replicas", "history", "cursor")

ALERTZ_KEYS = ("slo", "windows", "for_s", "clear_s", "min_samples",
               "alerts", "firing", "burn_rates", "history", "slo_eval")

ALERT_KEYS = ("name", "kind", "state", "age_s", "value", "fire_count",
              "fired_wall", "resolved_wall")

ALERT_HISTORY_KEYS = ("wall", "age_s", "alert", "from", "to", "value")

# alert states (the state machine's whole vocabulary)
OK = "ok"
PENDING = "pending"
FIRING = "firing"
RESOLVED = "resolved"

# goodput_min = 1.0 must not divide by zero: the budget floor
MIN_BUDGET = 1e-3

# SLO keys whose violation is a hard count bound, not a burnable budget
_COUNT_KEYS = ("sheds_max", "errors_max", "shed_reasons_allowed")

# gateway outcome -> the replay taxonomy evaluate_slo reads
# (unreachable and upstream_error are both "the fleet failed the
# request"; client_error / client_disconnect are the client's doing and
# excluded from the goodput denominator)
_OUTCOME_CLASS = {
    "ok": "ok",
    "shed": "shed",
    "unreachable": "error",
    "upstream_error": "error",
    "client_error": "client_error",
    "client_disconnect": "client_disconnect",
}
_GOODPUT_OUTCOMES = ("ok", "shed", "error")

DEFAULT_ALERT_WINDOWS = "60:300:10,300:1800:2"


class BurnWindow:
    """One short/long window pair with its burn-rate threshold."""

    __slots__ = ("short_s", "long_s", "burn")

    def __init__(self, short_s: float, long_s: float, burn: float):
        if not (0 < short_s < long_s):
            raise ValueError(
                f"alert window needs 0 < short < long, got "
                f"{short_s}:{long_s}")
        if burn <= 0:
            raise ValueError(f"burn threshold must be > 0, got {burn}")
        self.short_s = float(short_s)
        self.long_s = float(long_s)
        self.burn = float(burn)

    def as_dict(self) -> dict:
        return {"short_s": self.short_s, "long_s": self.long_s,
                "burn": self.burn}


def parse_alert_windows(spec: str) -> List[BurnWindow]:
    """``"60:300:10,300:1800:2"`` -> window pairs (seconds:seconds:
    burn-threshold). The SRE-workbook defaults pair a fast burn (page
    now) with a slow one (sustained budget spend)."""
    out = []
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        bits = part.split(":")
        if len(bits) != 3:
            raise ValueError(
                f"alert window {part!r} must be short:long:burn")
        out.append(BurnWindow(float(bits[0]), float(bits[1]),
                              float(bits[2])))
    if not out:
        raise ValueError(f"no window pairs in {spec!r}")
    return out


def parse_slo_spec(text: str) -> dict:
    """``--slo`` value -> validated SLO dict: inline JSON or
    ``@path/to/slo.json``. Validation is ``replay/slo.py``'s own
    (unknown keys raise) — the live plane accepts exactly the replay
    vocabulary, nothing forked."""
    text = (text or "").strip()
    if not text:
        return {}
    if text.startswith("@"):
        with open(text[1:]) as fh:
            text = fh.read()
    slo = json.loads(text)
    if not isinstance(slo, dict):
        raise ValueError("--slo must be a JSON object of SLO bounds")
    evaluate_slo({}, slo)  # raises ValueError on unknown keys
    return slo


class Alert:
    """One alert's state-machine record."""

    __slots__ = ("name", "kind", "state", "since_mono", "since_wall",
                 "pending_since", "clear_since", "fired_wall",
                 "resolved_wall", "fire_count", "value")

    def __init__(self, name: str, kind: str, now_mono: float):
        self.name = name
        self.kind = kind  # "slo" | "replica_down"
        self.state = OK
        self.since_mono = now_mono
        self.since_wall = time.time()
        self.pending_since: Optional[float] = None
        self.clear_since: Optional[float] = None
        self.fired_wall: Optional[float] = None
        self.resolved_wall: Optional[float] = None
        self.fire_count = 0
        self.value: Optional[float] = None

    def as_dict(self, now_mono: float) -> dict:
        return {"name": self.name, "kind": self.kind,
                "state": self.state,
                "age_s": round(now_mono - self.since_mono, 3),
                "value": self.value, "fire_count": self.fire_count,
                "fired_wall": self.fired_wall,
                "resolved_wall": self.resolved_wall}


class FleetSnapshotRing:
    """Time-bucketed bounded ring of fleet snapshots. One probe sweep
    folds into the bucket its timestamp lands in (latest sweep in a
    bucket wins — the ring is a downsampled history, not a sweep log),
    so memory is bounded by ``maxlen`` REGARDLESS of probe rate."""

    def __init__(self, bucket_s: float = 2.0, maxlen: int = 256):
        self.bucket_s = max(0.1, float(bucket_s))
        self.maxlen = max(1, int(maxlen))
        self._ring: deque = deque(maxlen=self.maxlen)
        self._lock = threading.Lock()
        self.sweeps_total = 0

    def fold(self, entry: dict, now_mono: float) -> None:
        bucket = int(now_mono / self.bucket_s)
        with self._lock:
            self.sweeps_total += 1
            if self._ring and self._ring[-1][0] == bucket:
                self._ring[-1] = (bucket, entry)
            else:
                self._ring.append((bucket, entry))

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def latest(self) -> Optional[dict]:
        with self._lock:
            return self._ring[-1][1] if self._ring else None

    def history(self, n: Optional[int] = None,
                since: Optional[float] = None) -> List[dict]:
        """Oldest -> newest bucket entries (bounded by ``n``).
        ``since`` is a bucket cursor (bucket start time, seconds in
        the monotonic domain — the ``cursor`` value a previous
        ``/fleetz`` read returned): only entries in STRICTLY newer
        buckets are returned, so a poller re-fetches nothing."""
        with self._lock:
            pairs = list(self._ring)
        if since is not None:
            pairs = [(b, e) for b, e in pairs
                     if b * self.bucket_s > since + 1e-9]
        entries = [e for _, e in pairs]
        return entries[-n:] if n else entries

    def cursor(self) -> Optional[float]:
        """Newest bucket's start time (pass back as ``since=`` to poll
        only deltas); None while the ring is empty."""
        with self._lock:
            if not self._ring:
                return None
            return round(self._ring[-1][0] * self.bucket_s, 3)


class Watchtower:
    """Router-side aggregation + alerting plane (see module doc).

    Thread model: gateway handler threads call the ``note_*`` intake;
    the prober thread calls :meth:`sweep` (which folds the ring and
    runs one :meth:`evaluate` tick); ``/fleetz`` / ``/alertz`` reads
    come from handler threads. One lock, short holds, allocations
    outside it where possible. ``clock`` is injectable so the state
    machine and window math test in closed form."""

    def __init__(self, replicas, *, slo: Optional[dict] = None,
                 windows=DEFAULT_ALERT_WINDOWS,
                 for_s: float = 0.0, clear_s: float = 30.0,
                 min_samples: int = 10,
                 bucket_s: float = 2.0, ring_max: int = 256,
                 max_measurements: int = 8192,
                 obs: Optional[dict] = None, event_log=None,
                 clock: Callable[[], float] = time.monotonic):
        self._replicas = replicas
        self.slo = dict(slo) if slo else {}
        if self.slo:
            evaluate_slo({}, self.slo)  # unknown keys raise, early
        self.windows = (parse_alert_windows(windows)
                        if isinstance(windows, str) else list(windows))
        self.for_s = max(0.0, float(for_s))
        self.clear_s = max(0.0, float(clear_s))
        self.min_samples = max(1, int(min_samples))
        self.ring = FleetSnapshotRing(bucket_s=bucket_s, maxlen=ring_max)
        self._obs = obs
        self._event_log = event_log
        self._clock = clock
        self._lock = threading.Lock()
        horizon = max(w.long_s for w in self.windows)
        self._horizon_s = horizon
        # measurement windows: (t_mono, ...) tuples, newest right;
        # bounded twice — by count (deque maxlen) and by the longest
        # window (pruned on evaluate) — so an idle-then-flooded router
        # can neither grow without bound nor hold stale samples
        m = max(64, int(max_measurements))
        self._requests: deque = deque(maxlen=m)   # (t, ms, class, tenant)
        self._ttft: deque = deque(maxlen=m)       # (t, ms)
        self._tbt: deque = deque(maxlen=m)        # (t, ms)
        self._sheds: deque = deque(maxlen=m)      # (t, reason)
        self._resumes: deque = deque(maxlen=m)    # (t, outcome)
        self._alerts: Dict[str, Alert] = {}
        self._history: deque = deque(maxlen=256)  # transition records
        self._ever_up: set = set()
        # cumulative UP-replica time, in minutes (the autoscaler's cost
        # axis: SLOs held per replica-minute spent). Integrated sweep to
        # sweep, so a 3-replica fleet accrues 3x faster than a 1-replica
        # one; carried on every rollup.
        self._replica_minutes = 0.0
        self._last_sweep_mono: Optional[float] = None
        self._last_burn: Dict[str, Dict[str, float]] = {}
        self._last_slo_eval: Optional[dict] = None

    # -- intake (gateway request path) -----------------------------------

    def note_request(self, latency_ms: float, outcome: str,
                     tenant: str = "default") -> None:
        """One routed request's terminal verdict. ``outcome`` is the
        gateway's taxonomy (``router_requests_total``'s outcome
        label); normalized here to the replay taxonomy."""
        cls = _OUTCOME_CLASS.get(outcome, "error")
        with self._lock:
            self._requests.append((self._clock(), float(latency_ms),
                                   cls, str(tenant)))

    def note_ttft(self, ms: float) -> None:
        """First-event latency of one relayed stream (router-measured:
        stream accept -> first token event written)."""
        with self._lock:
            self._ttft.append((self._clock(), float(ms)))

    def note_tbt(self, ms: float) -> None:
        """Gap between consecutive token events within one stream."""
        with self._lock:
            self._tbt.append((self._clock(), float(ms)))

    def note_shed(self, reason: Optional[str]) -> None:
        """One shed surfaced to a client, by server-reported reason."""
        with self._lock:
            self._sheds.append((self._clock(),
                                str(reason or "unknown")))

    def note_stream_resume(self, outcome: str) -> None:
        """One mid-stream failover attempt's verdict (ok | failed |
        exhausted | deadline — ``router_stream_resumes_total``'s
        vocabulary)."""
        with self._lock:
            self._resumes.append((self._clock(), str(outcome)))

    # -- intake (prober sweep) -------------------------------------------

    def sweep(self) -> dict:
        """Fold one completed probe sweep into the snapshot ring and
        run one alert-evaluation tick. Wired as the prober's
        ``on_sweep`` hook, so aggregation rides the sweep that already
        holds fresh ``/loadz`` bodies — zero extra replica HTTP."""
        now = self._clock()
        reps = self._replicas.all()
        autoscale = self._replicas.update_autoscale()
        per_replica: Dict[str, dict] = {}
        hit_rates, accept_rates, gens = [], [], set()
        tps_total = 0.0
        queued_total = active_total = 0
        counts = {"up": 0, "draining": 0, "down": 0}
        for r in reps:
            load = r.load or {}
            counts[r.state] = counts.get(r.state, 0) + 1
            if r.state == "up":
                self._ever_up.add(r.rid)

            def num(key, default=0.0):
                v = load.get(key)
                return (float(v) if isinstance(v, (int, float))
                        and not isinstance(v, bool) else default)

            tps = num("step_tokens_per_sec")
            rec = {
                "state": r.state,
                "capacity_free": int(num("capacity_free")),
                "queue_delay_ms": num("queue_delay_ms"),
                "prefix_hit_rate": num("prefix_hit_rate"),
                "spec_accept_rate": num("spec_accept_rate"),
                "step_host_overhead_frac": num("step_host_overhead_frac"),
                "step_tokens_per_sec": tps,
                "bundle_generation": load.get("bundle_generation"),
                "queued": int(num("queued")),
                "active": int(num("active")),
                "inflight": r.inflight,
                "role": r.role,
            }
            per_replica[r.rid] = rec
            if r.state == "up":
                hit_rates.append(rec["prefix_hit_rate"])
                accept_rates.append(rec["spec_accept_rate"])
                tps_total += tps
                queued_total += rec["queued"]
                active_total += rec["active"]
            if load.get("bundle_generation") is not None:
                gens.add(load["bundle_generation"])

        def mean(xs):
            return round(sum(xs) / len(xs), 4) if xs else 0.0

        # replica-minutes: rectangle rule over the sweep interval with
        # the CURRENT up count (a replica that died since the last sweep
        # stops accruing at this sweep, not retroactively)
        if self._last_sweep_mono is not None:
            dt = max(0.0, now - self._last_sweep_mono)
            self._replica_minutes += counts.get("up", 0) * dt / 60.0
        self._last_sweep_mono = now

        rollup = {
            "t_s": round(now, 3),
            "wall": round(time.time(), 3),
            "replicas": len(reps),
            "up": counts.get("up", 0),
            "draining": counts.get("draining", 0),
            "down": counts.get("down", 0),
            # the autoscale terms come from ReplicaSet.update_autoscale
            # VERBATIM — the HPA signal and the watchtower can never
            # disagree about capacity math
            "capacity_free_total": autoscale["capacity_free_total"],
            "demand_tokens_total": autoscale["demand_tokens_total"],
            "queue_delay_ms_max": autoscale["queue_delay_ms_max"],
            "step_host_overhead_frac_max":
                autoscale["step_host_overhead_frac_max"],
            "prefix_hit_rate_mean": mean(hit_rates),
            "spec_accept_rate_mean": mean(accept_rates),
            "step_tokens_per_sec_total": round(tps_total, 1),
            "queued_total": queued_total,
            "active_total": active_total,
            "bundle_generations": sorted(gens, key=str),
            "replica_minutes": round(self._replica_minutes, 4),
            # per-role split of the SAME autoscale terms — the HPA for a
            # disaggregated fleet scales prefill and decode Deployments
            # on their own demand/capacity, not the blended totals
            "roles": autoscale.get("by_role", {}),
        }
        entry = {"rollup": rollup, "replicas": per_replica}
        self.ring.fold(entry, now)
        if self._obs is not None:
            c = self._obs.get("router_fleet_snapshots_total")
            if c is not None:
                c.inc()
            g = self._obs.get("router_fleet_snapshot_buckets")
            if g is not None:
                g.set(len(self.ring))
        self.evaluate(now)
        return rollup

    # -- windowed measurement reports ------------------------------------

    def _window_slices(self, window_s: float, now: float):
        cut = now - window_s
        with self._lock:
            reqs = [x for x in self._requests if x[0] >= cut]
            ttft = [ms for t, ms in self._ttft if t >= cut]
            tbt = [ms for t, ms in self._tbt if t >= cut]
            sheds = [r for t, r in self._sheds if t >= cut]
            resumes = [o for t, o in self._resumes if t >= cut]
        return reqs, ttft, tbt, sheds, resumes

    def window_report(self, window_s: float,
                      now: Optional[float] = None) -> dict:
        """``evaluate_slo``-shaped report over the trailing window of
        router-side measurements, plus the router extras (stream
        resumes, raw outcome taxonomy). Same key meanings as the
        replay driver's report — the live and offline SLO verdicts
        speak one language."""
        now = self._clock() if now is None else now
        reqs, ttft, tbt, sheds, resumes = self._window_slices(
            window_s, now)
        outcomes: Dict[str, int] = {}
        shed_reasons: Dict[str, int] = {}
        tenants: Dict[str, List[int]] = {}
        for _, _, cls, tenant in reqs:
            outcomes[cls] = outcomes.get(cls, 0) + 1
            if cls in _GOODPUT_OUTCOMES:
                tot = tenants.setdefault(tenant, [0, 0])
                tot[1] += 1
                if cls == "ok":
                    tot[0] += 1
        for reason in sheds:
            shed_reasons[reason] = shed_reasons.get(reason, 0) + 1
        resume_counts: Dict[str, int] = {}
        for o in resumes:
            resume_counts[o] = resume_counts.get(o, 0) + 1
        counted = sum(outcomes.get(c, 0) for c in _GOODPUT_OUTCOMES)
        goodput = (outcomes.get("ok", 0) / counted if counted else None)
        ratio = None
        rates = [ok / tot for ok, tot in tenants.values() if tot]
        if len(rates) >= 2:
            best = max(rates)
            ratio = round(min(rates) / best, 4) if best > 0 else 0.0
        return {
            "n": len(reqs),
            "window_s": float(window_s),
            "latency_ms": summary([ms for _, ms, _, _ in reqs]),
            "ttft_ms": summary(ttft),
            "tbt_ms": summary(tbt),
            "goodput": (round(goodput, 4)
                        if goodput is not None else None),
            "tenant_ok_rate_ratio": ratio,
            "outcomes": outcomes,
            "sheds": shed_reasons,
            "stream_resumes": resume_counts,
        }

    # -- burn-rate math ---------------------------------------------------

    def _burn_for(self, key: str, bound, window_s: float,
                  now: float) -> Tuple[float, int]:
        """(burn_rate, n_samples) for one SLO key over one window.
        Closed-form (tests pin it): see the module docstring."""
        reqs, ttft, tbt, sheds, _ = self._window_slices(window_s, now)
        if key in ("latency_p50_ms", "latency_p99_ms",
                   "ttft_p50_ms", "ttft_p99_ms",
                   "tbt_p50_ms", "tbt_p99_ms"):
            q = 0.99 if key.endswith("p99_ms") else 0.50
            budget = max(1.0 - q, MIN_BUDGET)
            if key.startswith("latency"):
                xs = [ms for _, ms, _, _ in reqs]
            elif key.startswith("ttft"):
                xs = ttft
            else:
                xs = tbt
            if not xs:
                return 0.0, 0
            bad = sum(1 for v in xs if v > float(bound)) / len(xs)
            return bad / budget, len(xs)
        if key == "goodput_min":
            counted = [x for x in reqs if x[2] in _GOODPUT_OUTCOMES]
            if not counted:
                return 0.0, 0
            budget = max(1.0 - float(bound), MIN_BUDGET)
            bad = 1.0 - (sum(1 for x in counted if x[2] == "ok")
                         / len(counted))
            return bad / budget, len(counted)
        if key == "tenant_ok_rate_ratio_min":
            report = self.window_report(window_s, now)
            ratio = report["tenant_ok_rate_ratio"]
            if ratio is None:
                return 0.0, 0
            budget = max(1.0 - float(bound), MIN_BUDGET)
            return (1.0 - ratio) / budget, report["n"]
        if key == "sheds_max":
            value = sum(1 for x in reqs if x[2] == "shed")
            return value / max(float(bound), 1.0), value
        if key == "errors_max":
            value = sum(1 for x in reqs if x[2] == "error")
            return value / max(float(bound), 1.0), value
        if key == "shed_reasons_allowed":
            allowed = set(bound)
            value = sum(1 for r in sheds if r not in allowed)
            return float(value), value
        return 0.0, 0

    def burn_rates(self, now: Optional[float] = None
                   ) -> Dict[str, Dict[str, float]]:
        """``{slo_key: {"<window>s": burn}}`` over every distinct
        window length in the configured pairs — the
        ``router_slo_burn_rate{slo,window}`` gauge's source."""
        now = self._clock() if now is None else now
        lengths = sorted({w.short_s for w in self.windows}
                         | {w.long_s for w in self.windows})
        out: Dict[str, Dict[str, float]] = {}
        for key, bound in self.slo.items():
            per = {}
            for ws in lengths:
                burn, _ = self._burn_for(key, bound, ws, now)
                per[f"{ws:g}s"] = round(burn, 4)
            out[key] = per
        return out

    def _slo_condition(self, key: str, bound, now: float
                       ) -> Tuple[bool, float]:
        """(condition, worst_burn) across the window pairs."""
        worst = 0.0
        tripped = False
        for w in self.windows:
            b_short, n_short = self._burn_for(key, bound, w.short_s, now)
            b_long, n_long = self._burn_for(key, bound, w.long_s, now)
            worst = max(worst, b_short, b_long)
            if key in _COUNT_KEYS:
                # hard count bound: violated over the long window while
                # the short window still shows activity (resolution
                # when the burst stops)
                if key == "shed_reasons_allowed":
                    if n_long > 0 and n_short > 0:
                        tripped = True
                elif n_long > int(bound) and n_short > 0:
                    tripped = True
            else:
                if (n_short >= self.min_samples
                        and b_short >= w.burn and b_long >= w.burn):
                    tripped = True
        return tripped, worst

    # -- alert state machine ---------------------------------------------

    def _alert(self, name: str, kind: str, now: float) -> Alert:
        a = self._alerts.get(name)
        if a is None:
            a = Alert(name, kind, now)
            self._alerts[name] = a
        return a

    def _transition(self, a: Alert, new_state: str, now: float) -> None:
        prev = a.state
        a.state = new_state
        a.since_mono = now
        a.since_wall = time.time()
        rec = {"wall": round(a.since_wall, 3), "age_s": 0.0,
               "alert": a.name, "from": prev, "to": new_state,
               "value": a.value}
        self._history.append((now, rec))
        if new_state == FIRING:
            a.fire_count += 1
            a.fired_wall = a.since_wall
        if new_state == RESOLVED:
            a.resolved_wall = a.since_wall
        if self._obs is not None:
            g = self._obs.get("router_alerts_firing")
            if g is not None:
                g.labels(alert=a.name).set(1 if new_state == FIRING
                                           else 0)
            c = self._obs.get("router_alert_transitions_total")
            if c is not None:
                c.labels(alert=a.name, state=new_state).inc()
        # event-log policy: firing + resolved only — pending/ok churn
        # under flapping input must not flood the trail (the history
        # ring keeps every transition for /alertz)
        if new_state in (FIRING, RESOLVED) and self._event_log is not None:
            self._event_log.emit("router_alert", alert=a.name,
                                 alert_kind=a.kind, prev=prev,
                                 state=new_state, value=a.value,
                                 fire_count=a.fire_count)
        logger.info("alert %s: %s -> %s (value=%s)", a.name, prev,
                    new_state, a.value)

    def _step_alert(self, a: Alert, condition: bool, value,
                    now: float) -> None:
        """One state-machine tick. pending->firing needs ``for_s`` of
        sustained condition; firing->resolved needs ``clear_s`` of
        quiet (hysteresis: a re-trip during the quiet countdown resets
        it WITHOUT a new firing)."""
        a.value = (round(float(value), 4)
                   if isinstance(value, (int, float)) else value)
        if condition:
            a.clear_since = None
            if a.state in (OK, RESOLVED):
                self._transition(a, PENDING, now)
                a.pending_since = now
            if a.state == PENDING \
                    and now - (a.pending_since or now) >= self.for_s:
                self._transition(a, FIRING, now)
        else:
            if a.state == PENDING:
                a.pending_since = None
                self._transition(a, OK, now)
            elif a.state == FIRING:
                if a.clear_since is None:
                    a.clear_since = now
                if now - a.clear_since >= self.clear_s:
                    a.clear_since = None
                    self._transition(a, RESOLVED, now)

    def evaluate(self, now: Optional[float] = None) -> None:
        """One evaluation tick: burn rates -> gauges, SLO + structural
        alert conditions -> state machines. Called from every probe
        sweep (so cadence = probe interval) and directly by tests."""
        now = self._clock() if now is None else now
        # SLO burn-rate alerts
        if self.slo:
            burns = self.burn_rates(now)
            self._last_burn = burns
            if self._obs is not None:
                g = self._obs.get("router_slo_burn_rate")
                if g is not None:
                    for key, per in burns.items():
                        for win, burn in per.items():
                            g.labels(slo=key, window=win).set(burn)
            for key, bound in self.slo.items():
                cond, worst = self._slo_condition(key, bound, now)
                self._step_alert(self._alert(f"slo:{key}", "slo", now),
                                 cond, worst, now)
            self._last_slo_eval = evaluate_slo(
                self.window_report(self._horizon_s, now), self.slo)
        # structural replica-down alerts: a replica this watchtower has
        # seen UP that is now DOWN is an outage regardless of any SLO
        # spec (DRAINING is intentional and does not trip it)
        for r in self._replicas.all():
            if r.rid not in self._ever_up:
                continue
            a = self._alert(f"replica_down:{r.rid}", "replica_down",
                            now)
            self._step_alert(a, r.state == "down",
                             1.0 if r.state == "down" else 0.0, now)

    # -- endpoint payloads (pinned key sets) ------------------------------

    def fleetz(self, n: int = 32, replica: Optional[str] = None,
               since: Optional[float] = None) -> dict:
        """``GET /fleetz`` body. ``n`` bounds the rollup history;
        ``replica`` substring-filters the per-replica map; ``since``
        (a ``cursor`` from a previous read) restricts ``history`` to
        strictly newer buckets — the autopilot's incremental poll, so
        each tick fetches deltas instead of the whole ring."""
        latest = self.ring.latest() or {"rollup": None, "replicas": {}}
        reps = latest["replicas"]
        if replica:
            reps = {rid: rec for rid, rec in reps.items()
                    if replica in rid}
        return {
            "bucket_s": self.ring.bucket_s,
            "ring_max": self.ring.maxlen,
            "buckets": len(self.ring),
            "sweeps_total": self.ring.sweeps_total,
            "fleet": latest["rollup"],
            "replicas": reps,
            "history": [e["rollup"]
                        for e in self.ring.history(max(1, int(n)),
                                                   since=since)],
            "cursor": self.ring.cursor(),
        }

    def alertz(self, state: Optional[str] = None,
               name: Optional[str] = None, n: int = 64) -> dict:
        """``GET /alertz`` body. ``state`` / ``name`` filter the alert
        list; ``n`` bounds the transition history (newest last)."""
        now = self._clock()
        with self._lock:
            alerts = [a.as_dict(now) for a in self._alerts.values()]
            raw_history = list(self._history)[-max(1, int(n)):]
        # age the history records at read time (their wall stamps are
        # absolute; age_s is a convenience for humans)
        aged = []
        for t_mono, rec in raw_history:
            r = dict(rec)
            r["age_s"] = round(now - t_mono, 3)
            aged.append(r)
        alerts.sort(key=lambda a: a["name"])
        if state:
            alerts = [a for a in alerts if a["state"] == state]
        if name:
            alerts = [a for a in alerts if name in a["name"]]
        return {
            "slo": self.slo,
            "windows": [w.as_dict() for w in self.windows],
            "for_s": self.for_s,
            "clear_s": self.clear_s,
            "min_samples": self.min_samples,
            "alerts": alerts,
            "firing": sorted(a.name for a in self._alerts.values()
                             if a.state == FIRING),
            "burn_rates": self._last_burn,
            "history": aged,
            "slo_eval": self._last_slo_eval,
        }
