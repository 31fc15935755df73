"""Declarative SLO assertions over a replay report.

An SLO spec is a flat JSON object of named bounds; unknown keys are an
ERROR (a typo'd bound that silently never checks is worse than no
bound). The vocabulary:

* ``ttft_p50_ms`` / ``ttft_p99_ms`` — time-to-first-token percentile
  upper bounds (streamed replays only; a blocking replay has no TTFT
  and the check fails as unmeasurable rather than passing vacuously).
* ``tbt_p50_ms`` / ``tbt_p99_ms`` — time-between-tokens bounds.
* ``latency_p50_ms`` / ``latency_p99_ms`` — end-to-end bounds.
* ``goodput_min`` — minimum fraction of requests that completed OK
  within their deadline (requests without a deadline count as met on
  completion) — THE heavy-traffic serving metric.
* ``tenant_ok_rate_ratio_min`` — minimum (worst tenant ok-rate) /
  (best tenant ok-rate): the fairness floor. 1.0 = perfectly fair.
* ``shed_reasons_allowed`` — list; any shed with a reason OUTSIDE the
  list fails (e.g. a fairness scenario allows ``tenant_quota`` +
  ``tenant_queue_full`` but a global ``queue_full`` means isolation
  broke).
* ``sheds_max`` — total shed upper bound.
* ``errors_max`` — transport/engine error upper bound (default 0 is
  NOT implied; state it).

:func:`evaluate_slo` returns a machine-readable verdict: ``{"pass":
bool, "checks": [{"name", "bound", "value", "ok"}, ...]}`` — the
per-scenario object ``tools/replay.py run`` and ``smoke_check
--replay`` embed.
"""

from __future__ import annotations

from typing import List, Optional

_PCTL_KEYS = {
    "ttft_p50_ms": ("ttft_ms", "p50"),
    "ttft_p99_ms": ("ttft_ms", "p99"),
    "tbt_p50_ms": ("tbt_ms", "p50"),
    "tbt_p99_ms": ("tbt_ms", "p99"),
    "latency_p50_ms": ("latency_ms", "p50"),
    "latency_p99_ms": ("latency_ms", "p99"),
}

SLO_KEYS = tuple(sorted(
    list(_PCTL_KEYS) + ["goodput_min", "tenant_ok_rate_ratio_min",
                        "shed_reasons_allowed", "sheds_max",
                        "errors_max"]))


def _check(name: str, bound, value, ok: Optional[bool]) -> dict:
    return {"name": name, "bound": bound, "value": value,
            "ok": bool(ok) if ok is not None else False}


def evaluate_slo(report: dict, slo: dict) -> dict:
    """Evaluate declarative ``slo`` bounds against a replay ``report``.

    A bound whose input the report cannot supply (e.g. a TTFT bound on
    a non-streamed replay) FAILS with ``value: None`` — unmeasurable
    must never read as met."""
    unknown = set(slo) - set(SLO_KEYS)
    if unknown:
        raise ValueError(
            f"unknown SLO key(s) {sorted(unknown)}; valid: "
            f"{', '.join(SLO_KEYS)}")
    checks: List[dict] = []
    for key, (family, pct) in _PCTL_KEYS.items():
        if key not in slo:
            continue
        bound = float(slo[key])
        value = (report.get(family) or {}).get(pct)
        checks.append(_check(key, bound, value,
                             value is not None and value <= bound))
    if "goodput_min" in slo:
        bound = float(slo["goodput_min"])
        value = report.get("goodput")
        checks.append(_check("goodput_min", bound, value,
                             value is not None and value >= bound))
    if "tenant_ok_rate_ratio_min" in slo:
        bound = float(slo["tenant_ok_rate_ratio_min"])
        value = report.get("tenant_ok_rate_ratio")
        checks.append(_check("tenant_ok_rate_ratio_min", bound, value,
                             value is not None and value >= bound))
    if "shed_reasons_allowed" in slo:
        allowed = set(slo["shed_reasons_allowed"])
        sheds = report.get("sheds") or {}
        outside = {r: n for r, n in sheds.items() if r not in allowed}
        checks.append(_check("shed_reasons_allowed", sorted(allowed),
                             outside, not outside))
    if "sheds_max" in slo:
        bound = int(slo["sheds_max"])
        value = (report.get("outcomes") or {}).get("shed", 0)
        checks.append(_check("sheds_max", bound, value, value <= bound))
    if "errors_max" in slo:
        bound = int(slo["errors_max"])
        value = (report.get("outcomes") or {}).get("error", 0)
        checks.append(_check("errors_max", bound, value, value <= bound))
    return {"pass": all(c["ok"] for c in checks), "checks": checks}
