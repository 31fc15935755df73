"""The comparison that decides ``correct``: each number beside its limit."""

import math
import sys
from typing import Dict, List


def compare(readings: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """One row per compared number. A number with no limit in the cell's file
    is an error: a limit is set from readings (PERF.md), never defaulted."""
    rows = []
    for name, value in readings.items():
        if name not in limits:
            raise KeyError(f"cell file gives no limit for compared number {name!r}")
        limit = float(limits[name])
        ok = (value is not None and not math.isnan(float(value))
              and float(value) <= limit)
        rows.append({"name": name, "value": None if value is None else float(value),
                     "limit": limit, "ok": bool(ok)})
    return rows


def verdict(rows: List[dict]) -> bool:
    return bool(rows) and all(r["ok"] for r in rows)


def print_rows(rows: List[dict]) -> None:
    """The compared numbers as the last lines on standard error."""
    for r in rows:
        print(f"check {r['name']}: value={r['value']!r} limit={r['limit']!r} "
              f"{'ok' if r['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float], leaves=None) -> float:
    """Worst leaf of |norm_prog - norm_ref| over max(norm_ref, median leaf's
    norm_ref): a gap of norms, not the norm of a difference."""
    names = list(ref) if leaves is None else list(leaves)
    med = sorted(ref[n] for n in names)[len(names) // 2]
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def moving_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's (a key's bias under softmax has none and
    moves under Adam by round-off alone)."""
    med = sorted(ref_grad.values())[len(ref_grad) // 2]
    return [n for n, g in ref_grad.items() if g >= 1e-3 * med]
