"""Reduction of a ``jax.profiler`` trace to the numbers the metric readers
need. Read with nothing but JAX (``jax.profiler.ProfileData``).

What a TPU trace holds (looked at by hand, PR 24; see PERF.md §3): one plane
per chip named ``/device:TPU:<n>``; on it the line ``XLA Modules`` carries one
event per execution of a jitted program (``jit_<function>(<fingerprint>)``)
and the line ``XLA Ops`` one event per HLO operation inside it, named by its
whole HLO text (``%fusion.117 = (...) fusion(...)``). A Pallas kernel is there
as ``%<flax scope>.<method>.<n> = ... custom-call(...),
custom_call_target="tpu_custom_call"``: no ``pallas_call`` of the program
passes ``name=``, so the kernel function's name is NOT in the trace and a
kernel is found by the module method that calls it (``attention.
_causal_attend``, ``attention._paged_decode_attend``, ``ln_attn``). Times are
nanoseconds on the device's clock.

The in-memory form is plain data so that a small recorded extract can be kept
as JSON beside the tests::

    {"devices": [{"name": "/device:TPU:0",
                  "modules": [[name, start_ns, dur_ns], ...],
                  "ops": [[name, start_ns, dur_ns], ...]}, ...]}
"""

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
CONTAINER_OPS = ("%while", "%conditional", "%call")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def start(trace_dir: str) -> None:
    """Start a device trace into ``trace_dir`` (emptied first) with the
    host-side Python tracer off: it slows the host and the reduction reads
    device planes only."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str) -> dict:
    """The newest trace under ``trace_dir`` in the plain form above."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        dev = {"name": plane.name, "modules": [], "ops": []}
        for line in plane.lines:
            if line.name in MODULE_LINES:
                key = "modules"
            elif line.name in OP_LINES:
                key = "ops"
            else:
                continue
            dev[key] = [(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        devices.append(dev)
    return {"devices": devices}


def describe(trace_dir: str, top: int = 25) -> dict:
    """Every plane and line with its event count and most common names:
    what to look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            total: Dict[str, List[float]] = {}
            n = 0
            for e in line.events:
                n += 1
                t = total.setdefault(e.name, [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns
            names = sorted(total.items(), key=lambda kv: -kv[1][1])[:top]
            lines.append({"line": line.name, "events": n,
                          "top": [[k, v[0], v[1] / 1e9] for k, v in names]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def save_extract(trace: dict, path: str, modules: int = 12, min_ns: int = 50_000,
                 name_chars: int = 160) -> None:
    """A recorded extract small enough to keep beside the tests: the first
    ``modules`` program executions of each chip with, of the operations that
    start before the last of them ends, the Pallas kernels (``tpu_custom_call``)
    and whatever ran for ``min_ns`` or longer; names cut to ``name_chars``."""
    small = []
    for d in trace["devices"]:
        mods = sorted(d["modules"], key=lambda e: e[1])[:modules]
        end = max((m[1] + m[2] for m in mods), default=0)
        ops = [e for e in d["ops"] if e[1] < end
               and (e[2] >= min_ns or "tpu_custom_call" in e[0])]
        small.append({"name": d["name"],
                      "modules": [[m[0][:name_chars], m[1], m[2]] for m in mods],
                      "ops": [[kernel_label(e[0], name_chars), e[1], e[2]] for e in ops]})
    with open(path, "w") as f:
        json.dump({"devices": small}, f)


def kernel_label(name: str, chars: int = 160) -> str:
    """An operation's name cut to ``chars``, keeping the mark of a Pallas
    kernel (its custom-call target sits far into the HLO text)."""
    if len(name) <= chars:
        return name
    mark = " tpu_custom_call" if "tpu_custom_call" in name else ""
    return name[:chars] + mark


def load_extract(path: str) -> dict:
    with open(path) as f:
        raw = json.load(f)
    return {"devices": [{"name": d["name"],
                         "modules": [tuple(e) for e in d["modules"]],
                         "ops": [tuple(e) for e in d["ops"]]}
                        for d in raw["devices"]]}


# -- arithmetic on event lists ------------------------------------------------

def matching(events: Iterable[Event], patterns: Sequence) -> List[Event]:
    """Events whose name matches any of ``patterns``. A pattern is a
    substring, or a tuple of substrings that must all be there."""
    def hit(name, p):
        return p in name if isinstance(p, str) else all(q in name for q in p)

    return [e for e in events if any(hit(e[0], p) for p in patterns)]


def total_seconds(events: Iterable[Event]) -> float:
    return sum(e[2] for e in events) / 1e9


def union_seconds(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    spans = sorted((e[1], e[1] + e[2]) for e in events)
    busy, end = 0, None
    for s, t in spans:
        if end is None or s > end:
            busy += t - s
            end = t
        elif t > end:
            busy += t - end
            end = t
    return busy / 1e9


def inside(events: Iterable[Event], parents: Iterable[Event]) -> List[Event]:
    """Events that start within one of ``parents``' intervals."""
    spans = sorted((p[1], p[1] + p[2]) for p in parents)
    starts = [s for s, _ in spans]
    import bisect

    out = []
    for e in events:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < spans[i][1]:
            out.append(e)
    return out


def span_seconds(trace: dict) -> float:
    """First device event's start to the last one's end, over all chips."""
    lo, hi = None, None
    for d in trace["devices"]:
        for e in list(d["ops"]) + list(d["modules"]):
            lo = e[1] if lo is None else min(lo, e[1])
            hi = e[1] + e[2] if hi is None else max(hi, e[1] + e[2])
    return 0.0 if lo is None else (hi - lo) / 1e9


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    chips in the trace."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    return sum(union_seconds(d["ops"] or d["modules"]) for d in devs) / len(devs)


def top_ops(trace: dict, top: int = 10) -> List[list]:
    """[name, seconds] of the operations that took most device time, summed
    over executions and averaged over chips."""
    total: Dict[str, float] = {}
    for d in trace["devices"]:
        for name, _, dur in d["ops"]:
            if name.startswith(CONTAINER_OPS):
                continue      # a loop's own event spans the operations in it
            total[name] = total.get(name, 0.0) + dur
    n = max(len(trace["devices"]), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[kernel_label(k), v / 1e9 / n] for k, v in ranked]


def idle_gaps(trace: dict, top: int = 10) -> List[list]:
    """[label, seconds] of the longest gaps between device operations on the
    first chip. Until the program annotates its phases a gap can only be
    labelled by the jitted program that ended it."""
    if not trace["devices"]:
        return []
    d = trace["devices"][0]
    ops = sorted(d["ops"] or d["modules"], key=lambda e: e[1])
    mods = sorted(d["modules"], key=lambda e: e[1])
    mod_starts = [m[1] for m in mods]
    import bisect

    gaps, end = [], None
    for name, start, dur in ops:
        if end is not None and start > end:
            i = bisect.bisect_right(mod_starts, start) - 1
            label = mods[i][0] if i >= 0 and start < mods[i][1] + mods[i][2] else name
            gaps.append((start - end, f"before:{label}"))
        end = start + dur if end is None else max(end, start + dur)
    gaps.sort(reverse=True)
    return [[label, g / 1e9] for g, label in gaps[:top]]
