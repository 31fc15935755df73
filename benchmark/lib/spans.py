"""The program's own spans in a traced run, beside the device planes that
``lib/trace.py`` reduces.

Two sources, both written by the program (``pyspark_tf_gke_tpu/obs/trace.py``):

* **Annotations** in the profiler's host plane (``/host:CPU``), one line per
  thread, in the same ``.xplane.pb`` and on the same timebase as the device
  planes: ``train.fit`` > ``train.epoch`` > ``train.input_wait`` /
  ``train.step_dispatch`` / ``train.first_step_sync`` / ``train.epoch_sync``
  on the loop thread, ``engine.<phase>`` on a server's driver thread. A
  reader gets the newest trace under ``<checkout>/.bench_cache/trace/``
  (``ctx`` does not carry the directory). Thread lines share names (every
  Python thread's line is called after the process), so a thread is found
  by what it holds, not by its name.
* **The ring** of the process-default tracer (``obs.trace.get_tracer()``):
  one trace per ``Trainer.fit`` / ``init_state`` call with JAX's own
  ``jax.trace`` / ``jax.lower`` / ``jax.compile`` as children, wall-clock
  seconds.

A program that has neither (the parent of the PR that added them) gives
``None`` from both loaders and every reader then returns ``None``.

Plain forms, so that an extract can sit beside the tests::

    {"threads": [{"name": "python3",
                  "events": [[name, start_ns, dur_ns], ...]}, ...]}
    [{"trace_id": ..., "spans": [{"name", "span_id", "parent_id",
                                  "start", "end", "attrs"}, ...]}, ...]
"""

import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from lib import trace as T

Event = Tuple[str, int, int]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_ROOT = os.path.join(ROOT, ".bench_cache", "trace")
HOST_PLANE = "/host:CPU"
# what the program writes; the runtime's own host events (thousands a step)
# are left out
PROGRAM_PREFIXES = ("train.", "engine.", "jax.")
NONE = "none"

_host_cache: Dict[tuple, Optional[dict]] = {}


# -- loading --------------------------------------------------------------------

def load_host(trace_root: str = TRACE_ROOT) -> Optional[dict]:
    """Program annotations of the newest trace under ``trace_root``, by
    thread line; ``None`` when there is no trace or it holds none."""
    try:
        path = T.find_xplane(trace_root)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _host_cache:
        from jax.profiler import ProfileData

        threads = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events if e.name.startswith(PROGRAM_PREFIXES)]
                if events:
                    threads.append({"name": line.name, "events": events})
        _host_cache.clear()
        _host_cache[key] = {"threads": threads} if threads else None
    return _host_cache[key]


def ring() -> Optional[List[dict]]:
    """Every trace the process-default tracer retains, oldest first;
    ``None`` where the program has no such tracer."""
    try:
        from pyspark_tf_gke_tpu.obs.trace import get_tracer
    except ImportError:
        return None
    return get_tracer().traces(limit=1 << 20)


def host_of(ctx: dict) -> Optional[dict]:
    """A reader's host annotations: ``ctx["host"]`` where a test hands them
    over, else the newest trace's."""
    return ctx["host"] if "host" in ctx else load_host()


def ring_of(ctx: dict) -> Optional[List[dict]]:
    return ctx["ring"] if "ring" in ctx else ring()


def save_extract(host: dict, trace: dict, path: str, program: Sequence,
                 executions: int = 4, min_gap_ns: int = 1_000) -> None:
    """A recorded extract small enough to keep beside the tests, host and
    device side of the same stretch of one traced run: up to the end of the
    first ``executions`` executions of ``program`` on the first chip, the
    program annotations that start before it, the modules, the Pallas
    kernels (``tpu_custom_call``, names cut) and, in place of the other ten
    thousand operations a step, the intervals in which any operation ran
    (``busy``; gaps shorter than ``min_gap_ns`` closed)."""
    dev = trace["devices"][0]
    mods = sorted(T.matching(dev["modules"], program), key=lambda m: m[1])[:executions]
    end = max(m[1] + m[2] for m in mods)
    ops = sorted((e for e in dev["ops"] if e[1] < end), key=lambda e: e[1])
    busy: List[List] = []
    for _, start, dur in ops:
        if busy and start - (busy[-1][1] + busy[-1][2]) < min_gap_ns:
            busy[-1][2] = max(busy[-1][2], start + dur - busy[-1][1])
        else:
            busy.append(["busy", start, dur])
    kernels = [[T.kernel_label(e[0]), e[1], e[2]] for e in ops if "tpu_custom_call" in e[0]]
    with open(path, "w") as f:
        json.dump({
            "threads": [{"name": t["name"],
                         "events": [list(e) for e in t["events"] if e[1] < end]}
                        for t in host["threads"] if any(e[1] < end for e in t["events"])],
            "devices": [{"name": dev["name"],
                         "modules": [[m[0][:160], m[1], m[2]]
                                     for m in dev["modules"] if m[1] < end],
                         "ops": sorted(busy + kernels, key=lambda e: e[1])}],
        }, f)


def load_extract(path: str) -> Tuple[dict, dict]:
    """``(host, trace)`` of a file :func:`save_extract` wrote."""
    with open(path) as f:
        raw = json.load(f)
    host = {"threads": [{"name": t["name"], "events": [tuple(e) for e in t["events"]]}
                        for t in raw["threads"]]}
    trace = {"devices": [{"name": d["name"],
                          "modules": [tuple(e) for e in d["modules"]],
                          "ops": [tuple(e) for e in d["ops"]]}
                         for d in raw["devices"]]}
    return host, trace


# -- annotations ----------------------------------------------------------------

def named(events: Iterable[Event], name: str) -> List[Event]:
    return sorted((e for e in events if e[0] == name), key=lambda e: e[1])


def loop_thread(host: Optional[dict], marker: str) -> List[Event]:
    """Events of the thread that holds most ``marker`` annotations (the
    trainer's loop for ``train.step_dispatch``); empty when none does."""
    best: List[Event] = []
    count = 0
    for t in (host or {}).get("threads", []):
        n = sum(1 for e in t["events"] if e[0] == marker)
        if n > count:
            best, count = list(t["events"]), n
    return best


def innermost_at(events: Sequence[Event], t_ns: int) -> str:
    """Name of the innermost annotation that covers ``t_ns``, or ``none``."""
    best = None
    for e in events:
        if e[1] <= t_ns < e[1] + e[2] and (best is None or e[1] >= best[1]):
            best = e
    return best[0] if best else NONE


def time_by_label(events: Sequence[Event], start_ns: int, end_ns: int) -> Dict[str, int]:
    """Nanoseconds of ``[start_ns, end_ns)`` under each innermost annotation
    (``none`` where no annotation covers)."""
    cuts = {start_ns, end_ns}
    for e in events:
        for t in (e[1], e[1] + e[2]):
            if start_ns < t < end_ns:
                cuts.add(t)
    out: Dict[str, int] = {}
    edges = sorted(cuts)
    for a, b in zip(edges, edges[1:]):
        label = innermost_at(events, a)
        out[label] = out.get(label, 0) + (b - a)
    return out


def attribute(gaps: Sequence[Sequence[int]], host_events: Sequence[Event]) -> List[dict]:
    """For each device gap ``[start_ns, dur_ns]``: what the loop thread was
    inside while the device sat idle. ``at_start`` is the innermost program
    annotation that covers the gap's start (``none`` outside every one),
    ``by_label`` the gap's nanoseconds under each innermost annotation, and
    ``label`` the one that holds most of them. The two differ where the host
    leaves a sync a few microseconds into a gap and spends the rest of it
    getting the next step out: such a gap is the dispatch's, not the sync's."""
    out = []
    for start, dur in gaps:
        by_label = time_by_label(host_events, start, start + dur)
        out.append({"start_ns": start, "dur_ns": dur,
                    "at_start": innermost_at(host_events, start),
                    "label": max(by_label, key=by_label.get),
                    "by_label": by_label})
    return out


# -- device side ------------------------------------------------------------------

def program_span(dev: dict, program: Sequence) -> Optional[Tuple[int, int, List[Event]]]:
    """``(lo, hi, executions)``: first execution's start to the last one's
    end of the program named by ``program`` (the span ``device_idle.train``
    uses); ``None`` with fewer than two executions."""
    mods = sorted(T.matching(dev["modules"], program), key=lambda m: m[1])
    if len(mods) < 2:
        return None
    return mods[0][1], max(m[1] + m[2] for m in mods), mods


def device_gaps(dev: dict, lo: int, hi: int) -> List[List[int]]:
    """``[start_ns, dur_ns]`` of every interval inside ``[lo, hi)`` in which
    no operation ran on the device."""
    ops = sorted(((s, min(s + d, hi)) for _, s, d in (dev["ops"] or dev["modules"])
                  if lo <= s < hi))
    gaps, end = [], lo
    for s, t in ops:
        if s > end:
            gaps.append([end, s - end])
        end = max(end, t)
    if hi > end:
        gaps.append([end, hi - end])
    return gaps


def host_idle_ns(dev: dict, program: Sequence, host_events: Sequence[Event],
                 dispatch: str) -> Optional[Tuple[int, int]]:
    """``(idle_ns, host_ns)`` between the first execution's start and the
    last one's end of ``program``: all device idle time, and the part of it
    in which the host had not yet handed over the execution that ends the
    gap. Executions and ``dispatch`` annotations pair by ordinal; a gap is
    the host's from its start until that execution's ``dispatch``
    annotation ends (not at all if it ended before the gap began: the work
    was queued and the gap is the device's own launch gap, whatever the
    loop thread is blocked in meanwhile). A gap inside one execution is the
    device's own. ``None`` with fewer than two executions or where the
    annotations do not pair with them."""
    span = program_span(dev, program)
    calls = named(host_events, dispatch)
    if span is None or len(calls) != len(span[2]):
        return None
    lo, hi, mods = span
    idle = host = 0
    for start, dur in device_gaps(dev, lo, hi):
        idle += dur
        k = next((i for i, m in enumerate(mods) if start < m[1] <= start + dur), None)
        if k is not None:
            handed_over = calls[k][1] + calls[k][2]
            host += max(0, min(start + dur, handed_over) - start)
    return idle, host


def kernel_ms_per_execution(trace: Optional[dict], program: Sequence,
                            kernel: Sequence) -> Optional[float]:
    """Device milliseconds of the operations matching ``kernel`` inside the
    executions of ``program`` on the first chip, per execution."""
    if not trace or not trace["devices"]:
        return None
    dev = trace["devices"][0]
    mods = T.matching(dev["modules"], program)
    kernels = T.inside(T.matching(dev["ops"], kernel), mods)
    if not mods or not kernels:
        return None
    return T.total_seconds(kernels) * 1e3 / len(mods)


def dispatch_lags(dev: dict, program: Sequence, host_events: Sequence[Event],
                  dispatch: str) -> List[int]:
    """The clock check: for each execution of ``program``, in order, its
    start on the device less the start of the ``dispatch`` annotation of the
    same ordinal, in nanoseconds. One timebase means none is negative."""
    mods = sorted(T.matching(dev["modules"], program), key=lambda m: m[1])
    calls = named(host_events, dispatch)
    return [m[1] - c[1] for m, c in zip(mods, calls)]


# -- the ring -----------------------------------------------------------------------

def children(trace: dict, span: dict) -> List[dict]:
    return [s for s in trace["spans"] if s["parent_id"] == span["span_id"]]


def seconds(span: dict) -> float:
    return float(span["end"]) - float(span["start"])


def self_seconds(trace: dict, span: dict) -> float:
    """A span's duration less what its children cover (their union)."""
    kids = [(s["start"], s["end"]) for s in children(trace, span)]
    return seconds(span) - union_seconds(kids)


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for s, t in sorted(intervals):
        if end is None or s > end:
            total += t - s
            end = t
        elif t > end:
            total += t - end
            end = t
    return total


def under(trace: dict, names: Sequence[str], ancestor_prefix: str) -> List[dict]:
    """Spans called one of ``names`` with an ancestor whose name starts with
    ``ancestor_prefix``."""
    by_id = {s["span_id"]: s for s in trace["spans"]}

    def has_ancestor(s):
        while s["parent_id"] in by_id:
            s = by_id[s["parent_id"]]
            if s["name"].startswith(ancestor_prefix):
                return True
        return False

    return [s for s in trace["spans"] if s["name"] in names and has_ancestor(s)]


def union_under(traces: Optional[List[dict]], names: Sequence[str],
                ancestor_prefix: str) -> Optional[float]:
    """Seconds covered by the spans called one of ``names`` under an
    ``ancestor_prefix`` span, over all traces; nesting is counted once."""
    found = [(s["start"], s["end"]) for tr in traces or []
             for s in under(tr, names, ancestor_prefix)]
    return union_seconds(found) if found else None


def last_root(traces: Optional[List[dict]], name: str) -> Optional[Tuple[dict, dict]]:
    """``(trace, root span)`` of the newest trace whose root is ``name``."""
    for tr in reversed(traces or []):
        for s in tr["spans"]:
            if s["parent_id"] is None and s["name"] == name:
                return tr, s
    return None
