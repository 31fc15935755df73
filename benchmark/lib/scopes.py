"""Each part of the train step's device time, by the scope the program enters
at it: ``pyspark_tf_gke_tpu/ops/pallas/scope.py::part_scope`` names the step's
parts ``embed``, ``mixer``, ``ffn``, ``experts_walk``, ``head_loss`` and
``optimizer``, and every HLO instruction traced under one carries
``part.<name>`` in its ``op_name`` metadata.

An operation of the trace is joined to its ``op_name`` through the program's
HLO: a v5e's ``XLA Ops`` events carry no ``op_name`` of their own (PR 37 looked,
PERF.md section 3), but the profiler keeps each program's optimized HLO as the
stat ``Hlo Proto`` of its event metadata in the plane ``/host:metadata``,
which ``ProfileData`` does not show (the plane has no lines). The
``.xplane.pb`` is read by its wire format, the module parsed by
``HloModule.from_serialized_hlo_module_proto``, and an event joined by its
instruction's name, the text before `` = `` in the event's name.

Which part an operation is in:

* the last ``part.<name>`` in its ``op_name``: a part entered inside another
  wins (``experts_walk`` inside ``ffn``);
* a fusion's is what its fused instructions carry, never XLA's name for the
  fusion: one part, or where they carry two or more, all of them
  (``mixer+optimizer``), read as ``shared``, so that no part takes another's
  work. XLA fuses each weight's Adam update into the matmul that makes its
  gradient, and the residual sum that ends one sublayer into the norm that
  starts the next: those fusions are ``shared``. Only a fusion whose
  instructions carry no ``op_name`` at all (a layout change) takes XLA's;
* an operation with no part of its own takes the part of the innermost loop or
  branch (``%while``, ``%conditional``, ``%call``) it runs in: XLA's
  ``ragged_dot`` kernels (``%ragged-dot-none``) carry their own name as
  ``op_name``, and they run inside the held experts' walk;
* everything else is ``unscoped``: what XLA adds without metadata (layout
  copies, the broadcasts that zero a loop's carry) and what no part holds.

A container is never counted, only what runs in it. The time of a part is the
sum of its operations' device durations inside ``jit_train_step`` executions
on the first chip, per execution.

Plain form, for a test's ``ctx["ops"]`` and a recorded extract (parts
resolved, a shared fusion's as its parts joined by ``+``; ``modules`` the
executions; ``rest_ns`` the summed time by part of the operations an extract
leaves out)::

    {"executions": n, "modules": [[name, start_ns, dur_ns], ...],
     "ops": [[name, start_ns, dur_ns, part], ...], "rest_ns": {part: ns, ...}}
"""

import bisect
import os
import re
import sys
from typing import Dict, List, Optional, Sequence

from lib import trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_ROOT = os.path.join(ROOT, ".bench_cache", "trace")
PROGRAM = ("jit_train_step",)
UNSCOPED = "unscoped"
SHARED = "shared"
PART = re.compile(r"(?:^|[/(;])part\.(\w+)")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"

_cache: Dict[tuple, Optional[dict]] = {}


def part_of(op_name: Optional[str]) -> Optional[str]:
    """The last ``part.<name>`` in an ``op_name``; ``None`` where it has none."""
    found = PART.findall(op_name or "")
    return found[-1] if found else None


def bucket(part: str) -> str:
    """The part an operation's time is read under: ``shared`` for a fusion
    that joins parts (``mixer+optimizer``), else its own."""
    return SHARED if "+" in part else part


def is_container(name: str) -> bool:
    return name.startswith(T.CONTAINER_OPS)


def is_kernel(name: str, kernels: Sequence) -> bool:
    return bool(T.matching([(name, 0, 0)], kernels))


# -- the readers' arithmetic ------------------------------------------------------

def ms_by_part(found: Optional[dict], leave_out: Sequence = ()) -> Optional[Dict[str, float]]:
    """Device ms per execution of each part (and ``shared`` and
    ``unscoped``), containers never counted, operations matching
    ``leave_out`` (``lib/trace.py::matching`` patterns) left out. ``None``
    where there is nothing to read or no operation carries a part: a program
    without the scopes."""
    if not found or not found["executions"]:
        return None
    ns: Dict[str, float] = {}
    for part, dur in (found.get("rest_ns") or {}).items():
        ns[bucket(part)] = ns.get(bucket(part), 0) + dur
    for name, _, dur, part in found["ops"]:
        if is_container(name) or (leave_out and is_kernel(name, leave_out)):
            continue
        ns[bucket(part)] = ns.get(bucket(part), 0) + dur
    if not any(ns.get(p) for p in ns if p != UNSCOPED):
        return None
    return {p: v / 1e6 / found["executions"] for p, v in ns.items()}


def part_ms(ctx: dict, parts: Sequence[str], leave_out: Sequence = ()) -> Optional[float]:
    """A reader's number: the sum of ``parts`` in ms per execution."""
    by_part = ms_by_part(ops_of(ctx), leave_out)
    if by_part is None:
        return None
    return sum(by_part.get(p, 0.0) for p in parts)


def ops_of(ctx: dict) -> Optional[dict]:
    """A reader's operations: ``ctx["ops"]`` where a test hands them over,
    else the newest trace's."""
    return ctx["ops"] if "ops" in ctx else load_ops()


# -- resolving parts ----------------------------------------------------------------

def resolve(ops: List[list], parts: Sequence[Optional[str]]) -> List[list]:
    """``[name, start, dur, part]`` of ``ops`` (``[name, start, dur]``, one
    device's, sorted by start) with their parts (:func:`hlo_parts`, ``None``
    for none): a part of its own, or the innermost enclosing container's, or
    ``unscoped``."""
    out, stack = [], []              # stack: (end_ns, part) of open containers
    for (name, start, dur), own in zip(ops, parts):
        while stack and stack[-1][0] <= start:
            stack.pop()
        part = own or (stack[-1][1] if stack else None)
        if is_container(name):
            stack.append((start + dur, part))
        out.append([name, start, dur, part or UNSCOPED])
    return out


def hlo_parts(text: str) -> Dict[str, str]:
    """Instruction name -> part over a module's HLO text, for every
    instruction that has one: its ``op_name``'s, or for a fusion the parts
    its fused instructions carry (nested fusions' included), joined by ``+``
    where there are several, and XLA's ``op_name`` for the fusion only where
    they carry none. Names are unique in a module, fused computations' among
    them."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, List[str]] = {}
    comp = None
    line_re = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = ")
    name_re = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
    for line in text.split("\n"):
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        m = line_re.match(line)
        if not m:
            continue
        inst = m.group(1)
        members.setdefault(comp, []).append(inst)
        found = name_re.search(line)
        part = part_of(found.group(1)) if found else None
        if part:
            own[inst] = part
        called = re.search(r"calls=%([^\s,]+)", line)
        if called:
            calls[inst] = called.group(1)

    carried: Dict[str, frozenset] = {}

    def fused(comp):
        if comp not in carried:
            carried[comp] = frozenset()              # a cycle carries nothing
            found = set()
            for inst in members.get(comp, ()):
                if inst in calls:
                    found |= fused(calls[inst])
                elif inst in own:
                    found.add(own[inst])
            carried[comp] = frozenset(found)
        return carried[comp]

    out = dict(own)
    for inst, comp in calls.items():
        if fused(comp):
            out[inst] = "+".join(sorted(fused(comp)))
    return out


# -- loading ---------------------------------------------------------------------

def load_ops(trace_root: str = TRACE_ROOT, program: Sequence = PROGRAM) -> Optional[dict]:
    """The plain form of the newest trace under ``trace_root``: the first
    chip's operations inside the executions of ``program``, parts resolved;
    ``None`` where there is no trace, no execution, or no ``op_name`` to be
    had for any operation."""
    try:
        path = T.find_xplane(trace_root)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path), tuple(program))
    if key not in _cache:
        _cache.clear()
        try:
            _cache[key] = _load(path, program)
        except Exception as e:  # noqa: BLE001 - a trace this cannot read gives no parts
            print(f"lib/scopes.py: no parts read from {path}: {e!r}", file=sys.stderr)
            _cache[key] = None
    return _cache[key]


def _load(path: str, program: Sequence) -> Optional[dict]:
    from jax.profiler import ProfileData

    dev = next((p for p in ProfileData.from_file(path).planes
                if T.DEVICE_PLANE.match(p.name)), None)
    if dev is None:
        return None
    mods, ops = [], []
    for line in dev.lines:
        events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
        if line.name in T.MODULE_LINES:
            mods = events
        elif line.name in T.OP_LINES:
            ops = events
    execs = sorted(T.matching(mods, program), key=lambda m: m[1])
    tables = program_parts(path, program)
    if not execs or not tables:
        return None
    starts = [m[1] for m in execs]

    def execution(t):
        i = bisect.bisect_right(starts, t) - 1
        return execs[i] if i >= 0 and t < execs[i][1] + execs[i][2] else None

    kept, parts = [], []
    for op in sorted(ops, key=lambda e: (e[1], -e[2])):
        run = execution(op[1])
        if run is not None:
            kept.append(op)
            # the instruction's name: the HLO text before `` = ``, without ``%``
            parts.append(tables.get(run[0], {}).get(op[0].split(" = ", 1)[0].lstrip("%")))
    return {"executions": len(execs), "modules": [list(m) for m in execs],
            "ops": resolve(kept, parts), "rest_ns": {}}


def program_parts(path: str, program: Sequence) -> Dict[str, Dict[str, str]]:
    """For each program of the trace named like ``program``, by its name in
    the trace (``jit_train_step(<fingerprint>)``, as the ``XLA Modules`` line
    names an execution): :func:`hlo_parts` of its HLO from the plane
    ``/host:metadata``. Empty where the trace keeps none."""
    from jax._src.lib import xla_client

    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for name, proto in _metadata_programs(buf):
        module = next((v for n, w, v in _fields(proto) if n == 1 and w == 2), None)
        if module is not None and T.matching([(name, 0, 0)], program):
            text = xla_client._xla.HloModule.from_serialized_hlo_module_proto(
                bytes(proto[module[0]:module[1]])).to_string()
            out[name] = hlo_parts(text)
    return out


def _metadata_programs(buf: memoryview):
    """``(event name, HloProto bytes)`` of each program in the plane
    ``/host:metadata`` of an ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``:
    XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5;
    XEventMetadata.name 2, .stats 5; XStatMetadata.id 1, .name 2; XStat
    .metadata_id 1, .bytes_value 6)."""
    for num, wire, plane in _fields(buf):
        if num != 1 or wire != 2:
            continue
        fields = list(_fields(buf, *plane))
        name = next((_text(buf, v) for n, w, v in fields if n == 2 and w == 2), "")
        if name != METADATA_PLANE:
            continue
        stat_ids = set()
        for n, w, entry in fields:
            if n == 5 and w == 2:
                for n2, w2, meta in _fields(buf, *entry):
                    if n2 == 2 and w2 == 2:
                        sub = {k: v for k, _, v in _fields(buf, *meta)}
                        if 2 in sub and _text(buf, sub[2]) == HLO_PROTO_STAT:
                            stat_ids.add(sub.get(1))
        for n, w, entry in fields:
            if n != 4 or w != 2:
                continue
            for n2, w2, meta in _fields(buf, *entry):
                if n2 != 2 or w2 != 2:
                    continue
                event, proto = "", None
                for n3, w3, v3 in _fields(buf, *meta):
                    if n3 == 2 and w3 == 2:
                        event = _text(buf, v3)
                    elif n3 == 5 and w3 == 2:
                        stat = {k: v for k, _, v in _fields(buf, *v3)}
                        if stat.get(1) in stat_ids and isinstance(stat.get(6), tuple):
                            proto = buf[stat[6][0]:stat[6][1]]
                if proto is not None:
                    yield event, proto


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """``(field number, wire type, value)`` of a protobuf message: a varint's
    value, a length-delimited field's ``(start, end)`` in ``buf``, a fixed
    field's bytes."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            return
        yield num, wire, value


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


# -- a recorded extract -------------------------------------------------------------

def save_extract(found: dict, path: str, execution: int = 0, min_ns: int = 50_000,
                 name_chars: int = 160) -> None:
    """One execution of the plain form small enough to keep beside the tests:
    its containers, kernel launches and operations that run ``min_ns`` or
    longer with their parts, names cut (``lib/trace.py::kernel_label``), the
    summed time of the rest by part, and its busy time (the union of its
    operations' intervals, ``busy_ns``)."""
    import json

    name, lo, dur = found["modules"][execution]
    ops = [op for op in found["ops"] if lo <= op[1] < lo + dur]
    kept, rest = [], {}
    for op in ops:
        if op[2] >= min_ns or is_container(op[0]) or "tpu_custom_call" in op[0]:
            kept.append([T.kernel_label(op[0], name_chars), *op[1:]])
        else:
            rest[op[3]] = rest.get(op[3], 0) + op[2]
    busy = T.union_seconds([op[:3] for op in ops if not is_container(op[0])])
    with open(path, "w") as f:
        json.dump({"executions": 1, "modules": [[name, lo, dur]], "ops": kept,
                   "rest_ns": rest, "busy_ns": round(busy * 1e9)}, f)


def load_extract(path: str) -> dict:
    import json

    with open(path) as f:
        return json.load(f)
