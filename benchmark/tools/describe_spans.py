#!/usr/bin/env python3
"""Run one cell with ``--trace 1`` in this process and print what the
program's own spans say about it (``lib/spans.py``): every device gap of
``MIN_GAP_NS`` or more between the first and the last train step with what
the loop thread was inside meanwhile, the sums per label, the clock check
(device start of each step against its ``train.step_dispatch`` annotation)
and the span tree of the program's ring with self times.

    python3 benchmark/tools/describe_spans.py --workload <cell> --seed <n> \\
        --seconds <s> [--extract <dir>]

``--extract`` also writes the first steps' host annotations with the device
side of the same stretch, and the ring, as JSON (what sits in ``tests/data``
came from it). The ring lives in
the process that ran the program, which is why this tool runs the cell
itself and cannot be pointed at an old trace directory.
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
from lib import spans as S  # noqa: E402
from lib import trace as T  # noqa: E402

MIN_GAP_NS = 20_000
PROGRAM = ("jit_train_step",)
LOOP = "train.step_dispatch"


def gap_report(dev: dict, loop: list) -> dict:
    span = S.program_span(dev, PROGRAM)
    if span is None:
        return {}
    lo, hi, mods = span
    gaps = S.attribute(S.device_gaps(dev, lo, hi), loop)
    sums, idle = {}, 0
    for g in gaps:
        idle += g["dur_ns"]
        for label, ns in g["by_label"].items():
            sums[label] = sums.get(label, 0) + ns
    lags = S.dispatch_lags(dev, PROGRAM, loop, LOOP)
    return {
        "span_ms": (hi - lo) / 1e6, "steps": len(mods), "idle_ms": idle / 1e6,
        "gaps": [{"at_ms": (g["start_ns"] - lo) / 1e6, "us": g["dur_ns"] / 1e3,
                  "label": g["label"], "at_start": g["at_start"],
                  "by_label_us": {k: v / 1e3 for k, v in g["by_label"].items()}}
                 for g in gaps if g["dur_ns"] >= MIN_GAP_NS],
        "idle_us_by_label": {k: v / 1e3 for k, v in sorted(sums.items())},
        "dispatch_lag_us": ({"n": len(lags), "min": min(lags) / 1e3,
                             "median": statistics.median(lags) / 1e3,
                             "max": max(lags) / 1e3} if lags else None),
    }


def span_tree(traces: list) -> list:
    lines = []
    for tr in traces:
        def walk(span, depth):
            attrs = {k: (round(v, 3) if isinstance(v, float) else v)
                     for k, v in span["attrs"].items()}
            lines.append(f"{'  ' * depth}{span['name']}  {S.seconds(span) * 1e3:.1f} ms"
                         f"  self {S.self_seconds(tr, span) * 1e3:.1f} ms  {attrs}")
            for kid in sorted(S.children(tr, span), key=lambda s: s["start"]):
                walk(kid, depth + 1)

        for root in (s for s in tr["spans"] if s["parent_id"] is None):
            walk(root, 0)
    return lines


if __name__ == "__main__":
    argv = sys.argv[1:]
    extract = None
    if "--extract" in argv:
        i = argv.index("--extract")
        extract = argv[i + 1]
        del argv[i:i + 2]
    run.main(argv + ["--trace", "1"])
    host, traces = S.load_host(), S.ring() or []
    trace = T.load(S.TRACE_ROOT)
    dev = trace["devices"][0]
    loop = S.loop_thread(host, LOOP)
    print("== device gaps and what the loop thread was inside")
    print(json.dumps(gap_report(dev, loop), indent=1))
    print("== the program's ring")
    print("\n".join(span_tree(traces)))
    if extract:
        os.makedirs(extract, exist_ok=True)
        S.save_extract(host, trace, os.path.join(extract, "trace_train_host.json"), PROGRAM)
        with open(os.path.join(extract, "ring_train.json"), "w") as f:
            json.dump(traces, f)
    sys.stdout.flush()
    os._exit(0)
