#!/usr/bin/env python3
"""The control and the planted faults of a training cell, judged as a run is:
taken on the chip at the cell's own size, each put through ``lib/checks.py``
with the cell's own limits, and each has to come out as NOT correct. The
benchmark's own runs never run this.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1,2,3

No program run. For each seed the reference's first steps in float32, then
the reference put in the program's place (a) in float8, the control; (b) with
half of the batch left out and the mean taken over the rest; (c) with the
state left unchanged by every step (learning rate 0). One JSON line per seed
with every compared number beside its limit and the verdict of each; the exit
code is 1 if any of them came out correct. The program's own readings (the
lower readings of PERF.md section 2) are the ``checks`` of its ordinary runs.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402


def judge(cfg, cell, mix, seed, rows) -> dict:
    """``{name: {"correct": bool, "checks": {number: {value, limit}}}}`` for
    the control and each fault of one seed."""
    from lib import checks
    from reference import gpt2
    from runners import train

    feed = train.Feed(mix, seed, cfg["vocab_size"], rows)
    batches = [feed.batch(k)["input_ids"] for k in range(train.PROOF_STEPS)]
    opt = cell["train"]["optimizer"]
    kw = dict(steps=train.PROOF_STEPS,
              rows_block=int(cell["check"]["reference_rows_block"]))
    ref = gpt2.train_steps(cfg, seed, batches, opt, **kw)
    stand_ins = {
        "control_fp8": gpt2.train_steps(cfg, seed, batches, opt, precision="fp8", **kw),
        "fault_half_batch": gpt2.train_steps(cfg, seed, batches, opt,
                                             keep_rows=rows // 2, **kw),
        "fault_state_unchanged": gpt2.train_steps(
            cfg, seed, batches, dict(opt, learning_rate=0.0), **kw),
    }
    out = {}
    for name, prog in stand_ins.items():
        table = checks.compare(train.compared(train.gaps(prog, ref)), cell["limits"])
        print(f"seed {seed} {name}:", file=sys.stderr)
        checks.print_rows(table)
        out[name] = {"correct": checks.verdict(table),
                     "checks": {r["name"]: {"value": r["value"], "limit": r["limit"]}
                                for r in table}}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    _, _, ctx = bench_run.prepare(["--workload", a.workload, "--seed", str(seeds[0]),
                                   "--seconds", "1", "--trace", "0"])
    spec = ctx["spec"]
    rows = int(spec["cell"]["train"]["rows_per_chip"]) * ctx["chips"]
    passed = 0
    for seed in seeds:
        verdicts = judge(spec["config"], spec["cell"], spec["traffic"], seed, rows)
        passed += sum(v["correct"] for v in verdicts.values())
        print(json.dumps({"workload": a.workload, "seed": seed, **verdicts}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
