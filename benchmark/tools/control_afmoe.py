#!/usr/bin/env python3
"""``tools/control.py`` for the AFMoE cell (that file names ``reference.gpt2``
and ``runners.train``): the control and the planted faults, judged as a run is,
on the chip at the cell's own size, each put through ``lib/checks.py`` with the
cell's own limits; each has to come out NOT correct.

    python3 benchmark/tools/control_afmoe.py --workload <cell> --seeds 1,2,3

No program run. For each seed the reference's first steps in float32, then the
reference put in the program's place (a) in float8, the control; (b) with half
of the batch left out and the mean taken over the rest; (c) with the window
ignored on the sliding layers, what a kernel does whose schedule walks from the
sequence's start; (d) with the rotation left off them; (e) with the output gate
left out. One JSON line per seed; the exit code is 1 if any came out correct.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402


def judge(cfg, cell, mix, seed, rows) -> dict:
    from lib import checks
    from reference import afmoe
    from runners import train_afmoe as runner

    feed = runner.Feed(mix, seed, cfg["vocab_size"], rows)
    batches = [feed.batch(k)["input_ids"] for k in range(runner.PROOF_STEPS)]
    opt = cell["train"]["optimizer"]
    kw = dict(steps=runner.PROOF_STEPS,
              rows_block=int(cell["check"]["reference_rows_block"]))
    ref = afmoe.train_steps(cfg, seed, batches, opt, **kw)
    stand_ins = {"control_fp8": dict(precision="fp8"),
                 "fault_half_batch": dict(keep_rows=rows // 2),
                 **{f"fault_{f}": dict(fault=f) for f in afmoe.FAULTS}}
    out = {}
    for name, how in stand_ins.items():
        prog = afmoe.train_steps(cfg, seed, batches, opt, **kw, **how)
        table = checks.compare(runner.compared(runner.gaps(prog, ref)), cell["limits"])
        print(f"seed {seed} {name}:", file=sys.stderr)
        checks.print_rows(table)
        out[name] = {"correct": checks.verdict(table),
                     "checks": {r["name"]: {"value": r["value"], "limit": r["limit"]}
                                for r in table}}
    return out


def main(argv=None, allow_cpu=False):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--bench", default=None)
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    extra = ["--bench", a.bench] if a.bench else []
    _, _, ctx = bench_run.prepare(["--workload", a.workload, "--seed", str(seeds[0]),
                                   "--seconds", "1", "--trace", "0"] + extra,
                                  allow_cpu=allow_cpu)
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
