#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that loads one cell, makes weights and traffic from ``--seed``,
warms the cell's own shapes (set-up), measures for ``--seconds`` and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (``--trace 0``: the cell's end-to-end
metrics; ``--trace 1``: its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks`` (each compared number beside its limit,
also the last lines of standard error).

Driven by data: the cell, its configuration, its traffic mix and every
per-layer metric are files found by the names in ``BENCHMARK.json``
(benchmark/README.md). It exits non-zero and prints no result when JAX finds
no TPU or too few chips, or outside a checkout that holds the program.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def _die(code: int, msg: str):
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(code)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_file(bench: dict, sub: str, name: str, exts=(".json",)) -> str:
    """``<path>/<sub>/<name><ext>`` in the first of the benchmark's
    directories that has it."""
    for base in bench["paths"]:
        for ext in exts:
            path = os.path.join(ROOT, base, sub, name + ext)
            if os.path.exists(path):
                return path
    raise FileNotFoundError(
        f"no {sub}/{name}{'|'.join(exts)} under {bench['paths']}")


def load_cell(bench: dict, workload: str) -> dict:
    """Everything that defines one cell, from the files its name finds."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"workload {workload!r} is not in the benchmark file")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return {
        "workload": entry,
        "config": _load_json(os.path.join(ROOT, config["file"])),
        "cell": _load_json(find_file(bench, "cells", workload)),
        "traffic": _load_json(find_file(bench, "traffic", entry["traffic"])),
    }


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """Names of the metrics of ``kind`` that this cell reports."""
    return [m["name"] for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def read_metric(bench: dict, name: str, ctx: dict):
    """Run the per-layer metric's own reader; ``None`` when it finds
    nothing to read (the metric is then left out of the line)."""
    path = find_file(bench, "metrics", name, exts=(".py",))
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def setup_jax(cache_root: str):
    """Persistent compile cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program kept whatever it cost
    to compile; the program's event trail kept inside the checkout too."""
    os.makedirs(cache_root, exist_ok=True)
    os.environ.setdefault("PYSPARK_TF_GKE_TPU_EVENT_TRAIL",
                          os.path.join(cache_root, "events.jsonl"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(cache_root, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


class CompileCounter:
    """Counts backend compilations (cache hits included: a program that is
    built or loaded inside the window stalls it either way) and sums what
    JAX reports of tracing, lowering and compiling-or-loading, so that
    set-up can be broken down (``mark``). A jit traced inside another is
    reported at each level, so ``trace_s`` can exceed the wall time."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    PARTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
             EVENT: "compile_or_load_s"}

    def __init__(self):
        import jax.monitoring as monitoring

        self.count, self.cache, self.longest = 0, {}, []
        self.seconds = dict.fromkeys(self.PARTS.values(), 0.0)
        self._marked = dict(self.seconds, compiles=0)
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kwargs):
        if event in self.PARTS:
            self.seconds[self.PARTS[event]] += float(duration)
        if event == self.EVENT:
            self.count += 1
            self.longest = sorted(self.longest + [round(float(duration), 2)])[-6:]

    def _on_event(self, event, **kwargs):
        if "compilation_cache" in event:
            key = event.rsplit("/", 1)[-1]
            self.cache[key] = self.cache.get(key, 0) + 1

    def mark(self) -> dict:
        """What was traced, lowered and compiled or loaded since the last mark."""
        now = dict(self.seconds, compiles=self.count)
        part = {k: now[k] - self._marked[k] for k in now}
        self._marked = now
        return part

    def summary(self) -> dict:
        return {"compiles": self.count, **self.seconds,
                "longest_s": self.longest, **self.cache}


def device_block(devices, memory_peak: int, extra=None) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    out.update(extra or {})
    return out


def prepare(argv=None, allow_cpu: bool = False):
    """Parse the command, load the cell's files, look for the chips and
    build the context a runner takes. Returns ``(args, bench, ctx)``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "pyspark_tf_gke_tpu")):
        _die(2, "no program beside the benchmark (pyspark_tf_gke_tpu/ missing)")
    if not os.path.exists(args.bench):
        _die(2, f"{args.bench} missing")
    bench = _load_json(args.bench)
    spec = load_cell(bench, args.workload)
    chips = int(spec["workload"]["chips"])

    jax = setup_jax(os.path.join(ROOT, ".bench_cache"))
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        _die(3, f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        _die(3, f"cell needs {chips} chip(s), JAX found {len(devices)}")
    devices = devices[:chips]

    from lib import peaks

    on_chip = devices[0].platform == "tpu"
    ctx = {
        "t_start": _T_START, "root": ROOT, "bench": bench, "spec": spec,
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "devices": devices, "chips": chips,
        "on_chip": on_chip, "compiles": CompileCounter(),
        "peaks": peaks.peaks_for(devices[0].device_kind) if on_chip else None,
        "trace_dir": os.path.join(ROOT, ".bench_cache", "trace", args.workload),
    }
    return args, bench, ctx


def main(argv=None, allow_cpu: bool = False) -> dict:
    from lib import checks

    args, bench, ctx = prepare(argv, allow_cpu)
    spec, devices, on_chip = ctx["spec"], ctx["devices"], ctx["on_chip"]
    runner = importlib.import_module(f"runners.{spec['cell']['runner']}")
    out = runner.run(ctx)

    rows = checks.compare(out["readings"], spec["cell"]["limits"])
    metrics = {}
    if on_chip:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
        if args.trace:
            for name in cell_metrics(bench, args.workload, "per_layer"):
                value = read_metric(bench, name, out["layers"])
                if value is not None:
                    metrics[name] = {"value": float(value), "unit": units[name]}
        else:
            for name in cell_metrics(bench, args.workload, "end_to_end"):
                metrics[name] = {"value": float(out["end_to_end"][name]),
                                 "unit": units[name]}
    result = {
        "correct": checks.verdict(rows),
        "attempted": int(out["attempted"]), "failed": int(out["failed"]),
        "metrics": metrics,
        "device": device_block(devices, out["memory_peak_bytes"],
                               out.get("device_extra")),
    }
    if not on_chip:
        result["refused"] = "not a device run: no metric is written off the TPU"
    if args.trace and out.get("breakdown"):
        result["breakdown"] = out["breakdown"]
    result["info"] = dict(out.get("info", {}), compile=ctx["compiles"].summary())
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    sys.stdout.flush()
    checks.print_rows(rows)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
    # daemon threads of the program (watchdogs) must not hold the exit
    sys.stdout.flush()
    os._exit(0)
