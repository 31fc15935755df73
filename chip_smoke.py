#!/usr/bin/env python3
"""Chip gate: does the system still start on the TPU?

Drives the repo's two hot paths once, through the entry points a user
calls, at the full width of the GPT-2-small shape the repo trains and
serves (hidden 768, 12 layers, 12 heads, intermediate 3072, bf16,
sequence 1024, byte tokenizer), with weights trained for a few steps
from a seed:

  kernels   python -m pyspark_tf_gke_tpu.ops.pallas.selfcheck
            every main-path Pallas kernel compiled (interpret=False)
            against its pure-JAX reference; also reports the device
  trainer   python -m pyspark_tf_gke_tpu.train.lm_pretrain
            a few steps on a generated corpus: loss finite and falling,
            checkpoint + serving bundle written, run notes say `tpu`
  server    python -m pyspark_tf_gke_tpu.train.serve --continuous-slots
            the bundle above with paged KV geometry, the radix prefix
            cache and chunked prefill: a few /v1/generate requests over
            HTTP, no engine rebuild, pages released, SIGTERM drains to 0
  *_4chip   trainer with --mesh-shape dp=2,fsdp=2 and server with
            --tp 4, only when JAX reports >= 4 devices

One process per chip: this parent never imports jax (or the package);
the legs run as child processes one after another, each releasing the
chip on exit. Children inherit JAX_COMPILATION_CACHE_DIR, or else share
<repo>/.jax_cache (pyspark_tf_gke_tpu/utils/compile_cache.py).

The last two stdout lines are JSON objects: first the report (device,
jax/jaxlib/libtpu versions, pass/fail and seconds per leg, compile-cache
location; also written to <out>/report.json), then the verdict, exactly
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
Exit code 0 and "ok": true only when every leg passed on a TPU. With no
accelerator it exits 3 and prints no result. ``--tiny`` is the CPU rehearsal (toy width,
JAX_PLATFORMS=cpu, kernels in interpret mode, platform expected `cpu`);
``tools/smoke_check.py`` stays the CPU functional gate.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "pyspark_tf_gke_tpu"

# model / engine geometry per mode. FULL is CausalLMConfig()'s defaults
# (= lm_pretrain's) at --seq-len 1024; ops/pallas/selfcheck.py runs the
# kernels at exactly these shapes.
FULL = dict(
    model=["--seq-len", "1024", "--batch-size", "8"],
    steps=6, seq=1024, page=64, slots=8, prefill_chunk=128, new_tokens=16,
    corpus_bytes=1 << 20)
TINY = dict(
    model=["--seq-len", "128", "--batch-size", "4", "--hidden-size", "64",
           "--num-layers", "2", "--num-heads", "4",
           "--intermediate-size", "128"],
    steps=4, seq=128, page=16, slots=4, prefill_chunk=32, new_tokens=8,
    corpus_bytes=1 << 16)

LEG_TIMEOUT_S = {"kernels": 420, "trainer": 600, "server": 600}
ADMIN_TOKEN = "chip-smoke"        # the server is this run's own, on loopback
PROFILE_STEPS = 6


class LegFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as fh:
            return "".join(fh.readlines()[-n:])
    except OSError as exc:
        return f"<no log: {exc}>"


def run_child(name: str, argv: list, env: dict, log_path: str,
              timeout_s: float, capture_stdout: bool = False):
    """Run one JAX-owning child to completion. Returns ``(exit code,
    stdout)``; stderr — and stdout too unless captured — goes to
    ``log_path``. The child gets its own process group so a timeout
    takes its helpers down with it."""
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE if capture_stdout else log, stderr=log,
            start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            kill_group(proc)
            raise LegFailed(f"{name}: no exit after {timeout_s:.0f}s")
    return proc.returncode, out


def kill_group(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    proc.wait(timeout=30)


def last_json_line(text: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise LegFailed("child printed no JSON line")


# ---- leg: kernels ----------------------------------------------------------


def leg_kernels(ctx: dict) -> dict:
    argv = ["-m", f"{PACKAGE}.ops.pallas.selfcheck"]
    if ctx["tiny"]:
        argv.append("--tiny")
    log = os.path.join(ctx["logs"], "kernels.log")
    rc, stdout = run_child("kernels", argv, ctx["env"], log,
                           LEG_TIMEOUT_S["kernels"], capture_stdout=True)
    if rc == 3:
        # the child found no accelerator (or no CPU under --tiny):
        # nothing was measured, nothing is reported
        sys.stderr.write(tail(log, 5))
        say("no accelerator: JAX did not find the expected platform")
        sys.exit(3)
    try:
        report = last_json_line(stdout)
    except LegFailed:
        sys.stderr.write(tail(log))
        raise LegFailed(f"kernels: exit {rc}, no report")
    ctx["device"] = report["device"]
    ctx["versions"] = report["versions"]
    ctx["cache_dir"] = report["compile_cache"]
    bad = {k: v for k, v in report["kernels"].items() if not v["ok"]}
    for name, res in report["kernels"].items():
        say(f"  kernel {name}: "
            + (f"err {res['err']}" if "err" in res else res["error"][:200])
            + ("" if res["ok"] else "  FAILED"))
    if bad or rc:
        sys.stderr.write(tail(log, 60))
        raise LegFailed(f"kernels: {len(bad)} of {len(report['kernels'])} "
                        f"failed: {sorted(bad)}")
    return {"kernels_checked": len(report["kernels"]),
            "max_err": max(v["err"] for v in report["kernels"].values()),
            "tolerance": report["tolerance"]}


# ---- leg: trainer ----------------------------------------------------------


def write_corpus(directory: str, n_bytes: int, seed: int = 0) -> str:
    """Learnable text from a seed: documents of sentences drawn from a
    64-word vocabulary with a skewed (Zipf-like) frequency, two files."""
    rng = random.Random(seed)
    letters = "abcdefghijklmnopqrstuvwxyz"
    words = ["".join(rng.choice(letters) for _ in range(rng.randint(2, 9)))
             for _ in range(64)]
    weights = [1.0 / (i + 1) for i in range(len(words))]
    os.makedirs(directory, exist_ok=True)
    for shard in range(2):
        with open(os.path.join(directory, f"part-{shard}.txt"), "w") as fh:
            written = 0
            while written < n_bytes // 2:
                doc = []
                for _ in range(rng.randint(8, 40)):
                    sent = " ".join(rng.choices(words, weights,
                                                k=rng.randint(4, 14)))
                    doc.append(sent.capitalize() + ".")
                text = "\n".join(doc) + "\n\n"
                fh.write(text)
                written += len(text)
    return os.path.join(directory, "part-*.txt")


def leg_trainer(ctx: dict, name: str, extra: list) -> dict:
    cfg = ctx["cfg"]
    out = os.path.join(ctx["out"], name)
    bundle = os.path.join(out, "bundle")
    argv = ["-m", f"{PACKAGE}.train.lm_pretrain",
            "--data-pattern", ctx["corpus"], "--tokenizer", "byte",
            *cfg["model"], "--epochs", "2",
            "--steps-per-epoch", str(cfg["steps"]),
            "--learning-rate", "1e-3", "--seed", "0",
            "--output-dir", out, "--export-bundle", bundle, *extra]
    log = os.path.join(ctx["logs"], f"{name}.log")
    rc, _ = run_child(name, argv, ctx["env"], log, LEG_TIMEOUT_S["trainer"])
    if rc:
        sys.stderr.write(tail(log, 60))
        raise LegFailed(f"{name}: lm_pretrain exited {rc}")
    with open(os.path.join(out, "history.json")) as fh:
        loss = json.load(fh)["loss"]
    if len(loss) != 2 or not all(math.isfinite(x) for x in loss):
        raise LegFailed(f"{name}: per-epoch loss not finite: {loss}")
    if not loss[1] < loss[0]:
        raise LegFailed(f"{name}: loss did not fall: {loss}")
    steps = [d for d in os.listdir(os.path.join(out, "checkpoints"))
             if d.isdigit()]
    if not steps:
        raise LegFailed(f"{name}: no checkpoint step under {out}/checkpoints")
    for need in ("config.json", "params"):
        if not os.path.exists(os.path.join(bundle, need)):
            raise LegFailed(f"{name}: bundle is missing {need}")
    # the child's own account of its device (train/harness.py run notes)
    with open(os.path.join(out, "causal-lm.txt")) as fh:
        notes = [ln for ln in fh if ln.startswith("devices:")]
    want = f"{ctx['device']['count']}x {ctx['platform']}"
    if not notes or want not in notes[0]:
        raise LegFailed(f"{name}: run notes say {notes}, want {want!r}")
    ctx["bundles"][name] = bundle
    return {"loss": [round(x, 4) for x in loss],
            "checkpoint_step": max(int(s) for s in steps),
            "devices": notes[0].split(":", 1)[1].strip()}


# ---- leg: server -----------------------------------------------------------


def http_json(url: str, payload=None, timeout: float = 300.0, headers=None):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json",
                                 **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def http_stream(url: str, payload: dict, timeout: float = 300.0) -> dict:
    """POST with "stream": true; returns the terminal SSE entry."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    events, done = [], False
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        if resp.status != 200:
            raise LegFailed(f"stream answered {resp.status}")
        for raw in resp:
            line = raw.decode().strip()
            if line == "data: [DONE]":
                done = True
            elif line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
    if not done or not events or not events[-1].get("done"):
        raise LegFailed(f"stream ended without a terminal entry: "
                        f"{events[-1:]}")
    streamed = sum(len(e.get("token_ids", [])) for e in events[:-1])
    if streamed != events[-1]["new_tokens"]:
        raise LegFailed(f"stream delivered {streamed} tokens, terminal "
                        f"entry says {events[-1]['new_tokens']}")
    return events[-1]


def metric_value(exposition: str, name: str) -> float:
    values = [float(ln.split()[-1]) for ln in exposition.splitlines()
              if ln.startswith(name) and not ln.startswith("#")
              and ln[len(name):len(name) + 1] in (" ", "{")]
    if not values:
        raise LegFailed(f"/metrics has no sample for {name}")
    return sum(values)


def add_paged_geometry(bundle: str, cfg: dict) -> int:
    """Paged KV reaches the server through the bundle's config.json:
    page size + pool size (slots x pages-per-sequence)."""
    path = os.path.join(bundle, "config.json")
    with open(path) as fh:
        meta = json.load(fh)
    pages = cfg["slots"] * (cfg["seq"] // cfg["page"])
    meta["config"]["kv_page_size"] = cfg["page"]
    meta["config"]["kv_num_pages"] = pages
    with open(path, "w") as fh:
        json.dump(meta, fh, indent=2)
    return pages


def leg_server(ctx: dict, name: str, bundle: str, extra: list) -> dict:
    cfg = ctx["cfg"]
    pages = add_paged_geometry(bundle, cfg)
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    log_path = os.path.join(ctx["logs"], f"{name}.log")
    argv = [sys.executable, "-m", f"{PACKAGE}.train.serve",
            "--bundle", bundle, "--host", "127.0.0.1", "--port", str(port),
            "--continuous-slots", str(cfg["slots"]),
            "--prefix-cache", str(pages),
            "--prefill-chunk", str(cfg["prefill_chunk"]),
            "--drain-timeout", "60", *extra]
    deadline = time.monotonic() + LEG_TIMEOUT_S["server"]
    # the token opens POST /admin/profile: one capture of the engine's
    # steps, so that the gate also drives the profiler on this device
    env = dict(ctx["env"], SERVE_ADMIN_TOKEN=ADMIN_TOKEN)
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=REPO, env=env, stdout=log,
                                stderr=log, start_new_session=True)
    try:
        return _drive_server(ctx, name, proc, url, deadline)
    except Exception:
        sys.stderr.write(tail(log_path, 60))
        raise
    finally:
        kill_group(proc)


def _drive_server(ctx, name, proc, url, deadline) -> dict:
    cfg = ctx["cfg"]
    while True:
        if proc.poll() is not None:
            raise LegFailed(f"server exited {proc.returncode} during boot")
        if time.monotonic() > deadline:
            raise LegFailed("server never answered /healthz")
        try:
            _, health = http_json(url + "/healthz", timeout=5)
            break
        except (urllib.error.URLError, OSError):
            time.sleep(0.5)
    got = (health["platform"], health["device_kind"], health["n_devices"])
    want = (ctx["platform"], ctx["device"]["kind"], ctx["device"]["count"])
    if got != want:
        raise LegFailed(f"/healthz reports {got}, want {want}")
    paged = health["continuous"]["paged"]
    if paged["pages_in_use"] != 0:
        raise LegFailed(f"fresh pool not empty: {paged}")

    # byte tokenizer: bytes == tokens. `long` spans > 2 prefill chunks;
    # the repeat shares all of it and adds a new suffix.
    with open(ctx["corpus"].replace("*", "0")) as fh:
        text = fh.read(4 * cfg["prefill_chunk"])
    long = text[:int(2.5 * cfg["prefill_chunk"])]
    budget = cfg["new_tokens"]
    requests = [
        ("short", {"prompts": [text[:12]]}),
        ("long", {"prompts": [long]}),
        ("repeat", {"prompts": [long + " and then"]}),
        ("stream", {"prompts": [text[:40]], "stream": True}),
    ]
    tokens = {}
    trace_dir = os.path.join(ctx["out"], "traces", name)
    for tag, body in requests:
        if tag == "long":
            # armed here: the capture spans the chunked prefill's and the
            # decode's steps (engine.<phase> annotations, the paged
            # kernel's own name) and closes itself after PROFILE_STEPS
            status, _ = http_json(
                url + "/admin/profile",
                {"output_dir": trace_dir, "steps": PROFILE_STEPS},
                headers={"X-Admin-Token": ADMIN_TOKEN})
            if status != 202:
                raise LegFailed(f"/admin/profile: HTTP {status}")
        body["max_new_tokens"] = budget
        left = max(deadline - time.monotonic(), 1.0)
        if body.get("stream"):
            entry = http_stream(url + "/v1/generate", body, timeout=left)
        else:
            status, out = http_json(url + "/v1/generate", body, timeout=left)
            if status != 200:
                raise LegFailed(f"{tag}: HTTP {status}")
            entry = out["completions"][0]
        if not entry["new_tokens"] > 0:
            raise LegFailed(f"{tag}: empty completion {entry}")
        tokens[tag] = entry["new_tokens"]
        say(f"  {name} {tag}: {entry['new_tokens']} tokens, "
            f"{entry['latency_ms']} ms")

    # idle: every slot's pages released; what stays is the radix cache
    while True:
        _, health = http_json(url + "/healthz", timeout=10)
        eng = health["continuous"]
        if not (eng["active"] or eng["queued"] or eng["inflight"]):
            break
        if time.monotonic() > deadline:
            raise LegFailed(f"engine never went idle: {eng}")
        time.sleep(0.2)
    held = eng["paged"]["pages_in_use"] - eng["prefix_cache"]["resident_pages"]
    if held:
        raise LegFailed(f"{held} pages still held at idle: {eng['paged']}")
    if eng["prefix_cache"]["hits"] < 1:
        raise LegFailed(f"repeated prefix missed the radix cache: "
                        f"{eng['prefix_cache']}")
    if eng["prefill_chunks"] < 3:
        raise LegFailed(f"long prompt was not prefilled in chunks: "
                        f"{eng['prefill_chunks']} pieces")
    with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
        rebuilds = metric_value(resp.read().decode(),
                                "serve_engine_rebuilds_total")
    if rebuilds:
        raise LegFailed(f"serve_engine_rebuilds_total = {rebuilds}: an "
                        f"engine step failed and the server hid it")
    written = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
               for f in fs if f.endswith(".xplane.pb")]
    if not written:
        raise LegFailed(f"the profiler capture left no trace in {trace_dir}")

    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=90)
    except subprocess.TimeoutExpired:
        raise LegFailed("server still alive 90s after SIGTERM")
    if rc != 0:
        raise LegFailed(f"server exited {rc} after SIGTERM, want 0")
    return {"new_tokens": tokens, "prefix_hits": eng["prefix_cache"]["hits"],
            "prefill_chunks": eng["prefill_chunks"],
            "pages_total": eng["paged"]["pages_total"],
            "peak_pages_in_use": eng["paged"]["peak_pages_in_use"],
            "profile_trace": os.path.relpath(written[0], ctx["out"])}


# ---- driver ----------------------------------------------------------------


def cache_entries(path) -> int:
    try:
        return len(os.listdir(path))
    except (OSError, TypeError):
        return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal at toy width (expects platform cpu)")
    p.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"),
                   help="output directory (emptied first)")
    args = p.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, PACKAGE)):
        print(f"chip_smoke: {REPO} holds no {PACKAGE}/ — run it from a "
              f"checkout of the repo", file=sys.stderr)
        return 2
    # built from what git would commit: a native library or a compile
    # cache made elsewhere must not travel into this run
    shutil.rmtree(os.path.join(REPO, PACKAGE, "native", "_build"),
                  ignore_errors=True)
    out = os.path.abspath(args.out)
    shutil.rmtree(out, ignore_errors=True)
    logs = os.path.join(out, "logs")
    os.makedirs(logs)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.tiny:
        env["JAX_PLATFORMS"] = "cpu"
    cfg = TINY if args.tiny else FULL
    ctx = {"tiny": args.tiny, "cfg": cfg, "env": env, "out": out,
           "logs": logs, "bundles": {},
           "platform": "cpu" if args.tiny else "tpu"}
    legs: dict = {}
    t_start = time.monotonic()

    def run_leg(name, fn, *a) -> bool:
        say(f"leg {name} ...")
        t0 = time.monotonic()
        try:
            legs[name] = {"ok": True, **fn(ctx, *a)}
        except (LegFailed, OSError, KeyError, ValueError) as exc:
            # a refused connection, a missing artifact or a malformed
            # reply fails the leg like an explicit check does
            legs[name] = {"ok": False,
                          "error": f"{type(exc).__name__}: {exc}"[:300]}
            print(f"[chip_smoke] {name}: {exc!r}", file=sys.stderr,
                  flush=True)
        legs[name]["seconds"] = round(time.monotonic() - t0, 1)
        say(f"leg {name}: {'ok' if legs[name]['ok'] else 'FAILED'} "
            f"in {legs[name]['seconds']}s")
        return legs[name]["ok"]

    run_leg("kernels", leg_kernels)
    if "device" not in ctx:
        return 1  # the kernel child died before reporting a device
    cache_before = cache_entries(ctx["cache_dir"])
    ctx["corpus"] = write_corpus(os.path.join(out, "corpus"),
                                 cfg["corpus_bytes"])
    if run_leg("trainer", leg_trainer, "trainer", []):
        run_leg("server", leg_server, "server", ctx["bundles"]["trainer"], [])
    else:
        legs["server"] = {"ok": False, "error": "no bundle: trainer failed"}
    n_dev = ctx["device"]["count"]
    if n_dev >= 4:
        if run_leg("trainer_4chip", leg_trainer, "trainer_4chip",
                   ["--mesh-shape", "dp=2,fsdp=2"]):
            run_leg("server_4chip", leg_server, "server_4chip",
                    ctx["bundles"]["trainer_4chip"], ["--tp", "4"])
        else:
            legs["server_4chip"] = {
                "ok": False, "error": "no bundle: trainer_4chip failed"}
    else:
        say(f"multi-chip legs skipped: {n_dev} device(s)")
        legs["multi_chip"] = {"skipped": f"{n_dev} device(s)"}

    ok = all(leg.get("ok", True) for leg in legs.values())
    dev = ctx["device"]
    device = {"platform": str(dev["platform"]), "kind": str(dev["kind"]),
              "count": int(dev["count"])}
    report = json.dumps({
        "ok": ok, "tiny": args.tiny, "device": device,
        "versions": ctx["versions"], "legs": legs,
        "compile_cache": {"dir": ctx["cache_dir"],
                          "entries_before": cache_before,
                          "entries_after": cache_entries(ctx["cache_dir"])},
        "seconds": round(time.monotonic() - t_start, 1),
    }, separators=(",", ":"))
    with open(os.path.join(out, "report.json"), "w") as fh:
        fh.write(report + "\n")
    print(report)
    # the verdict: these keys and no others, last on stdout
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
