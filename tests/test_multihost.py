"""Multi-process fake slice: 2 real processes x 4 virtual CPU devices,
bootstrapped with jax.distributed through the SAME CLI path a 2-host TPU
pod uses. This is the SURVEY §4 'kind+MetalLB' analog taken one step
further than the in-process 8-device mesh: it exercises
initialize_distributed, per-host input sharding (host_shard), and
make_array_from_process_local_data global-batch assembly across real
process boundaries."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RUNNER = r"""
import sys
import jax
# pin the CPU fake slice the same way conftest does
jax.config.update("jax_platforms", "cpu")
from pyspark_tf_gke_tpu.train import cli

history = cli.main(sys.argv[1:])
assert all(l == l for l in history["loss"]), "NaN loss"  # NaN != NaN
print("WORKER_OK", jax.process_index(), history["loss"][-1])
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- backend capability probe -------------------------------------------------
# Some jax builds/backends cannot run multi-PROCESS computations at all
# (this env's CPU backend raises "Multiprocess computations aren't
# implemented on the CPU backend" from every cross-process collective).
# That is an environment capability gap, not a regression in the code
# under test — probe ONCE per session and skip the 2-proc tests with an
# explicit reason instead of failing them, so the tier-1/slow log stops
# carrying known-env noise. Any OTHER probe failure does NOT skip: the
# tests run and fail attributably.

_MULTIPROC_UNIMPL_MARKERS = ("aren't implemented", "not implemented",
                             "unimplemented")

_PROBE_RUNNER = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
jax.distributed.initialize(coordinator_address=sys.argv[1],
                           num_processes=2, process_id=int(sys.argv[2]))
x = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(
    jnp.ones((jax.local_device_count(),)))
assert float(x[0]) == jax.device_count(), x
print("PROBE_OK", jax.process_index())
"""

_multiproc_probe_memo: list = []  # [reason_or_None], filled once


def _multiprocess_unimplemented_reason():
    """None when 2-process jax.distributed works here; otherwise the
    backend's own 'unimplemented' line (the skip reason)."""
    if _multiproc_probe_memo:
        return _multiproc_probe_memo[0]
    procs = _spawn_pair(
        lambda pid, port: ["-c", _PROBE_RUNNER,
                           f"127.0.0.1:{port}", str(pid)])
    outs = _communicate_pair(procs, timeout_s=180)
    reason = None
    if not all(p.returncode == 0 and "PROBE_OK" in t
               for p, t in zip(procs, outs)):
        marker = next(
            (ln.strip()[-300:] for text in outs
             for ln in text.splitlines()
             if any(m in ln.lower() for m in _MULTIPROC_UNIMPL_MARKERS)),
            None)
        # only the capability gap converts to a skip; other failures
        # leave reason None and the real tests surface them
        reason = marker
    _multiproc_probe_memo.append(reason)
    return reason


@pytest.fixture()
def multiproc_backend():
    """Skip (with the backend's own words) when this environment cannot
    run 2-process jax computations at all."""
    reason = _multiprocess_unimplemented_reason()
    if reason:
        pytest.skip("backend reports multiprocess unimplemented: "
                    + reason)


def _spawn_pair(argv_for_pid, extra_env=None):
    """Launch the 2-process fake-slice pair (4 virtual CPU devices per
    process): ``argv_for_pid(pid, port) -> argv after sys.executable``.
    One launch/env recipe for every multihost test in this file."""
    env_base = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_PLATFORMS": "cpu",
        **(extra_env or {}),
    }
    port = _free_port()
    return [
        subprocess.Popen(
            [sys.executable, *argv_for_pid(pid, port)],
            env=env_base, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(2)
    ]


def _communicate_pair(procs, timeout_s=420):
    """Collect both workers' output; ALWAYS reaps stragglers (a worker
    stalled in a collective would otherwise block forever)."""
    try:
        return [p.communicate(timeout=timeout_s)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()


def _launch_workers(csv: str, out: str, epochs: int, extra_args=()):
    """Start the 2-process fake-slice training job (dp=8 mesh) through
    the real CLI bootstrap path."""
    return _spawn_pair(lambda pid, port: [
        "-c", RUNNER,
        "--data-path", csv, "--epochs", str(epochs),
        "--batch-size", "32",
        "--output-dir", out, "--mesh-shape", "dp=8",
        "--num-processes", "2", "--process-id", str(pid),
        "--coordinator-addr", f"127.0.0.1:{port}",
        *extra_args,
    ])


def _wait_for_checkpoint(procs, ckdir, extra_ready=None, timeout_s=300):
    """Poll until a numbered checkpoint exists (and ``extra_ready()``,
    if given, holds) with every worker alive. A worker dying first is
    reported from ITS log (survivors are killed first — a live worker
    stalled in a collective would block communicate indefinitely)."""
    import time

    deadline = time.time() + timeout_s
    while time.time() < deadline:
        # crash check FIRST: an early nonzero exit must fail the wait
        # even when a checkpoint already landed. A clean rc=0 exit is
        # not a crash — the run simply finished fast; let the
        # checkpoint condition decide.
        dead = [i for i, p in enumerate(procs)
                if p.poll() is not None and p.returncode != 0]
        steps = [d for d in (os.listdir(ckdir) if os.path.isdir(ckdir) else [])
                 if d.isdigit()]
        if not dead and steps and (extra_ready is None or extra_ready()):
            return
        if dead:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            texts = [p.communicate(timeout=60)[0] for p in procs]
            raise AssertionError(
                f"worker {dead[0]} died early:\n{texts[dead[0]][-2000:]}")
        time.sleep(0.5)
    raise AssertionError("no checkpoint appeared before the deadline")


@pytest.mark.slow
def test_two_process_csv_training(multiproc_backend, tmp_path):
    from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_csv

    csv = str(tmp_path / "d.csv")
    make_synthetic_csv(csv, rows=320)
    out = str(tmp_path / "out")

    procs = _launch_workers(csv, out, epochs=2)
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"worker {i} failed:\n{text[-3000:]}"
        assert f"WORKER_OK {i}" in text

    # Process 0 wrote the artifacts; losses finite and identical across
    # hosts (synchronous SPMD: every process computes the same metrics).
    final = [t.split(f"WORKER_OK {i} ")[1].splitlines()[0]
             for i, t in enumerate(outputs)]
    assert np.isfinite(float(final[0]))
    assert final[0] == final[1]
    assert os.path.exists(os.path.join(out, "history.json"))


@pytest.mark.slow
def test_two_process_kill_and_resume(multiproc_backend, tmp_path):
    """Fault-tolerance across real process boundaries: both workers are
    SIGKILLed mid-training (the synchronous SPMD failure unit is the
    whole job — one dead worker stalls collectives, so k8s restarts the
    set), then relaunched with --resume. The relaunch must restore the
    mid-run checkpoint and finish with finite, host-identical losses."""
    import signal
    import time

    from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_csv

    csv = str(tmp_path / "d.csv")
    make_synthetic_csv(csv, rows=320)
    out = str(tmp_path / "out")
    ckdir = os.path.join(out, "checkpoints")

    def launch(resume: bool):
        extra = ["--checkpoint-every-steps", "3"] + (["--resume"] if resume else [])
        return _launch_workers(csv, out, epochs=4, extra_args=extra)

    # Run 1: wait for the first mid-run checkpoint, then kill both
    # workers hard (no cleanup — the crash path, not shutdown).
    procs = launch(resume=False)
    try:
        _wait_for_checkpoint(procs, ckdir)
        for p in procs:
            p.send_signal(signal.SIGKILL)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.communicate()

    killed_at = max(int(d) for d in os.listdir(ckdir) if d.isdigit())

    # Run 2: relaunch with --resume; must restore and complete.
    procs = launch(resume=True)
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"resumed worker {i} failed:\n{text[-3000:]}"
        assert f"WORKER_OK {i}" in text
    assert any(f"Restored checkpoint step {killed_at}" in t for t in outputs), (
        f"no restore log; expected step {killed_at}"
    )
    final = [t.split(f"WORKER_OK {i} ")[1].splitlines()[0]
             for i, t in enumerate(outputs)]
    assert np.isfinite(float(final[0])) and final[0] == final[1]


# ONE serving fixture (model config / seed / mesh shape / placement),
# shared verbatim by both runner scripts and — via _tp_serve_fixture —
# by both in-test reference paths: the token-identity asserts compare
# the SAME model by construction.
TP_SERVE_SETUP = r"""
import jax.numpy as jnp
from flax import linen as nn
from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
from pyspark_tf_gke_tpu.train.serving import (
    announce_shutdown, mh_generate, serve_generate, serve_worker_loop,
    shard_params_for_serving)
from pyspark_tf_gke_tpu.utils.seeding import make_rng

cfg = CausalLMConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, num_kv_heads=2, intermediate_size=64,
                     max_seq_len=32, dtype=jnp.float32)
mesh = make_mesh({"dp": 4, "tp": 2}, jax.devices()[:8])
model = CausalLM(cfg, mesh=mesh)
params = jax.device_get(nn.meta.unbox(
    jax.jit(model.init)(make_rng(7), jnp.zeros((1, 8), jnp.int32))["params"]))
placed = shard_params_for_serving(model, params, mesh)
"""

_RUNNER_PREAMBLE = r"""
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from pyspark_tf_gke_tpu.parallel.distributed import initialize_distributed

num, pid, addr = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
initialize_distributed(num_processes=num, process_id=pid,
                       coordinator_addr=addr)
"""

SERVE_RUNNER = _RUNNER_PREAMBLE + TP_SERVE_SETUP + r"""
assert len(jax.devices()) == 2 * jax.local_device_count()
prompt = jnp.asarray(np.tile(np.arange(4, 12, dtype=np.int32)[None], (2, 1)))
out = serve_generate(model, placed, prompt, mesh=mesh, max_new_tokens=6)
assert getattr(out, "is_fully_addressable", True), (
    "serve output must be host-readable")
print("SERVE_TOKENS", pid, np.asarray(out)[:, 8:].tolist())
"""


def _tp_serve_fixture():
    """In-process twin of TP_SERVE_SETUP: exec the SAME source so the
    single-process reference can never drift from the runners."""
    ns = {"__builtins__": __builtins__}
    exec("import jax\n" + TP_SERVE_SETUP, ns)
    return ns["model"], ns["placed"], ns["mesh"]


@pytest.mark.slow
def test_two_process_tp_serving_matches_single_process(multiproc_backend, tmp_path):
    """VERDICT round-3 #5: serving exercised across real process
    boundaries. A 2-process x 4-device dp=4 x tp=2 ``serve_generate``
    (tensor-parallel param placement + collectives over the wire) must
    produce the SAME tokens as the identical model served on the
    in-process 8-device mesh — param-placement and collective bugs on
    the serving path hide exactly here."""
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.train.serving import serve_generate

    # Single-process reference on the same mesh shape / seed / prompt.
    model, placed, mesh = _tp_serve_fixture()
    prompt = jnp.asarray(
        np.tile(np.arange(4, 12, dtype=np.int32)[None], (2, 1)))
    ref = np.asarray(serve_generate(model, placed, prompt, mesh=mesh,
                                    max_new_tokens=6))[:, 8:].tolist()

    procs = _spawn_pair(lambda pid, port: [
        "-c", SERVE_RUNNER, "2", str(pid), f"127.0.0.1:{port}"])
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"serve worker {i} failed:\n{text[-3000:]}"
        assert f"SERVE_TOKENS {i}" in text
    toks = [t.split(f"SERVE_TOKENS {i} ")[1].splitlines()[0]
            for i, t in enumerate(outputs)]
    # identical across hosts, and identical to the single-process mesh
    assert toks[0] == toks[1]
    assert toks[0] == str(ref)


MH_SERVE_RUNNER = _RUNNER_PREAMBLE + TP_SERVE_SETUP + r"""
from pyspark_tf_gke_tpu.train.serving import mh_score

if pid == 0:
    # four requests with DIFFERENT shapes and ops: the worker loop must
    # learn each payload shape from the header broadcast, and replay
    # score and beams as well as greedy generate
    p1 = np.tile(np.arange(4, 12, dtype=np.int32)[None], (2, 1))
    p2 = np.arange(10, 16, dtype=np.int32)[None]
    o1 = np.asarray(mh_generate(model, placed, p1, mesh, max_new_tokens=5))
    o2 = np.asarray(mh_generate(model, placed, p2, mesh, max_new_tokens=3))
    nll = np.asarray(mh_score(model, placed, p1,
                              np.array([8, 5], np.int32), mesh))
    ob, sc = mh_generate(model, placed, p2, mesh, max_new_tokens=3,
                         num_beams=2)
    o5 = np.asarray(mh_generate(model, placed, p2, mesh, max_new_tokens=4,
                                temperature=0.8, top_p=0.9,
                                rng=jax.random.PRNGKey(42)))
    announce_shutdown()
    print("MH_TOKENS", o1[:, 8:].tolist(), o2[:, 6:].tolist(),
          [round(float(v), 4) for v in nll],
          np.asarray(ob)[:, 6:].tolist(),
          [round(float(v), 4) for v in np.asarray(sc)],
          o5[:, 6:].tolist())
else:
    served = serve_worker_loop(model, placed, mesh)
    assert served == 5, f"worker replayed {served} != 5 requests"
    print("MH_WORKER_OK", served)
"""


@pytest.mark.slow
def test_two_process_serving_driver_worker_loop(multiproc_backend, tmp_path):
    """The multi-host serving CONTROL plane (train/serving.py): process
    0 announces each request (header + payload broadcast), process 1
    replays it in serve_worker_loop, and the collective-backed decode
    stays in lockstep across request shapes — tokens must equal the
    single-process reference."""
    import jax
    import jax.numpy as jnp
    from pyspark_tf_gke_tpu.train.serving import serve_generate, serve_score

    model, placed, mesh = _tp_serve_fixture()
    p1 = jnp.asarray(np.tile(np.arange(4, 12, dtype=np.int32)[None], (2, 1)))
    p2 = jnp.asarray(np.arange(10, 16, dtype=np.int32)[None])
    r1 = np.asarray(serve_generate(model, placed, p1, mesh=mesh,
                                   max_new_tokens=5))[:, 8:].tolist()
    r2 = np.asarray(serve_generate(model, placed, p2, mesh=mesh,
                                   max_new_tokens=3))[:, 6:].tolist()
    rn = [round(float(v), 4) for v in np.asarray(serve_score(
        model, placed, np.asarray(p1), np.array([8, 5], np.int32),
        mesh=mesh))]
    from pyspark_tf_gke_tpu.train.serving import mh_generate, serve_beam

    rb, rs = serve_beam(model, placed, np.asarray(p2), mesh=mesh,
                        max_new_tokens=3, num_beams=2)
    rb = np.asarray(rb)[:, 6:].tolist()
    rs = [round(float(v), 4) for v in np.asarray(rs)]
    # sampling reference goes through the SAME mh_generate construction
    # (single-process: no broadcasts, same typed-key normalization)
    r5 = np.asarray(mh_generate(
        model, placed, np.asarray(p2), mesh, max_new_tokens=4,
        temperature=0.8, top_p=0.9,
        rng=jax.random.PRNGKey(42)))[:, 6:].tolist()

    procs = _spawn_pair(lambda pid, port: [
        "-c", MH_SERVE_RUNNER, "2", str(pid), f"127.0.0.1:{port}"])
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"mh worker {i} failed:\n{text[-3000:]}"
    assert "MH_WORKER_OK 5" in outputs[1]
    toks = outputs[0].split("MH_TOKENS ")[1].splitlines()[0]
    assert toks == f"{r1} {r2} {rn} {rb} {rs} {r5}"


SERVE_MAIN_RUNNER = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from pyspark_tf_gke_tpu.train import serve

sys.exit(serve.main(sys.argv[1:]))
"""


@pytest.mark.slow
def test_two_process_serve_cli_http_end_to_end(multiproc_backend, tmp_path):
    """The DEPLOYMENT surface on a multi-host mesh: two processes run
    the real `train.serve` CLI (process 0 = HTTP server, process 1 =
    worker loop), the parent speaks HTTP to process 0, and greedy
    completions match a single-process BundleServer on the same mesh
    shape; sampling requests are rejected with 400."""
    import json as _json
    import time
    import urllib.error
    import urllib.request

    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.export import export_serving_bundle
    from pyspark_tf_gke_tpu.train.serve import BundleServer
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    # vocab 259 covers the byte tokenizer the bundle records by default
    cfg = CausalLMConfig(vocab_size=259, hidden_size=32, num_layers=2,
                         num_heads=4, num_kv_heads=2, intermediate_size=64,
                         max_seq_len=64, dtype=jnp.float32)
    model = CausalLM(cfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        make_rng(11), jnp.zeros((1, 8), jnp.int32))["params"])
    bundle = str(tmp_path / "bundle")
    export_serving_bundle(cfg, params, bundle, quantize=False)
    # a smaller draft (same vocab): single-prompt greedy requests route
    # through speculative decoding — over the wire, on multi-host
    dcfg = CausalLMConfig(vocab_size=259, hidden_size=16, num_layers=1,
                          num_heads=2, num_kv_heads=1, intermediate_size=32,
                          max_seq_len=64, dtype=jnp.float32)
    dmodel = CausalLM(dcfg)
    dparams = nn.meta.unbox(jax.jit(dmodel.init)(
        make_rng(12), jnp.zeros((1, 8), jnp.int32))["params"])
    draft = str(tmp_path / "draft")
    export_serving_bundle(dcfg, dparams, draft, quantize=False)

    # single-process reference on the same dp x tp mesh shape (no draft
    # needed: speculative decoding is greedy-exact by construction)
    ref_server = BundleServer(
        bundle, mesh=make_mesh({"dp": 4, "tp": 2}, jax.devices()[:8]))
    ref = ref_server.generate(["ab"], max_new_tokens=6)[0]["completion"]

    http_port = _free_port()
    procs = _spawn_pair(lambda pid, port: [
        "-c", SERVE_MAIN_RUNNER,
        "--bundle", bundle, "--draft-bundle", draft,
        "--host", "127.0.0.1",
        "--port", str(http_port), "--tp", "2",
        "--num-processes", "2", "--process-id", str(pid),
        "--coordinator-addr", f"127.0.0.1:{port}",
    ])
    try:
        base = f"http://127.0.0.1:{http_port}"
        deadline = time.time() + 240
        health = None
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                break  # a worker died — fall through to the asserts
            try:
                with urllib.request.urlopen(base + "/healthz",
                                            timeout=5) as r:
                    health = _json.loads(r.read())
                break
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(1.0)
        assert health is not None, "server never became healthy"
        assert health["processes"] == 2 and health["tp"] == 2

        def post(payload, path="/v1/generate"):
            req = urllib.request.Request(
                base + path, data=_json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                return _json.loads(r.read())

        # single-prompt greedy routes SPECULATIVE (draft bundle loaded)
        # over the wire; greedy-exact, so it matches the plain reference
        out = post({"prompts": ["ab"], "max_new_tokens": 6})
        assert out["completions"][0]["completion"] == ref
        assert "speculative" in out["completions"][0]
        assert out["completions"][0]["speculative"]["gamma"] == 4

        # scoring rides the wire protocol too (OP_SCORE replay)
        sc = post({"texts": ["hello world"]}, path="/v1/score")
        ref_sc = ref_server.score(["hello world"])
        assert sc["scores"][0]["tokens"] == ref_sc[0]["tokens"]
        assert abs(sc["scores"][0]["nll"] - ref_sc[0]["nll"]) < 1e-3

        # deterministic beams ride it as well (header num_beams)
        bm = post({"prompts": ["ab"], "max_new_tokens": 4, "num_beams": 2})
        ref_bm = ref_server.generate(["ab"], max_new_tokens=4, num_beams=2)
        assert (bm["completions"][0]["completion"]
                == ref_bm[0]["completion"])
        assert abs(bm["completions"][0]["beam_score"]
                   - ref_bm[0]["beam_score"]) < 1e-4

        # sampling rides the wire too (the per-request rng key is
        # broadcast); no parity reference — the server draws a fresh
        # key — but the request must succeed and produce tokens
        sm = post({"prompts": ["ab"], "max_new_tokens": 4,
                   "temperature": 1.0})
        # 0 is legitimate (an untrained model can sample eos first)
        assert 0 <= sm["completions"][0]["new_tokens"] <= 4
        assert "completion" in sm["completions"][0]

        # graceful shutdown: SIGINT on process 0 -> KeyboardInterrupt ->
        # announce_shutdown releases the worker loop -> both exit 0.
        # (A SIGKILL teardown instead makes the worker die rc=1 in the
        # jax.distributed fatal-error handler — the coordinator's death
        # cascade, not a crash, but indistinguishable from one.)
        import signal

        procs[0].send_signal(signal.SIGINT)
        outputs = _communicate_pair(procs, timeout_s=120)
        for i, (p, text) in enumerate(zip(procs, outputs)):
            assert p.returncode == 0, (
                f"serve process {i} did not shut down cleanly:"
                f"\n{text[-3000:]}")
        assert "worker loop done after 4 requests" in outputs[1]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()


@pytest.mark.slow
def test_two_process_sigstop_stall_detection_and_restart(multiproc_backend, tmp_path):
    """The REAL TPU-pod failure shape: a worker that is alive but hung
    (SIGSTOP — the process exists, collectives never complete). End to
    end: per-process heartbeats -> watchdog detects the stalled worker
    by heartbeat age (train/resilience.detect_stall, the k8s liveness
    probe's logic) -> job-level restart (sync SPMD: one hung worker
    stalls every peer, so the whole set restarts) -> resume from the
    mid-run checkpoint -> completion."""
    import signal
    import time

    from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_csv
    from pyspark_tf_gke_tpu.train.resilience import detect_stall

    csv = str(tmp_path / "d.csv")
    make_synthetic_csv(csv, rows=320)
    out = str(tmp_path / "out")
    ckdir = os.path.join(out, "checkpoints")
    hb = [str(tmp_path / f"hb-{i}.json") for i in range(2)]

    def launch(resume: bool, epochs: int):
        extra = [
            "--checkpoint-every-steps", "3",
            "--heartbeat-every-steps", "1",
            "--heartbeat-file", str(tmp_path / "hb-{process_index}.json"),
        ] + (["--resume"] if resume else [])
        return _launch_workers(csv, out, epochs=epochs, extra_args=extra)

    # Run 1: plenty of epochs — it is not meant to finish; the stopped
    # worker wedges the job and the watchdog ends it.
    procs = launch(resume=False, epochs=200)
    try:
        # wait until a checkpoint exists and both workers are beating
        _wait_for_checkpoint(
            procs, ckdir,
            extra_ready=lambda: all(os.path.exists(p) for p in hb))

        # Hang worker 1 (alive, not dead — SIGKILL is the easy case;
        # this is the hard one the heartbeat exists for).
        procs[1].send_signal(signal.SIGSTOP)

        stalled = detect_stall(hb, stall_seconds=6.0, timeout_s=120.0)
        assert stalled is not None, "watchdog never saw the stall"
        # worker 1 must be among the stalled (worker 0 may stall too —
        # it is blocked in a collective with a hung peer; that is the
        # sync-SPMD point). Ensure specifically that hb-1 goes stale.
        deadline = time.time() + 60
        from pyspark_tf_gke_tpu.train.resilience import Heartbeat

        while time.time() < deadline and not Heartbeat.is_stalled(hb[1], 6.0):
            time.sleep(0.5)
        assert Heartbeat.is_stalled(hb[1], 6.0)
        assert procs[1].poll() is None, "worker must be hung, not dead"

        # Job-level restart: kill the whole set (SIGKILL terminates a
        # stopped process too).
        for p in procs:
            p.send_signal(signal.SIGKILL)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.communicate()

    killed_at = max(int(d) for d in os.listdir(ckdir) if d.isdigit())

    # Run 2: short, resumable, must restore the mid-run checkpoint.
    procs = launch(resume=True, epochs=4)
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"restarted worker {i} failed:\n{text[-3000:]}"
        assert f"WORKER_OK {i}" in text
    assert any(f"Restored checkpoint step {killed_at}" in t for t in outputs)


CB_RUNNER = _RUNNER_PREAMBLE + TP_SERVE_SETUP + r"""
import os
from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
from pyspark_tf_gke_tpu.train.serving import serve_worker_loop as swl

if pid == 0:
    eng = ContinuousEngine(model, placed, num_slots=2, chunk=3,
                           buckets=(8, 16), mesh=mesh, announce=True,
                           pipeline_depth=int(os.environ.get(
                               "CB_PIPELINE", "0")))
    rids = [eng.submit(np.arange(4, 12, dtype=np.int32), 5),
            eng.submit(np.arange(10, 16, dtype=np.int32), 7),
            eng.submit(np.arange(2, 7, dtype=np.int32), 4),
            eng.submit(np.arange(3, 9, dtype=np.int32), 5,
                       temperature=0.8, top_p=0.9, seed=41)]
    results = dict(eng.run_until_drained())
    announce_shutdown()
    print("CB_TOKENS", [results[r] for r in rids])
else:
    served = swl(model, placed, mesh)
    print("CB_WORKER_OK", served)
"""


@pytest.mark.slow
def test_two_process_continuous_batching_matches_single_process(multiproc_backend):
    """Continuous batching over the announce/replay wire: process 0's
    slot engine announces every device op (admit/chunk/free); process 1
    replays them into a SlotDeviceState replica. Three staggered
    requests (slot reuse mid-flight, 2 slots) must produce the same
    tokens as the identical engine on the in-process 8-device mesh."""
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine

    model, placed, mesh = _tp_serve_fixture()
    eng = ContinuousEngine(model, placed, num_slots=2, chunk=3,
                           buckets=(8, 16), mesh=mesh)
    rids = [eng.submit(np.arange(4, 12, dtype=np.int32), 5),
            eng.submit(np.arange(10, 16, dtype=np.int32), 7),
            eng.submit(np.arange(2, 7, dtype=np.int32), 4),
            # a SAMPLED request rides the wire too: the sampling lane
            # (temperature/top_p/seed) is broadcast at admit, so every
            # process draws the same tokens
            eng.submit(np.arange(3, 9, dtype=np.int32), 5,
                       temperature=0.8, top_p=0.9, seed=41)]
    results = dict(eng.run_until_drained())
    ref = [results[r] for r in rids]

    procs = _spawn_pair(lambda pid, port: [
        "-c", CB_RUNNER, "2", str(pid), f"127.0.0.1:{port}"])
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"cb proc {i} failed:\n{text[-3000:]}"
    assert "CB_WORKER_OK" in outputs[1]
    toks = outputs[0].split("CB_TOKENS ")[1].splitlines()[0]
    assert toks == str(ref)


@pytest.mark.slow
def test_two_process_continuous_batching_decode_ahead_matches(multiproc_backend):
    """Decode-ahead over the wire: process 0 announces deferred chunks
    (dispatch-only) and separate OP_CB_COLLECT gathers; the worker
    replays both, so the collective order stays aligned while the
    readback overlaps compute. Tokens must equal the UNPIPELINED
    single-process engine's (the oracle both paths share) — including
    the sampled request's lane."""
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine

    model, placed, mesh = _tp_serve_fixture()
    eng = ContinuousEngine(model, placed, num_slots=2, chunk=3,
                           buckets=(8, 16), mesh=mesh)
    rids = [eng.submit(np.arange(4, 12, dtype=np.int32), 5),
            eng.submit(np.arange(10, 16, dtype=np.int32), 7),
            eng.submit(np.arange(2, 7, dtype=np.int32), 4),
            eng.submit(np.arange(3, 9, dtype=np.int32), 5,
                       temperature=0.8, top_p=0.9, seed=41)]
    results = dict(eng.run_until_drained())
    ref = [results[r] for r in rids]

    procs = _spawn_pair(lambda pid, port: [
        "-c", CB_RUNNER, "2", str(pid), f"127.0.0.1:{port}"],
        extra_env={"CB_PIPELINE": "1"})
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"cb-pipe proc {i} failed:\n{text[-3000:]}"
    assert "CB_WORKER_OK" in outputs[1]
    toks = outputs[0].split("CB_TOKENS ")[1].splitlines()[0]
    assert toks == str(ref)


CB_CHUNKED_RUNNER = _RUNNER_PREAMBLE + r"""
import jax.numpy as jnp
from flax import linen as nn
from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
from pyspark_tf_gke_tpu.train.serving import (
    announce_shutdown, serve_worker_loop, shard_params_for_serving)
from pyspark_tf_gke_tpu.utils.seeding import make_rng

# PAGED model: chunk progress (pieces + activation) must ride the
# OP_CB_ADMIT wire so both replicas' block tables stay identical
cfg = CausalLMConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, num_kv_heads=2, intermediate_size=64,
                     max_seq_len=64, dtype=jnp.float32,
                     kv_page_size=8, kv_num_pages=24)
mesh = make_mesh({"dp": 8}, jax.devices()[:8])
model = CausalLM(cfg, mesh=mesh)
params = jax.device_get(nn.meta.unbox(
    jax.jit(model.init)(make_rng(7), jnp.zeros((1, 8), jnp.int32))["params"]))
placed = shard_params_for_serving(model, params, mesh)

if pid == 0:
    eng = ContinuousEngine(model, placed, num_slots=2, chunk=3,
                           buckets=(8, 16, 64), mesh=mesh, announce=True,
                           prefill_chunk=32)
    # 40-token prompt -> two 32/8 pieces over the wire; short ones
    # admit whole and decode between the pieces
    rids = [eng.submit(np.arange(4, 44, dtype=np.int32) % 60 + 1, 5),
            eng.submit(np.arange(10, 16, dtype=np.int32), 7),
            eng.submit(np.arange(2, 7, dtype=np.int32), 4)]
    results = dict(eng.run_until_drained())
    announce_shutdown()
    print("CBC_TOKENS", [results[r] for r in rids])
else:
    served = serve_worker_loop(model, placed, mesh)
    print("CBC_WORKER_OK", served)
"""


@pytest.mark.slow
def test_two_process_chunked_prefill_paged_matches_single_process(multiproc_backend):
    """Chunked prefill over the announce/replay wire (paged engine):
    process 0 announces each prompt PIECE on OP_CB_ADMIT (flags
    bitfield + fill payload + block-table row) and the final
    activation; process 1 replays them into its SlotDeviceState
    replica. Tokens must equal the identical single-process engine's —
    the proof that chunk progress on the wire keeps worker schedules
    (and block tables) identical."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
    from pyspark_tf_gke_tpu.train.serving import shard_params_for_serving
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    cfg = CausalLMConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=4, num_kv_heads=2,
                         intermediate_size=64, max_seq_len=64,
                         dtype=jnp.float32, kv_page_size=8,
                         kv_num_pages=24)
    mesh = make_mesh({"dp": 8}, jax.devices()[:8])
    model = CausalLM(cfg, mesh=mesh)
    params = jax.device_get(nn.meta.unbox(jax.jit(model.init)(
        make_rng(7), jnp.zeros((1, 8), jnp.int32))["params"]))
    placed = shard_params_for_serving(model, params, mesh)
    eng = ContinuousEngine(model, placed, num_slots=2, chunk=3,
                           buckets=(8, 16, 64), mesh=mesh,
                           prefill_chunk=32)
    rids = [eng.submit(np.arange(4, 44, dtype=np.int32) % 60 + 1, 5),
            eng.submit(np.arange(10, 16, dtype=np.int32), 7),
            eng.submit(np.arange(2, 7, dtype=np.int32), 4)]
    results = dict(eng.run_until_drained())
    ref = [results[r] for r in rids]
    assert eng.stats["prefill_chunks"] == 2  # the long prompt chunked

    procs = _spawn_pair(lambda pid, port: [
        "-c", CB_CHUNKED_RUNNER, "2", str(pid), f"127.0.0.1:{port}"])
    outputs = _communicate_pair(procs)
    for i, (p, text) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"cbc proc {i} failed:\n{text[-3000:]}"
    assert "CBC_WORKER_OK" in outputs[1]
    toks = outputs[0].split("CBC_TOKENS ")[1].splitlines()[0]
    assert toks == str(ref)


@pytest.mark.slow
def test_dryrun_envelope_n16():
    """Round-4 verdict Next #7: the full dryrun config matrix (incl.
    pp*tp composed, ep*fsdp, 4-slice hybrid DCN) must hold beyond the
    8-device mesh the driver exercises. Subprocess: the envelope needs
    its own XLA_FLAGS device count before jax initializes. n=32 is the
    same code path (committed evidence: tools/dryrun_envelope.json)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(16)"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=1500)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert "dryrun_multichip(16) passed" in out
    for label in ("dp×pp×tp composed pipeline", "dp×fsdp×ep moe",
                  "hybrid 4-slice dcn:dp×ici:fsdp×tp mlm"):
        assert f"dryrun[{label}]" in out, f"missing envelope config {label}"
