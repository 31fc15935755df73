"""Local replica-fleet harness: the ONE copy of the launch scaffolding
shared by ``tools/smoke_check.py --router``, ``tools/replay.py run
--localfleet`` and the slow kill-one-replica soak in
``tests/test_router.py``.

All of them drive the same contract — N tiny CPU ``BundleServer``
subprocesses behind the real router CLI — and before this module each
carried its own bundle-export recipe, port allocator, Popen argv, and
wait-for-healthy loop; a replica CLI flag change had to be edited three
times and would silently drift. Everything here is stdlib-only and
keeps the CALLING process jax-free: the tiny serving bundle is exported
by a CPU-pinned child process, so a smoke_check parent never
initializes a jax backend (a chip belongs to one process at a time; a
router-plane check must not take it).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Optional, Sequence

from pyspark_tf_gke_tpu.replay.stats import pct

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# byte-tokenizer-compatible CausalLM (vocab 259 covers the byte range);
# small enough that two replicas + a router fit a 1-vCPU box
TINY_BUNDLE_EXPORT_SRC = (
    "import jax, sys\n"
    "jax.config.update('jax_platforms', 'cpu')\n"
    "import jax.numpy as jnp\n"
    "from flax import linen as nn\n"
    "from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig\n"
    "from pyspark_tf_gke_tpu.train.export import export_serving_bundle\n"
    "from pyspark_tf_gke_tpu.utils.seeding import make_rng\n"
    "cfg = CausalLMConfig(vocab_size=259, hidden_size=32,\n"
    "                     num_layers=2, num_heads=2,\n"
    "                     intermediate_size=64, max_seq_len=64,\n"
    "                     dtype=jnp.float32)\n"
    "model = CausalLM(cfg)\n"
    "params = nn.meta.unbox(jax.jit(model.init)(\n"
    "    make_rng(0), jnp.zeros((1, 8), jnp.int32))['params'])\n"
    "export_serving_bundle(cfg, params, sys.argv[1], quantize=False)\n")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cpu_env() -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu")


# PAGED variant of the tiny bundle: same weights recipe, but exported
# with KV page-pool geometry so serve's --prefix-cache routes to the
# engine-level radix cache — the precondition for the disaggregated
# KV-page handoff (export/import rides the radix trie). The model is
# BUILT dense (init needs no pool) and EXPORTED paged, the same shape
# smoke_check's --prefix-cache check uses.
TINY_PAGED_BUNDLE_EXPORT_SRC = (
    "import dataclasses, jax, sys\n"
    "jax.config.update('jax_platforms', 'cpu')\n"
    "import jax.numpy as jnp\n"
    "from flax import linen as nn\n"
    "from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig\n"
    "from pyspark_tf_gke_tpu.train.export import export_serving_bundle\n"
    "from pyspark_tf_gke_tpu.utils.seeding import make_rng\n"
    "cfg = CausalLMConfig(vocab_size=259, hidden_size=32,\n"
    "                     num_layers=2, num_heads=2,\n"
    "                     intermediate_size=64, max_seq_len=256,\n"
    "                     kv_page_size=32, kv_num_pages=32,\n"
    "                     dtype=jnp.float32)\n"
    "model = CausalLM(dataclasses.replace(cfg, kv_num_pages=None))\n"
    "params = nn.meta.unbox(jax.jit(model.init)(\n"
    "    make_rng(0), jnp.zeros((1, 8), jnp.int32))['params'])\n"
    "export_serving_bundle(cfg, params, sys.argv[1], quantize=False)\n")


def export_tiny_bundle(dest: str, timeout_s: float = 600.0,
                       paged: bool = False) -> str:
    """Export the tiny serving bundle via a CPU-pinned child process
    (the caller's jax stays un-initialized). ``paged=True`` exports
    the paged-KV variant (radix cache, KV-page handoff)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         TINY_PAGED_BUNDLE_EXPORT_SRC if paged
         else TINY_BUNDLE_EXPORT_SRC, dest],
        env=cpu_env(), cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=timeout_s)
    if proc.returncode != 0:
        raise RuntimeError(f"bundle export failed: {proc.stderr[-800:]}")
    return dest


def launch_replica(bundle: str, port: int,
                   extra_args: Sequence[str] = (),
                   quiet: bool = True) -> subprocess.Popen:
    """One CPU-pinned ``train.serve`` replica on 127.0.0.1:port."""
    kw = ({"stdout": subprocess.DEVNULL, "stderr": subprocess.DEVNULL}
          if quiet else {})
    return subprocess.Popen(
        [sys.executable, "-m", "pyspark_tf_gke_tpu.train.serve",
         "--bundle", bundle, "--host", "127.0.0.1", "--port", str(port),
         "--continuous-slots", "2", "--continuous-chunk", "2",
         *extra_args],
        env=cpu_env(), cwd=REPO_ROOT, **kw)


def launch_router(replica_ports: Sequence[int], port: int,
                  extra_args: Sequence[str] = (),
                  quiet: bool = True) -> subprocess.Popen:
    """The real router CLI fronting ``replica_ports``, tuned for local
    checks: tight probe interval, single-failure DOWN."""
    kw = ({"stdout": subprocess.DEVNULL, "stderr": subprocess.DEVNULL}
          if quiet else {})
    return subprocess.Popen(
        [sys.executable, "-m", "pyspark_tf_gke_tpu.router",
         "--host", "127.0.0.1", "--port", str(port),
         "--replicas", ",".join(f"http://127.0.0.1:{p}"
                                for p in replica_ports),
         "--probe-interval", "0.2", "--fail-threshold", "1",
         *extra_args],
        env=dict(os.environ), cwd=REPO_ROOT, **kw)


def wait_healthy(base_url: str, deadline: float,
                 proc: Optional[subprocess.Popen] = None) -> None:
    """Poll ``/healthz`` until 200 or ``deadline`` (epoch seconds);
    fail fast if ``proc`` exits before answering."""
    while True:
        try:
            urllib.request.urlopen(base_url + "/healthz", timeout=2)
            return
        except Exception:  # noqa: BLE001 — still booting
            if proc is not None and proc.poll() is not None:
                raise RuntimeError(
                    f"{base_url} process died at startup "
                    f"(rc={proc.returncode})")
            if time.time() > deadline:
                raise RuntimeError(f"{base_url} never became healthy")
            time.sleep(0.3)


def post_generate(base_url: str, prompt: str, max_new_tokens: int = 6,
                  timeout_s: float = 120.0) -> dict:
    req = urllib.request.Request(
        base_url + "/v1/generate",
        data=json.dumps({"prompts": [prompt],
                         "max_new_tokens": max_new_tokens}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read())


def post_tenant(base_url: str, prompt: str, tenant: str,
                max_new_tokens: int = 6, timeout_s: float = 120.0):
    """One tenant-tagged generate that NEVER raises on an HTTP error
    verdict: returns ``(status, body, latency_ms)`` — 429s are data to
    the fairness scenarios, not exceptions. Transport failures return
    status 0 with the error string in the body."""
    import urllib.error

    req = urllib.request.Request(
        base_url + "/v1/generate",
        data=json.dumps({"prompts": [prompt],
                         "max_new_tokens": max_new_tokens}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Tenant": tenant})
    t0 = time.monotonic()
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            body = json.loads(resp.read())
            status = resp.status
    except urllib.error.HTTPError as exc:
        try:
            body = json.loads(exc.read() or b"{}")
        except ValueError:
            body = {}
        body.setdefault("retry_after", exc.headers.get("Retry-After"))
        body.setdefault("tenant_shed", exc.headers.get("X-Tenant-Shed"))
        status = exc.code
    except Exception as exc:  # noqa: BLE001 — transport failure is an
        #   outcome the scenarios assert on, not a crash
        return 0, {"error": repr(exc)}, (time.monotonic() - t0) * 1000.0
    return status, body, (time.monotonic() - t0) * 1000.0


def run_noisy_neighbor(url: str, *, light_requests: int = 10,
                       light_budget: int = 6, flood_threads: int = 3,
                       flood_budget: int = 12,
                       light_prompt: str = "light request",
                       mid_flood_hook=None,
                       timeout_s: float = 120.0) -> dict:
    """THE noisy-neighbor scenario, shared by ``tools/smoke_check.py
    --fairness`` and the slow chaos soak in ``tests/test_fairness.py``:
    ``flood_threads`` greedy "noisy"-tenant loops hammer ``url`` while
    the "light" tenant runs ``light_requests`` serial generates.
    ``mid_flood_hook`` (optional) fires once, halfway through the light
    sequence — the scale-up/down injection point (start or SIGKILL a
    replica). Returns per-tenant outcome tallies + the light tenant's
    latency list; every request reaches a terminal outcome before this
    returns (the flood stops and joins)."""
    import threading

    out = {
        "light": {"ok": 0, "lat_ms": [], "errors": []},
        "noisy": {"ok": 0, "tenant_429": 0, "other_429": 0,
                  "shed_503": 0, "errors": []},
        "noisy_attempts": 0,
    }
    lock = threading.Lock()
    stop = threading.Event()

    def flood(i: int):
        n = 0
        while not stop.is_set():
            status, body, _dt = post_tenant(
                url, f"noisy {i} {n}", "noisy",
                max_new_tokens=flood_budget, timeout_s=timeout_s)
            n += 1
            with lock:
                out["noisy_attempts"] += 1
                if status == 200:
                    out["noisy"]["ok"] += 1
                elif status == 429 and (
                        str(body.get("reason", "")).startswith("tenant_")
                        or body.get("tenant_shed")):
                    out["noisy"]["tenant_429"] += 1
                elif status == 429:
                    out["noisy"]["other_429"] += 1
                elif status == 503:
                    # router/replica drain or no-replica blips during a
                    # scale event: terminal, counted, not a loss
                    out["noisy"]["shed_503"] += 1
                else:
                    out["noisy"]["errors"].append((status, str(body)[:200]))
            if status == 429:
                time.sleep(0.05)  # a real client honors Retry-After;
                #   a zero-sleep loop would just measure socket churn

    threads = [threading.Thread(target=flood, args=(i,), daemon=True)
               for i in range(flood_threads)]
    for t in threads:
        t.start()
    try:
        for i in range(light_requests):
            if mid_flood_hook is not None and i == light_requests // 2:
                mid_flood_hook()
            status, body, dt = post_tenant(
                url, f"{light_prompt} {i}", "light",
                max_new_tokens=light_budget, timeout_s=timeout_s)
            if status == 200:
                out["light"]["ok"] += 1
                out["light"]["lat_ms"].append(dt)
            else:
                out["light"]["errors"].append((status, str(body)[:200]))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=timeout_s)
    return out


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile of a latency list (0 when empty).
    Thin wrapper over ``replay/stats.pct`` — the ONE percentile
    implementation site — keeping this module's historical empty-list
    contract (0.0, not None)."""
    v = pct(list(xs), q)
    return 0.0 if v is None else v


class LocalFleet:
    """Context manager owning one complete local fleet: a tiny bundle
    export, N CPU replica subprocesses and (optionally) the real
    router CLI in front — the setup every fleet-level check repeats
    (``smoke_check --replay``, ``tools/replay.py run --localfleet``). Exit kills every process and removes the
    temp dir; a partially-failed boot cleans up the same way."""

    def __init__(self, n_replicas: int = 2, *, router: bool = True,
                 replica_args: Sequence[str] = (),
                 per_replica_args: Optional[
                     Sequence[Sequence[str]]] = None,
                 router_args: Sequence[str] = (),
                 bundle: Optional[str] = None, paged: bool = False,
                 boot_timeout_s: float = 600.0, quiet: bool = True):
        self.n_replicas = int(n_replicas)
        self.with_router = router
        self.replica_args = tuple(replica_args)
        # per-index extra args APPENDED to replica_args — the role-split
        # fleet shape (replica 0 `--role prefill`, the rest `--role
        # decode`); a restart keeps its index's args, a scale-up beyond
        # the list gets the shared args only
        self.per_replica_args = (None if per_replica_args is None else
                                 tuple(tuple(a) for a in per_replica_args))
        if (self.per_replica_args is not None
                and len(self.per_replica_args) != self.n_replicas):
            raise ValueError("per_replica_args must have one entry "
                             "per replica")
        self.router_args = tuple(router_args)
        self.bundle = bundle  # pre-exported dir to reuse (callers
        #   booting several fleets pay the export once)
        self.paged = bool(paged)  # export the paged-KV tiny bundle
        #   (radix cache + KV-page handoff) when self-exporting
        self.boot_timeout_s = float(boot_timeout_s)
        self.quiet = quiet
        self.procs: list = []
        self.router_proc: Optional[subprocess.Popen] = None
        self.replica_ports: list = []
        self.router_port: Optional[int] = None
        self._tmp: Optional[str] = None

    def _args_for(self, i: int) -> tuple:
        extra = (self.per_replica_args[i]
                 if self.per_replica_args is not None
                 and i < len(self.per_replica_args) else ())
        return self.replica_args + tuple(extra)
        self._bundle_dir: Optional[str] = None  # retained for restarts

    @property
    def url(self) -> str:
        """The fleet's front door (router when present, else the
        first replica)."""
        port = (self.router_port if self.with_router
                else self.replica_ports[0])
        return f"http://127.0.0.1:{port}"

    @property
    def replica_urls(self) -> list:
        return [f"http://127.0.0.1:{p}" for p in self.replica_ports]

    def warm(self, prompts: Sequence[str] = ("warm a", "warm b"),
             max_new_tokens: int = 4) -> None:
        """Hit each replica DIRECTLY (routed warms can all land on one
        replica via affinity), so first-request JIT compiles never
        land inside a caller's timed run."""
        for rurl in self.replica_urls:
            for prompt in prompts:
                post_generate(rurl, prompt,
                              max_new_tokens=max_new_tokens)

    def wait_idle(self, timeout_s: float = 60.0) -> bool:
        """Poll every replica's ``/loadz`` until the whole fleet
        reports an empty engine (``queued == 0 and active == 0``) or
        the timeout passes; returns whether it quiesced. A replica
        still grinding a previous scenario's backlog steals the
        shared core from whatever the caller measures next, so
        fleet-level checks quiesce between phases. Transient poll
        errors count as busy (a saturated replica answering late is
        exactly the not-idle case)."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            idle = True
            for rurl in self.replica_urls:
                try:
                    with urllib.request.urlopen(rurl + "/loadz",
                                                timeout=5) as resp:
                        lz = json.loads(resp.read())
                    if lz["queued"] or lz["active"]:
                        idle = False
                except Exception:  # noqa: BLE001 — late answer = busy
                    idle = False
            if idle:
                return True
            time.sleep(0.3)
        return False

    # -- chaos hooks (chaos/runner.py drives these at scheduled offsets) --

    def kill_replica(self, i: int) -> None:
        """SIGKILL replica ``i`` (the pod-death shape: no drain, no
        goodbye — in-flight requests to it fail at the transport)."""
        proc = self.procs[i]
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)

    def stop_replica(self, i: int) -> None:
        """SIGSTOP replica ``i``: alive but unresponsive — the local
        stand-in for a hung host AND a network partition (probes time
        out, open streams stall). Pair with :meth:`cont_replica`."""
        import signal

        self.procs[i].send_signal(signal.SIGSTOP)

    def cont_replica(self, i: int) -> None:
        import signal

        if self.procs[i].poll() is None:
            self.procs[i].send_signal(signal.SIGCONT)

    # -- scale hooks (router/autopilot.py actuates through these) --------

    def start_replica(self) -> str:
        """Boot ONE additional replica (the scale-up actuation shape):
        fresh port, same bundle and args, appended to
        ``procs``/``replica_ports``; returns its base URL once
        ``/healthz`` answers. The caller registers it with the router
        (POST /admin/replicas) — a booted-but-unregistered replica
        receives no traffic."""
        if self._bundle_dir is None:
            raise RuntimeError("fleet never booted")
        port = free_port()
        proc = launch_replica(self._bundle_dir, port,
                              extra_args=self._args_for(len(self.procs)),
                              quiet=self.quiet)
        self.replica_ports.append(port)
        self.procs.append(proc)
        self.n_replicas = len(self.procs)
        url = f"http://127.0.0.1:{port}"
        wait_healthy(url, time.time() + self.boot_timeout_s, proc)
        return url

    def drain_replica(self, i: int, timeout_s: float = 30.0) -> bool:
        """SIGTERM replica ``i`` — the graceful-eviction shape: serve's
        drain path finishes in-flight work, then the process exits.
        Returns whether it exited within ``timeout_s`` (False = still
        draining, e.g. the hung-drain chaos case — the caller decides
        whether to escalate to :meth:`kill_replica`)."""
        import signal

        proc = self.procs[i]
        if proc.poll() is not None:
            return True
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=timeout_s)
            return True
        except subprocess.TimeoutExpired:
            return False

    def restart_replica(self, i: int) -> None:
        """Relaunch replica ``i`` on its ORIGINAL port and args (the
        k8s pod-replacement shape: same Service endpoint, fresh
        process) and wait until it answers /healthz."""
        if self._bundle_dir is None:
            raise RuntimeError("fleet never booted")
        if self.procs[i].poll() is None:
            self.kill_replica(i)
        self.procs[i] = launch_replica(
            self._bundle_dir, self.replica_ports[i],
            extra_args=self._args_for(i), quiet=self.quiet)
        wait_healthy(self.replica_urls[i],
                     time.time() + self.boot_timeout_s, self.procs[i])

    def __enter__(self) -> "LocalFleet":
        import tempfile

        self._tmp = tempfile.mkdtemp(prefix="localfleet-")
        try:
            bundle = self.bundle or export_tiny_bundle(
                os.path.join(self._tmp, "bundle"),
                timeout_s=self.boot_timeout_s, paged=self.paged)
            self._bundle_dir = bundle
            self.replica_ports = [free_port()
                                  for _ in range(self.n_replicas)]
            self.procs = [launch_replica(bundle, p,
                                         extra_args=self._args_for(i),
                                         quiet=self.quiet)
                          for i, p in enumerate(self.replica_ports)]
            deadline = time.time() + self.boot_timeout_s
            if self.with_router:
                self.router_port = free_port()
                self.router_proc = launch_router(
                    self.replica_ports, self.router_port,
                    extra_args=self.router_args, quiet=self.quiet)
            for p, proc in zip(self.replica_ports, self.procs):
                wait_healthy(f"http://127.0.0.1:{p}", deadline, proc)
            if self.router_proc is not None:
                wait_healthy(self.url, deadline, self.router_proc)
        except BaseException:
            self.__exit__(None, None, None)
            raise
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        import shutil

        for p in [self.router_proc, *self.procs]:
            if p is not None and p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
        if self._tmp:
            shutil.rmtree(self._tmp, ignore_errors=True)
