"""chip_smoke.py's contract off the chip, and the compile-cache helper
every compiling entry point calls.

The chip itself is only reachable through the chip tool; what the CPU
can show is that the gate FAILS without an accelerator (no result line),
that its parent stays off jax (one process per chip), that ``--tiny``
rehearses every leg, and that the cache lands where it can be placed
from outside.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from pyspark_tf_gke_tpu.utils.compile_cache import (
    DEFAULT_CACHE_DIR,
    ENV_VAR,
    enable_compile_cache,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_compile_cache_env_set_means_code_sets_nothing(monkeypatch, tmp_path):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv(ENV_VAR, placed)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(placed)  # jax makes it, on first use


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = enable_compile_cache(), enable_compile_cache()
        assert first == second == DEFAULT_CACHE_DIR
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_path_equal_across_processes(tmp_path):
    code = ("import jax; from pyspark_tf_gke_tpu.utils.compile_cache import "
            "enable_compile_cache as e; print(e()); "
            "print(jax.config.jax_compilation_cache_dir)")

    def child(env, cwd):
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd, text=True,
            capture_output=True, timeout=120, check=True).stdout.split()
        return out

    base = _env(PYTHONPATH=REPO)
    # two processes, two working directories: same in-checkout path
    assert child(base, REPO) == child(base, str(tmp_path)) == [
        DEFAULT_CACHE_DIR] * 2
    # placed from outside: jax reads the variable itself
    placed = str(tmp_path / "placed")
    assert child(dict(base, **{ENV_VAR: placed}), REPO) == [placed] * 2


def test_chip_smoke_parent_stays_off_jax():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib', 'pyspark_tf_gke_tpu')]; "
            "assert not bad, bad" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_chip_smoke_without_accelerator_fails_with_no_result(tmp_path):
    done = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out")],
        env=_env(), text=True, capture_output=True, timeout=300)
    assert done.returncode not in (0, 1), done.stdout + done.stderr
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_chip_smoke_outside_a_checkout_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(SMOKE, "rb").read())
    done = subprocess.run([sys.executable, str(lone)], env=_env(),
                          cwd=tmp_path, text=True, capture_output=True,
                          timeout=60)
    assert done.returncode != 0
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


@pytest.mark.slow
def test_chip_smoke_tiny_runs_every_leg_on_the_cpu(tmp_path):
    # 4 fake devices so the multi-chip legs (dp=2,fsdp=2 trainer, --tp 4
    # server) rehearse too
    done = subprocess.run(
        [sys.executable, SMOKE, "--tiny", "--out", str(tmp_path / "out")],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        text=True, capture_output=True, timeout=900)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]
    report, verdict = map(json.loads, done.stdout.strip().splitlines()[-2:])
    # the last line carries exactly the verdict keys; the report precedes it
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert report["ok"] is True and report["tiny"] is True
    assert report["device"] == verdict["device"]
    assert set(report["versions"]) == {"jax", "jaxlib", "libtpu"}
    for leg in ("kernels", "trainer", "server", "trainer_4chip",
                "server_4chip"):
        assert report["legs"][leg]["ok"] is True, report["legs"][leg]
        assert report["legs"][leg]["seconds"] > 0
    assert report["compile_cache"]["dir"] == DEFAULT_CACHE_DIR
    loss = report["legs"]["trainer"]["loss"]
    assert loss[1] < loss[0]
