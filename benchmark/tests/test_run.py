"""The runner end to end at a tiny size on the CPU, its control, and the
timed path broken underneath it. These skip the harness's look for a chip
(``allow_cpu``); what they print names the platform and carries no metric."""

import json
import os
import subprocess
import sys

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
TRAIN = "tiny-gpt2.train.tiny-seq128"


def argv(workload, seed=7, seconds=2, trace=0):
    return ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace), "--bench", TINY]


def tiny_run(workload, **kw):
    return bench_run.main(argv(workload, **kw), allow_cpu=True)


def test_train_runner_end_to_end(capsys):
    result = tiny_run(TRAIN, seed=2 ** 31 + 9)
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    # refused as a device result: the platform is named, no metric is written
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {} and "refused" in result
    assert list(result)[-1] == "checks"
    for row in result["checks"].values():
        assert set(row) == {"value", "limit"}
    assert result["correct"] and result["attempted"] >= 2
    assert set(result["checks"]) == {"loss3_gap", "grad1_gap", "delta3_gap",
                                     "compiles_in_window", "loss_not_finite"}
    assert {"loss1_gap", "loss2_gap"} <= set(result["info"])      # shown, not compared
    assert set(result["info"]["setup_parts"]) == {
        "start_and_devices", "import_program", "init_state", "weights",
        "proof_steps", "warm"}
    captured = capsys.readouterr()
    assert json.loads(captured.out.strip().splitlines()[-1])["correct"] is True
    assert captured.err.strip().splitlines()[-1].startswith("check ")


def test_no_accelerator_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py")] + argv(TRAIN),
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 3 and p.stdout.strip() == ""


def test_alone_with_the_benchmark_file_it_refuses(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, str(tmp_path / "benchmark" / "run.py"),
                        "--workload", "gpt2-medium.train.seq1024", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, None) and p.stdout.strip() == ""


def test_no_knob_rewrites_the_cell_from_the_command_line():
    with pytest.raises(SystemExit):
        bench_run.prepare(argv(TRAIN) + ["--override", "cell.limits.grad1_gap=1"],
                          allow_cpu=True)


# -- the control and the planted faults, judged as a run is --------------------

def test_control_and_faults_come_out_as_not_correct():
    """``tools/control.py`` as the chip runs it, at a size the CPU holds: the
    float8 control, half a batch and an unchanged state each go through
    ``lib/checks.py`` with the cell's limits and each fails."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
    import control

    _, _, ctx = bench_run.prepare(argv(TRAIN, seed=22), allow_cpu=True)
    spec = ctx["spec"]
    verdicts = control.judge(spec["config"], spec["cell"], spec["traffic"], 22,
                             spec["cell"]["train"]["rows_per_chip"])
    assert set(verdicts) == {"control_fp8", "fault_half_batch", "fault_state_unchanged"}
    for name, v in verdicts.items():
        assert v["correct"] is False, name
        assert set(v["checks"]) == {"loss3_gap", "grad1_gap", "delta3_gap"}
    unchanged = verdicts["fault_state_unchanged"]["checks"]["delta3_gap"]
    assert unchanged["value"] == pytest.approx(1.0) and unchanged["value"] > unchanged["limit"]


# -- the timed path broken underneath a whole run ------------------------------

def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax

    from pyspark_tf_gke_tpu.train.trainer import Trainer

    build = Trainer._build_steps

    def broken(self):
        build(self)
        raw = self._raw_train_step
        self._train_step = jax.jit(lambda state, batch: (state, raw(state, batch)[1]))

    monkeypatch.setattr(Trainer, "_build_steps", broken)
    result = tiny_run(TRAIN, seed=31)
    assert result["correct"] is False
    assert result["checks"]["delta3_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from pyspark_tf_gke_tpu.train.trainer import Trainer

    step = Trainer.step

    def broken(self, state, batch):
        return step(self, state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})

    monkeypatch.setattr(Trainer, "step", broken)
    result = tiny_run(TRAIN, seed=32)
    assert result["correct"] is False
