"""Failure detection + elastic recovery (train/resilience.py).

The chaos test drives the REAL CLI end to end: inject a fault mid-run,
watch the recovery wrapper restore the latest checkpoint and finish —
the behavior the reference never had (SURVEY §5: no trainer-level
failure handling, no fault injection anywhere).
"""

import json
import os
import time

import numpy as np
import pytest

from pyspark_tf_gke_tpu.train.resilience import (
    FaultInjector,
    Heartbeat,
    InjectedFault,
    retry_with_backoff,
    run_with_recovery,
)


def test_heartbeat_write_and_age(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = Heartbeat(path, every_steps=5)
    hb.beat(3)  # not a multiple of 5 → skipped
    assert Heartbeat.age(path) is None
    hb.beat(5)
    data = Heartbeat.read(path)
    assert data["step"] == 5 and data["process_count"] == 1
    assert Heartbeat.age(path) < 5.0
    assert not Heartbeat.is_stalled(path, stall_seconds=60)
    # Backdate the beat → stalled.
    data["time"] = time.time() - 120
    with open(path, "w") as fh:
        json.dump(data, fh)
    assert Heartbeat.is_stalled(path, stall_seconds=60)


def test_heartbeat_missing_file_not_stalled(tmp_path):
    path = str(tmp_path / "never.json")
    assert Heartbeat.age(path) is None
    assert not Heartbeat.is_stalled(path, stall_seconds=0.001)


def test_fault_injector_fires_once():
    fi = FaultInjector([4])
    fi.maybe_fail(3)
    with pytest.raises(InjectedFault):
        fi.maybe_fail(4)
    fi.maybe_fail(4)  # replay after resume: no re-fire
    assert FaultInjector.from_spec("") is None
    assert FaultInjector.from_spec("2, 7").pending == {2, 7}


def test_fault_injector_chaos_spec_parses_fail_and_slow():
    fi = FaultInjector.from_chaos_spec("fail@3, 7,slow@5:0.25")
    assert fi.pending == {3, 7}
    assert fi.slow_pending == {5: 0.25}
    assert fi.n_faults == 2 and fi.n_slow == 1
    assert FaultInjector.from_chaos_spec("") is None
    with pytest.raises(ValueError, match="slow@STEP:SECONDS"):
        FaultInjector.from_chaos_spec("slow@5")
    with pytest.raises(ValueError):
        FaultInjector.from_chaos_spec("fail@x")


def test_fault_injector_slow_fires_once(monkeypatch):
    from pyspark_tf_gke_tpu.train import resilience

    slept = []
    monkeypatch.setattr(resilience.time, "sleep",
                        lambda s: slept.append(s))
    fi = FaultInjector(slow_at_steps={4: 0.5})
    assert fi.maybe_slow(3) == 0.0
    assert fi.maybe_slow(4) == 0.5
    assert fi.maybe_slow(4) == 0.0  # once per planned step
    assert slept == [0.5]
    assert fi.fired_faults == 0  # slow steps are not failures


def test_fault_injector_fired_faults_accounting():
    fi = FaultInjector([2, 9])
    assert fi.fired_faults == 0
    with pytest.raises(InjectedFault):
        fi.maybe_fail(2)
    assert fi.fired_faults == 1 and fi.n_faults == 2


def test_retry_with_backoff_succeeds_with_jittered_delays():
    calls = []
    delays = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_with_backoff(
        flaky, attempts=4, base_delay_s=0.1, max_delay_s=5.0,
        jitter=0.5, op="test_op", sleep=delays.append) == "ok"
    assert len(calls) == 3 and len(delays) == 2
    # exponential with the top half jittered: delay_k in
    # [nominal/2, nominal] for nominal = base * 2**(k-1)
    assert 0.05 <= delays[0] <= 0.1
    assert 0.1 <= delays[1] <= 0.2


def test_retry_with_backoff_exhausts_and_reraises():
    calls = []

    def always(*_):
        calls.append(1)
        raise RuntimeError("permanent")

    with pytest.raises(RuntimeError, match="permanent"):
        retry_with_backoff(always, attempts=2, sleep=lambda _: None)
    assert len(calls) == 2  # attempts counts calls


def test_retry_with_backoff_give_up_on_fails_fast():
    # deterministic/permanent classes carve OUT of a broad retry_on:
    # a mistyped path must not masquerade as a storage outage
    calls = []

    def missing():
        calls.append(1)
        raise FileNotFoundError("no such bundle")

    with pytest.raises(FileNotFoundError):
        retry_with_backoff(missing, attempts=5,
                           give_up_on=(FileNotFoundError,),
                           sleep=lambda _: None)
    assert len(calls) == 1


def test_retry_with_backoff_non_matching_propagates_immediately():
    calls = []

    def wrong_kind():
        calls.append(1)
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        retry_with_backoff(wrong_kind, attempts=5, retry_on=(OSError,),
                           sleep=lambda _: None)
    assert len(calls) == 1


def test_retry_with_backoff_emits_trail_and_counter(tmp_path):
    from pyspark_tf_gke_tpu.obs.events import (EventLog, read_events,
                                               set_event_log)
    from pyspark_tf_gke_tpu.obs.metrics import (MetricsRegistry,
                                                set_registry)

    trail = str(tmp_path / "trail.jsonl")
    set_event_log(EventLog(trail))
    reg = MetricsRegistry()
    set_registry(reg)
    try:
        state = {"n": 0}

        def once():
            state["n"] += 1
            if state["n"] == 1:
                raise OSError("blip")
            return state["n"]

        assert retry_with_backoff(once, op="unit_op",
                                  base_delay_s=0.001,
                                  sleep=lambda _: None) == 2
        events = [e for e in read_events(trail) if e["kind"] == "retry"]
        assert len(events) == 1
        assert events[0]["op"] == "unit_op" and events[0]["attempt"] == 1
        assert "OSError" in events[0]["error"]
        assert reg.get("retries_total").labels(op="unit_op").value == 1
    finally:
        set_event_log(None)
        set_registry(None)


def test_run_with_recovery_retries_then_succeeds():
    calls = []

    def train_once(attempt):
        calls.append(attempt)
        if attempt < 2:
            raise RuntimeError("boom")
        return "done"

    assert run_with_recovery(train_once, max_restarts=2) == "done"
    assert calls == [0, 1, 2]


def test_run_with_recovery_exhausts_restarts():
    def train_once(attempt):
        raise RuntimeError("always")

    with pytest.raises(RuntimeError, match="always"):
        run_with_recovery(train_once, max_restarts=1)


def test_run_with_recovery_fatal_propagates():
    def train_once(attempt):
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        run_with_recovery(train_once, max_restarts=5)


def test_cli_chaos_recovery_end_to_end(tmp_path):
    """Fault at global step 12 with checkpoints every 5 steps: the wrapper
    must resume from step >= 10 and finish all epochs, producing the full
    artifact set plus a live heartbeat."""
    from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_csv
    from pyspark_tf_gke_tpu.train import cli

    csv = str(tmp_path / "d.csv")
    make_synthetic_csv(csv, rows=320)
    out = str(tmp_path / "out")
    history = cli.main([
        "--data-path", csv, "--epochs", "4", "--batch-size", "32",
        "--output-dir", out, "--mesh-shape", "dp=8",
        "--checkpoint-every-steps", "5", "--max-restarts", "1",
        "--fail-at-steps", "12", "--heartbeat-every-steps", "2",
    ])
    # 4 epochs x 8 steps = 32 steps total; the restart re-runs whole
    # epochs, so history still records 4 epochs.
    assert len(history["loss"]) == 4
    assert all(np.isfinite(v) for v in history["loss"])
    # default heartbeat path is per-process: a hung
    # process must not hide behind a live peer's shared-file beats
    hb = Heartbeat.read(os.path.join(out, "heartbeat-0.json"))
    assert hb is not None and hb["step"] >= 30
    assert os.path.exists(os.path.join(out, "history.json"))


def test_cli_chaos_exhausted_raises(tmp_path):
    """max_restarts=0 → the injected fault propagates."""
    from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_csv
    from pyspark_tf_gke_tpu.train import cli

    csv = str(tmp_path / "d.csv")
    make_synthetic_csv(csv, rows=320)
    with pytest.raises(InjectedFault):
        cli.main([
            "--data-path", csv, "--epochs", "2", "--batch-size", "32",
            "--output-dir", str(tmp_path / "out2"), "--mesh-shape", "dp=8",
            "--fail-at-steps", "3",
        ])


def test_watchdog_cli_detects_stale_and_clean(tmp_path, capsys):
    import json
    import time as _time

    from pyspark_tf_gke_tpu.train.resilience import _watch_main

    stale = tmp_path / "hb.json"
    stale.write_text(json.dumps({"step": 3, "time": 1.0,
                                 "process_index": 0, "process_count": 1}))
    rc = _watch_main(["--paths", str(stale), "--stall", "5",
                      "--timeout", "3", "--poll", "0.1"])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out["stalled"] == str(stale) and out["last"]["step"] == 3

    fresh = tmp_path / "hb2.json"
    fresh.write_text(json.dumps({"step": 9, "time": _time.time() + 3600,
                                 "process_index": 0, "process_count": 1}))
    assert _watch_main(["--paths", str(fresh), "--stall", "60",
                        "--timeout", "1", "--poll", "0.2"]) == 0


def test_detect_stall_never_appearing_file(tmp_path):
    # A worker hung before its FIRST beat writes no file at all — after
    # stall_seconds of watchdog runtime a still-missing path is stalled
    # (it previously passed as healthy forever).
    from pyspark_tf_gke_tpu.train.resilience import detect_stall

    missing = str(tmp_path / "never-appears.json")
    hit = detect_stall([missing], stall_seconds=0.2, timeout_s=2.0,
                       poll_s=0.05)
    assert hit == missing
    # ... but with timeout < stall window the grace never elapses: the
    # "not started yet" (k8s initialDelay) phase stays healthy.
    assert detect_stall([missing], stall_seconds=60, timeout_s=0.3,
                        poll_s=0.05) is None
