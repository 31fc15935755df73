"""bench.py's parent-side helpers that survive without a probe ladder:
argv identity, flag guards, the variant-regression guard, the one-shot
runner's failure contract, and tools/trail_report.py's rendering.

Nothing here touches a device: subprocess layers are monkeypatched.
"""

import json
import subprocess

import pytest

import bench


class _Proc:
    def __init__(self, rc=0, out="", err=""):
        self.returncode = rc
        self.stdout = out
        self.stderr = err


def test_latest_history_distinguishes_cnn_variants(monkeypatch, tmp_path):
    # A cnn --bf16-moments entry must never stand in for the f32 parity
    # flagship in the variant guard's baseline lookup (and vice versa).
    hist = tmp_path / "hist.jsonl"
    hist.write_text(
        json.dumps({"ts": "t1", "argv": ["cnn"],
                    "result": {"value": 1.0}}) + "\n" +
        json.dumps({"ts": "t2", "argv": ["cnn", "--bf16-moments"],
                    "result": {"value": 2.0}}) + "\n")
    monkeypatch.setattr(bench, "HISTORY_PATH", str(hist))
    assert bench._latest_history(["cnn"])["ts"] == "t1"
    assert bench._latest_history(["cnn", "--bf16-moments"])["ts"] == "t2"
    assert bench._latest_history([])["ts"] == "t1"  # bare == flagship


def test_failed_run_prints_no_number_and_no_old_entry(monkeypatch, tmp_path,
                                                      capsys):
    # Off the chip a device workload's --run child exits non-zero. The
    # parent must run it ONCE, print an error line with value null and
    # nothing from the trail, and exit non-zero — even when the trail
    # holds an entry for exactly this invocation.
    hist = tmp_path / "hist.jsonl"
    hist.write_text(json.dumps(
        {"ts": "t1", "argv": ["vit"],
         "result": {"metric": "m", "value": 938.2, "unit": "u"}}) + "\n")
    monkeypatch.setattr(bench, "HISTORY_PATH", str(hist))
    runs = []

    def fake_run(cmd, **kw):
        runs.append(cmd)
        return _Proc(1, "", "bench.py vit: a device workload measures on "
                            "a TPU; JAX found 'cpu' (cpu).")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    assert bench.orchestrate(["vit"]) == 1
    assert len(runs) == 1 and "--run" in runs[0]
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    err = json.loads(out[0])
    assert err["value"] is None and err["error"]["stage"] == "run"
    assert err["error"]["rc"] == 1
    assert "938.2" not in out[0] and "last_recorded" not in err
    assert "stale_matrix_summary" not in err
    assert len(out[0]) < 2000  # survives a tail -c 2000 window
    assert hist.read_text().count("\n") == 1  # nothing appended


def test_timed_out_run_is_not_retried(monkeypatch, capsys):
    runs = []

    def fake_run(cmd, **kw):
        runs.append(cmd)
        raise subprocess.TimeoutExpired(cmd=cmd, timeout=1)

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    assert bench.orchestrate(["vit"]) == 1
    assert len(runs) == 1
    err = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert err["value"] is None and "timed out" in err["error"]["detail"]


def test_all_runs_each_workload_once(monkeypatch, capsys):
    # `bench.py all` is a plain loop over the matrix: no probe, no gate
    ran = []
    monkeypatch.setattr(
        bench, "orchestrate",
        lambda argv: ran.append(list(argv)) or (1 if argv[0] == "vit" else 0))
    assert bench.orchestrate_all([]) == 1
    assert ran == [list(w) for w in bench.ALL_WORKLOADS]
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["metric"] == "bench_all" and summary["failures"] == 1
    assert summary["value"] == len(bench.ALL_WORKLOADS) - 1


def test_device_workload_refuses_cpu(monkeypatch):
    # the --run child's device claim: a device workload without --smoke
    # exits non-zero off the TPU; host-only workloads pass through
    import jax

    assert jax.devices()[0].platform == "cpu"  # the test environment
    with pytest.raises(SystemExit, match="measures on a TPU"):
        bench._claim_device("generate", smoke=False)
    bench._claim_device("io", smoke=False)


def test_normalize_argv_order_insensitive():
    a = bench._normalize_argv(["bert", "--seq", "2048", "--no-flash"])
    b = bench._normalize_argv(["bert", "--no-flash", "--seq", "2048"])
    assert a == b
    # --smoke is part of the identity (a tiny-shape smoke measurement,
    # recordable via --history, must never stand in for the full one);
    # the --history/--no-history markers are not
    assert bench._normalize_argv(["cnn", "--smoke"]) == ["cnn", "--smoke"]
    assert bench._normalize_argv(["cnn", "--smoke", "--history"]) == \
        ["cnn", "--smoke"]
    assert bench._normalize_argv([]) == ["cnn"]
    assert (bench._normalize_argv(["cnn", "--bf16-moments"])
            != bench._normalize_argv(["cnn"]))


def test_bf16_moments_rejected_off_flagship():
    import pytest

    with pytest.raises(SystemExit, match="cnn workload only"):
        bench.run_bench(["resnet50", "--bf16-moments"])


def test_s2d_rejected_off_resnet50():
    import pytest

    with pytest.raises(SystemExit, match="resnet50 workload only"):
        bench.run_bench(["cnn", "--s2d"])


def test_trail_report_latest_per_identity(tmp_path):
    # The report must pick the LATEST entry per order-insensitive argv
    # identity and render one markdown row for each.
    from tools import trail_report

    trail = tmp_path / "hist.jsonl"
    rows = [
        {"ts": "t1", "argv": ["cnn"],
         "result": {"metric": "m", "value": 1.0, "unit": "u"}},
        {"ts": "t2", "argv": ["cnn"],
         "result": {"metric": "m", "value": 2.0, "unit": "u"}},
        {"ts": "t3", "argv": ["--s2d", "resnet50"],
         "result": {"metric": "r", "value": 3.0, "unit": "u"}},
        "not json at all",
    ]
    trail.write_text("\n".join(
        r if isinstance(r, str) else json.dumps(r) for r in rows) + "\n")
    entries = trail_report.load(str(trail))
    assert len(entries) == 3  # bad line tolerated
    latest = trail_report.latest_per_identity(entries)
    assert [e["ts"] for e in latest] == ["t2", "t3"]
    # identity is order-insensitive: same as bench.py's variant guard
    assert trail_report.identity(["resnet50", "--s2d"]) == \
        trail_report.identity(["--s2d", "resnet50"])
    out = trail_report.row(latest[0])
    assert "**2 u**" in out and "`t2`" in out


def test_trail_report_update_doc(tmp_path):
    # --update must rewrite ONLY the marked block, idempotently, and
    # refuse a doc without the marker pair (silent no-op would defeat
    # the no-stale-figures guarantee).
    from tools import trail_report

    trail = tmp_path / "hist.jsonl"
    trail.write_text(json.dumps(
        {"ts": "t9", "argv": ["cnn"],
         "result": {"metric": "m", "value": 7.5, "unit": "u"}}) + "\n")
    doc = tmp_path / "doc.md"
    doc.write_text("before\n<!-- trail:table:begin -->\nstale\n"
                   "<!-- trail:table:end -->\nafter\n")
    rc = trail_report.main(["--update", str(doc), "--trail", str(trail)])
    assert rc == 0
    text = doc.read_text()
    assert "stale" not in text and "**7.5 u**" in text
    assert text.startswith("before\n") and text.endswith("after\n")
    # idempotent: second run leaves the file byte-identical
    trail_report.main(["--update", str(doc), "--trail", str(trail)])
    assert doc.read_text() == text
    bare = tmp_path / "bare.md"
    bare.write_text("no markers here\n")
    with pytest.raises(SystemExit):
        trail_report.main(["--update", str(bare), "--trail", str(trail)])


def test_adafactor_flag_guards():
    # argv IS the measurement identity: a silently-ignored or ambiguous
    # optimizer flag would mislabel a trail entry (same contract as the
    # --bf16-moments guard).
    with pytest.raises(SystemExit):
        bench.run_bench(["resnet50", "--adafactor", "--smoke"])
    with pytest.raises(SystemExit):
        bench.run_bench(["cnn", "--bf16-moments", "--adafactor", "--smoke"])


def test_gn_flag_guard():
    with pytest.raises(SystemExit):
        bench.run_bench(["cnn", "--gn", "--smoke"])


def test_trail_report_row_tolerates_non_numeric_value():
    # load() is per-line tolerant; row() must match that stance instead
    # of aborting --update on one malformed entry (ADVICE r4).
    from tools import trail_report

    e = {"ts": "t1", "argv": ["cnn"],
         "result": {"metric": "m", "value": None, "unit": "u"}}
    out = trail_report.row(e)
    assert "t1" in out  # rendered, not raised
    e["result"]["value"] = "broken"
    assert "broken" in trail_report.row(e)


def test_trail_report_keeps_cb_schema_keys():
    # ADVICE r4: bench.py's cb result now writes chunk/unpipelined_chunk/
    # pipeline_depth; the committed round-4 entry still says tuned_chunk.
    # All four must render so no disclosed field silently drops.
    from tools import trail_report

    for k in ("tuned_chunk", "chunk", "unpipelined_chunk",
              "pipeline_depth"):
        assert k in trail_report.EXTRA_KEYS
    e = {"ts": "t1", "argv": ["cb"],
         "result": {"metric": "m", "value": 1.0, "unit": "u",
                    "chunk": 64, "unpipelined_chunk": 16,
                    "pipeline_depth": 1}}
    out = trail_report.row(e)
    assert "chunk 64" in out and "unpipelined_chunk 16" in out
    assert "pipeline_depth 1" in out


def test_variant_regression_guard(monkeypatch):
    # BENCH_r05: resnet50 --fused-bn at 1481 vs 2431 baseline raised no
    # flag. The guard must attach the A/B delta and "regression": true
    # past the 10% threshold — and stay silent within it.
    base_entry = {"ts": "2026-01-01T00:00:00+00:00", "argv": ["resnet50"],
                  "result": {"metric": "m", "value": 2431.0,
                             "unit": "examples/sec/chip"}}
    monkeypatch.setattr(bench, "_latest_history",
                        lambda argv: base_entry)
    result = {"metric": "m", "value": 1481.0, "unit": "examples/sec/chip"}
    bench.annotate_variant_regression(["resnet50", "--fused-bn"], result)
    assert result["regression"] is True
    ab = result["vs_variant_baseline"]
    assert ab["regression"] is True
    assert ab["baseline_value"] == 2431.0
    assert ab["ratio"] == round(1481.0 / 2431.0, 3)
    # within threshold: delta attached, no regression flag
    ok = {"metric": "m", "value": 2300.0, "unit": "examples/sec/chip"}
    bench.annotate_variant_regression(["resnet50", "--fused-bn"], ok)
    assert "regression" not in ok
    assert ok["vs_variant_baseline"]["ratio"] == round(2300 / 2431.0, 3)
    # unit mismatch or no trail entry: silent no-op
    other = {"metric": "m", "value": 1.0, "unit": "tokens/sec"}
    bench.annotate_variant_regression(["resnet50", "--fused-bn"], other)
    assert "vs_variant_baseline" not in other
    monkeypatch.setattr(bench, "_latest_history", lambda argv: None)
    miss = {"metric": "m", "value": 1.0, "unit": "examples/sec/chip"}
    bench.annotate_variant_regression(["resnet50", "--fused-bn"], miss)
    assert "vs_variant_baseline" not in miss
    # non-variant workloads and smoke runs never compare
    plain = {"metric": "m", "value": 1.0, "unit": "examples/sec/chip"}
    bench.annotate_variant_regression(["resnet50"], plain)
    bench.annotate_variant_regression(
        ["resnet50", "--fused-bn", "--smoke"], plain)
    assert "vs_variant_baseline" not in plain


def test_serial_variant_guard_flags_inverted_overlap(monkeypatch):
    # The async engine core's A/B pair: `cb --serial` scores the
    # unpipelined loop against the committed pipelined `cb` baseline.
    # A serial run ABOVE the pipelined baseline means the overlap is
    # hurting — the inversion this mapping exists to surface — while a
    # serial run >10% below it is the expected shape and must flag as
    # the (here: tolerated) variant regression so the delta is on
    # record either way.
    base_entry = {"ts": "2026-01-01T00:00:00+00:00", "argv": ["cb"],
                  "result": {"metric": "m", "value": 3000.0,
                             "unit": "useful_tokens/sec/chip"}}
    monkeypatch.setattr(bench, "_latest_history", lambda argv: base_entry)
    serial = {"metric": "m", "value": 2400.0,
              "unit": "useful_tokens/sec/chip"}
    bench.annotate_variant_regression(["cb", "--serial"], serial)
    ab = serial["vs_variant_baseline"]
    assert ab["baseline_argv"] == "cb"
    assert ab["ratio"] == 0.8 and ab["regression"] is True
    inverted = {"metric": "m", "value": 3300.0,
                "unit": "useful_tokens/sec/chip"}
    bench.annotate_variant_regression(["cb", "--serial"], inverted)
    assert inverted["vs_variant_baseline"]["ratio"] == 1.1
    assert "regression" not in inverted


def test_variant_baselines_are_matrix_workloads():
    # every guard mapping must point at real matrix identities on both
    # sides, or a renamed argv silently disables its A/B
    matrix = {" ".join(bench._normalize_argv(w))
              for w in bench.ALL_WORKLOADS}
    for variant, base in bench.VARIANT_BASELINES.items():
        assert variant in matrix, f"unknown variant {variant!r}"
        assert " ".join(bench._normalize_argv(base)) in matrix, \
            f"unknown baseline for {variant!r}"


def test_chunked_prefill_flag_guards():
    with pytest.raises(SystemExit):
        bench.run_bench(["generate", "--chunked-prefill", "--smoke"])
    with pytest.raises(SystemExit):
        bench.run_bench(["cb", "--chunked-prefill", "--paged", "--smoke"])


def test_fused_bn_flag_guards():
    with pytest.raises(SystemExit):
        bench.run_bench(["cnn", "--fused-bn", "--smoke"])
    with pytest.raises(SystemExit):
        bench.run_bench(["resnet50", "--fused-bn", "--gn", "--smoke"])


def test_trail_report_renders_dict_disclosures():
    # The cb tuning grid is a dict-valued disclosure; it must render as
    # one escaped cell, not break the table or drop silently.
    from tools import trail_report

    assert "tuning_grid" in trail_report.EXTRA_KEYS
    e = {"ts": "t1", "argv": ["cb"],
         "result": {"metric": "m", "value": 1.0, "unit": "u",
                    "tuning_grid": {"chunk64_depth1": 1700.1,
                                    "chunk128_depth2": 1800.5}}}
    out = trail_report.row(e)
    assert '"chunk64_depth1":1700.1' in out
    # 6 columns + borders (incl. the step-telemetry host-overhead
    # column): grid stayed one cell
    assert out.count("|") == 7
    assert "| — |" in out  # no step_phases block -> em-dash, not 0


def test_trail_report_host_overhead_column():
    from tools import trail_report

    e = {"ts": "t1", "argv": ["cb", "--smoke"],
         "result": {"metric": "m", "value": 1.0, "unit": "u",
                    "step_phases": {"host_overhead_frac": 0.5947,
                                    "records": 12}}}
    assert "| 59.5% |" in trail_report.row(e)


def test_paged_flag_guard():
    # --paged off the cb workload must be rejected, not silently
    # ignored (argv IS the trail identity)
    with pytest.raises(SystemExit, match="cb workload only"):
        bench.run_bench(["cnn", "--paged"])
    assert ["cb", "--paged"] in [list(w) for w in bench.ALL_WORKLOADS]


def test_chaos_flag_guard():
    # --chaos (the goodput/p99-under-faults A/B) is a cb-only lever too
    with pytest.raises(SystemExit, match="cb workload only"):
        bench.run_bench(["generate", "--chaos"])
    assert ["cb", "--chaos"] in [list(w) for w in bench.ALL_WORKLOADS]
