"""What PR 32 added beside the other cells' files: the Nemotron-H
configuration, weights, reference, FLOP counts, runner, control and readers.
The runner goes end to end at a tiny size on the CPU (``allow_cpu``: what it
prints names the platform and carries no metric)."""

import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from lib import flops_nemotron_h as F
from lib import peaks
from lib import spans as S
from lib import weights as W
from lib import weights_nemotron_h as N

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny-nemotron.json")
CELL = "tiny-nemotron.train.tiny-seq256"
REAL_CELL = "nemotron-3-nano-30b-a3b.train.seq8192"
NEW_METRICS = ["mfu.train.nemotron-h", "ssd_ms.train", "ssd_roofline",
               "moe_held_tokens_per_expert.train.nemotron-h",
               "moe_held_load_max_over_mean.train.nemotron-h", "gqa_flash_roofline"]


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("configs", "nemotron-3-nano-30b-a3b.json")


def argv(seed, trace=0):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), "--bench", TINY]


# -- the configuration and its weights ------------------------------------------

def test_the_cut_is_written_into_the_configuration(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, "MEMEM*EME", 8, 16384)
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (52, 128, 131072)
    pattern = published["hybrid_override_pattern"]
    assert len(pattern) == 52 and pattern.startswith(cfg["hybrid_override_pattern"])
    assert [pattern.count(k) for k in "ME*"] == [23, 23, 6]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert "16 chips share each layer" in cfg["deployment"]["what"]
    # no width changed
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"], cfg["intermediate_size"]) == (
        2688, 1856, 3712, 1856)
    assert (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
            cfg["conv_kernel"], cfg["chunk_size"], cfg["expand"]) == (64, 64, 128, 8, 4, 128, 2)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (32, 2, 128, 6, 2.5)
    assert {"rotary", "A_log", "dt_bias", "D", "conv", "e_score_correction_bias", "param_dtype",
            "compute_dtype", "weights"} <= set(cfg["assumed"])
    entry = next(c for c in load("..", "BENCHMARK.json")["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


@pytest.mark.parametrize("layer,kind,count", [
    (1, "mamba2", 38_744_896), (2, "experts", 100_125_440), (5, "mamba2", 38_744_896),
    (6, "gqa", 23_399_040), (9, "experts", 100_125_440)])
def test_layer_kinds_and_parameter_counts(cfg, layer, kind, count):
    assert N.kind(cfg, layer) == kind
    assert sum(math.prod(s) for s in N.layer_leaf_shapes(cfg, layer).values()) == count
    assert N.param_count(cfg) == 666_963_456           # 10.67 GB at 16 B a parameter


def test_scan_leaves_are_drawn_as_the_family_initialises_them(cfg):
    make_leaf = N.leaf_maker(cfg)
    key = W.seed_key(2 ** 31 + 5)
    a_log = make_leaf(key, "layer_0/attention/A_log", W.name_tag("a"), (4096,))
    dt_bias = make_leaf(key, "layer_0/attention/dt_bias", W.name_tag("b"), (4096,))
    skip = make_leaf(key, "layer_0/attention/D", W.name_tag("d"), (4096,))
    a, dt = np.exp(np.asarray(a_log)), np.asarray(jax.nn.softplus(dt_bias))
    assert 1.0 <= a.min() < 1.5 and 15.0 < a.max() <= 16.0
    assert 1e-3 <= dt.min() < 2e-3 and 0.05 < dt.max() <= 0.1 + 1e-6
    assert abs(float(np.mean(skip)) - 1.0) < 0.01 and 0.01 < float(np.std(skip)) < 0.03
    # the state carries past a chunk of 128 (more than 5% of it) in a good share of heads
    carried = np.exp(-128 * a * dt) > 0.05
    assert 0.15 < carried.mean() < 0.4
    taps = np.asarray(make_leaf(key, "layer_0/attention/conv/kernel", W.name_tag("t"), (4, 6144)))
    assert -0.5 <= taps.min() < -0.49 and 0.49 < taps.max() <= 0.5
    # every other leaf is lib/weights.py's
    other = make_leaf(key, "layer_0/attention/in_proj/kernel", W.name_tag("c"), (8, 8))
    same = W.make_leaf(key, "layer_0/attention/in_proj/kernel", W.name_tag("c"), (8, 8))
    assert np.array_equal(np.asarray(other), np.asarray(same))


# -- FLOP and byte counts against hand counts --------------------------------------

def test_flop_counts_against_hand_counts(cfg):
    h = 2688
    scan = (5 * 64 * 128 + 3 * 64) * 64
    mamba = 2 * (h * 10304 + 4096 * h) + 2 * 4 * 6144 + scan
    assert F.ssd_flops_token(cfg) == scan
    assert F.mixer_forward_flops_token(cfg, "mamba2", 100.0, 0.0) == mamba
    gqa_dense = 2 * (h * 4096 + 2 * h * 256 + 4096 * h)
    assert F.mixer_forward_flops_token(cfg, "gqa", 100.0, 0.0) == (
        gqa_dense + 2 * 2 * 100 * 32 * 128)
    assert F.expert_flops_assignment(cfg) == 2 * 2 * h * 1856
    experts = 2 * (h * 128 + 2 * h * 3712) + 0.375 * 2 * 2 * h * 1856
    assert F.mixer_forward_flops_token(cfg, "experts", 100.0, 0.375) == experts
    assert [F.layers_of(cfg, k) for k in ("mamba2", "gqa", "experts")] == [4, 1, 4]
    forward = 4 * mamba + gqa_dense + 2 * 2 * 4096.5 * 32 * 128 + 4 * experts + 2 * h * 16384
    assert F.train_flops_token(cfg, 8192, 0.375) == pytest.approx(3 * forward)
    assert F.train_flops_token(cfg, 8192, 0.375) / 1e9 == pytest.approx(2.144, abs=0.001)


def test_the_scans_roofline_counts_what_the_recurrence_needs(cfg):
    tokens = 16384
    assert F.ssd_flops(cfg, tokens) == 3 * (5 * 64 * 128 + 3 * 64) * 64 * tokens
    # x and y, B and C in bf16, dt in float32, forward; backward reads x dY B C dt
    # and writes dx dB dC ddt
    steps = 4 * 64
    assert F.ssd_bytes(cfg, tokens) == tokens * (
        ((2 * 4096 + 2048) * 2 + steps) + ((2 * 4096 + 2048) * 2 + steps)
        + ((4096 + 2048) * 2 + steps))
    # bound by bytes on the v5e: 1.07 ms a layer a step against 0.66 of FLOPs
    p = peaks.peaks_for("TPU v5 lite")
    assert F.ssd_bytes(cfg, tokens) / p["hbm_bytes_s"] > F.ssd_flops(cfg, tokens) / p["bf16_flops"]


# -- the reference's layer-at-a-time backpropagation ---------------------------------

def test_reference_train_steps_are_grad_of_sum_ce_and_adam():
    from reference import nemotron_h as R

    tiny = load("tests", "data", "configs", "tiny-nemotron.json")
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 256), 0, 256))
    opt = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    out = R.train_steps(tiny, 5, [ids], opt, steps=1, rows_block=1)
    w = R.weights(tiny, 5)
    loss, grads = jax.value_and_grad(lambda w_: R.sum_ce(w_, jnp.asarray(ids), tiny))(w)
    tokens = 2 * 255
    assert out["loss"][0] == pytest.approx(float(loss) / tokens, rel=1e-6)
    assert set(out["grad_norm"]) == set(grads) == set(out["delta_norm"])
    for name, g in grads.items():
        want = float(jnp.sqrt(jnp.sum(jnp.square(g / tokens))))
        assert out["grad_norm"][name] == pytest.approx(want, rel=1e-4, abs=1e-12), name
    # one Adam step moves every leaf that has a gradient by lr a weight
    moved = out["delta_norm"]["layer_1/mlp/w_up"]
    assert moved == pytest.approx(3e-4 * math.sqrt(4 * 64 * 48), rel=0.05)
    assert out["delta_norm"]["layer_1/mlp/router_bias"] < 1e-7       # a buffer: no gradient


def test_the_references_attention_is_the_whole_causal_softmax():
    """Blocks of queries one after another (``lax.map``) against one softmax
    over the whole ``[S, S]`` matrix; K and V of 2 heads shared by 4."""
    from reference import nemotron_h as R

    tiny = load("tests", "data", "configs", "tiny-nemotron.json")
    d = N.dims(tiny)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    u = jax.random.normal(ks[0], (2, 64, 64))
    w = {f"{n}_proj/kernel": 0.2 * jax.random.normal(k, s) for (n, s), k in zip(
        (("q", (64, 64)), ("k", (64, 32)), ("v", (64, 32)), ("o", (64, 64))), ks[1:])}
    got = R.gqa_attention(u, w, d, lambda x: x, q_block=16)
    q = (u @ w["q_proj/kernel"]).reshape(2, 64, 4, 16)
    k, v = ((u @ w[f"{n}_proj/kernel"]).reshape(2, 64, 2, 16) for n in "kv")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) / 4.0
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((64, 64), bool)), scores, -1e30), -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(v, 2, axis=2)).reshape(2, 64, 64)
    assert float(jnp.max(jnp.abs(got - want @ w["o_proj/kernel"]))) < 1e-5


# -- the runner end to end, the control and the faults --------------------------------

def test_runner_end_to_end_at_a_tiny_size(capsys):
    result = bench_run.main(argv(53), allow_cpu=True)
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {} and "refused" in result
    assert result["correct"] and result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["checks"]) == {"loss3_gap", "grad1_gap", "delta3_gap",
                                     "compiles_in_window", "loss_not_finite"}
    info = result["info"]
    assert {"loss1_gap", "loss2_gap"} <= set(info)
    assert set(info["counters"]) == {"moe_held_assignments", "moe_held_load_max"}
    # 2 x 256 tokens, top 4 of 16 experts, 4 held, 4 expert layers: about 512 a layer
    assert 800 < info["counters"]["moe_held_assignments"] < 3000
    assert set(info["setup_parts"]) == {"start_and_devices", "import_program", "init_state",
                                        "weights", "proof_steps", "warm"}
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["correct"] is True


def test_control_and_faults_are_judged_by_the_cells_limits():
    """The float8 control and half a batch come out as not correct at the toy
    size too. The scan's state zeroed at every 128th token does not, here: a
    toy row has one border, and only the ``[H]``-sized decay leaves feel it
    (the toy cell's ``settled`` has the readings); it is planted and judged all
    the same, and the chip's cell is where it has to fail (PERF.md section 2)."""
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import control_nemotron_h as control

    _, _, ctx = bench_run.prepare(argv(41), allow_cpu=True)
    spec = ctx["spec"]
    verdicts = control.judge(spec["config"], spec["cell"], spec["traffic"], 41,
                             spec["cell"]["train"]["rows_per_chip"])
    assert set(verdicts) == {"control_fp8", "fault_half_batch", "fault_ssd_state_zeroed"}
    for name in ("control_fp8", "fault_half_batch"):
        assert verdicts[name]["correct"] is False, name
    limit = spec["cell"]["limits"]["grad1_gap"]
    assert verdicts["fault_half_batch"]["checks"]["grad1_gap"]["value"] > 10 * limit
    assert verdicts["control_fp8"]["checks"]["grad1_gap"]["value"] > 4 * limit
    zeroed = verdicts["fault_ssd_state_zeroed"]["checks"]
    assert zeroed["grad1_gap"]["value"] > 0 and zeroed["delta3_gap"]["value"] > 0


def test_the_planted_fault_is_the_recurrence_restarted_at_every_chunk():
    from reference.nemotron_h import ssd_recurrence

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (1, 384, 2, 8))
    b, c = (jax.random.normal(k, (1, 384, 1, 16)) for k in ks[1:3])
    dt = 0.02 * jnp.ones((1, 384, 2))
    a, d = jnp.array([-1.0, -3.0]), jnp.array([1.0, 0.5])
    faulty = ssd_recurrence(x, dt, a, b, c, d, zero_state_every=128)
    pieces = jnp.concatenate([ssd_recurrence(x[:, i:i + 128], dt[:, i:i + 128], a,
                                             b[:, i:i + 128], c[:, i:i + 128], d)
                              for i in range(0, 384, 128)], axis=1)
    assert float(jnp.max(jnp.abs(faulty - pieces))) < 1e-5
    sound = ssd_recurrence(x, dt, a, b, c, d)
    assert float(jnp.max(jnp.abs(sound[:, :128] - faulty[:, :128]))) == 0.0
    assert float(jnp.max(jnp.abs(sound - faulty))) > 0.05 * float(jnp.max(jnp.abs(sound)))


def test_the_runner_fails_at_once_where_the_program_lacks_the_scan(monkeypatch):
    """With the benchmark's files laid over the parent commit the new cell has
    to exit non-zero soon: the runner asks for ``ops/state_space.py`` before it
    builds anything (``models/hybrid_lm.py`` exists there)."""
    from runners import train_nemotron_h as runner

    monkeypatch.setitem(sys.modules, "pyspark_tf_gke_tpu.ops.state_space", None)
    built = []
    monkeypatch.setattr(runner, "build", lambda *a, **kw: built.append(a))
    with pytest.raises(ImportError):
        runner.run({"spec": {}, "seed": 1, "seconds": 1.0})
    assert not built


def test_the_cell_is_listed_where_it_reports():
    bench = load("..", "BENCHMARK.json")
    assert [w["name"] for w in bench["workloads"]][-1] == REAL_CELL
    entry = bench["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron-3-nano-30b-a3b", "train.seq8192", 1)
    assert bench_run.cell_metrics(bench, REAL_CELL, "end_to_end") == ["train_tok_s", "setup_s"]
    per_layer = set(bench_run.cell_metrics(bench, REAL_CELL, "per_layer"))
    assert per_layer == set(NEW_METRICS) | {
        "device_idle.train", "step_ms_p50.train", "flash_fwd_ms.train", "flash_dq_ms.train",
        "flash_dkv_ms.train", "input_wait_ms_p50.train", "dispatch_ms_p50.train",
        "fit_self_ms.train", "idle_host_share.train", "setup_trace_lower_s.train",
        "setup_compile_load_s.train"}
    # the new readers are the last entries and report in this cell alone
    assert [m["name"] for m in bench["per_layer"]][-6:] == NEW_METRICS
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == [REAL_CELL] and m["moves"] == "train_tok_s"
    for name in per_layer:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py")), name
    spec = bench_run.load_cell(bench, REAL_CELL)
    assert spec["cell"]["runner"] == "train_nemotron_h"
    assert spec["cell"]["train"]["rows_per_chip"] * spec["traffic"]["seq_len"] == 16384
    assert set(spec["cell"]["limits"]) == {"loss3_gap", "grad1_gap", "delta3_gap",
                                           "compiles_in_window", "loss_not_finite"}


# -- the readers -------------------------------------------------------------------------

def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@pytest.fixture(scope="module")
def made(cfg):
    """Two steps as a trace of the cell holds them, made by hand: a step of 600
    ms with eight ``ssd_fwd`` launches of 2 ms (remat runs the forward twice),
    four ``ssd_bwd`` of 5 ms and the GQA layer's flash launches (forward 10 ms
    twice, dQ 11, dK/dV 14), named as ``ops/pallas/scope.py`` names them."""
    ms = 1_000_000
    mods, ops = [], []
    for step in range(2):
        t0 = step * 700 * ms
        mods.append(("jit_train_step(123)", t0, 600 * ms))
        for i in range(8):
            name = "%ssd_fwd.{} = custom-call() tpu_custom_call" if i % 2 else \
                "%attention.ssd_fwd.{} = custom-call() tpu_custom_call"
            ops.append((name.format(i), t0 + i * 10 * ms, 2 * ms))
        for i in range(4):
            ops.append((f"%attention.ssd_bwd.{i} = custom-call() tpu_custom_call",
                        t0 + (100 + i * 10) * ms, 5 * ms))
        for i, (name, dur) in enumerate([
                ("%flash_fwd.3", 10), ("%attention._causal_attend.flash_fwd.5", 10),
                ("%attention._causal_attend.flash_dq.7", 11),
                ("%attention._causal_attend.flash_dkv.9", 14)]):
            ops.append((name + " = custom-call() tpu_custom_call", t0 + (150 + i * 20) * ms,
                        dur * ms))
        ops.append(("%fusion.1 = fusion()", t0 + 250 * ms, 300 * ms))
    trace = {"devices": [{"name": "/device:TPU:0", "modules": mods, "ops": ops}]}
    return {"trace": trace, "cfg": cfg, "traffic": {"seq_len": 8192},
            "cell": load("cells", REAL_CELL + ".json"),
            "peaks": peaks.peaks_for("TPU v5 lite"), "chips": 1, "rows": 2,
            "tokens_per_step": 16384, "steps": 33, "window_s": 19.8,
            "counters": {"moe_held_assignments": 12288.0, "moe_held_load_max": 768.0}}


def test_readers_on_a_trace_made_by_hand(made, cfg):
    assert read("ssd_ms.train", made) == pytest.approx(8 * 2 + 4 * 5)
    least = F.ssd_bytes(cfg, 16384) / 819e9
    assert read("ssd_roofline", made) == pytest.approx(100 * 4 * least / 0.036)
    assert 0 < read("ssd_roofline", made) < 100
    # 7 causal products of 2 x 32 x 8192^2 x 128 MACs: 3.85 TFLOP a step, bound by FLOPs
    assert F.gqa_flash_flops(cfg, 2, 8192) == 2 * 32 * 8192 ** 2 * 7 * 128
    assert F.gqa_flash_bytes(cfg, 2, 8192) == 2 * 8192 * 128 * 2 * (6 * 32 + 6 * 2)
    assert read("gqa_flash_roofline", made) == pytest.approx(
        100 * F.gqa_flash_flops(cfg, 2, 8192) / 197e12 / 0.045)
    assert 0 < read("gqa_flash_roofline", made) < 100
    per_token = F.train_flops_token(cfg, 8192, 12288.0 / (4 * 16384))
    assert read("mfu.train.nemotron-h", made) == pytest.approx(
        100 * per_token * 33 * 16384 / (19.8 * 197e12))
    assert 0 < read("mfu.train.nemotron-h", made) < 100
    assert read("moe_held_tokens_per_expert.train.nemotron-h", made) == 12288.0 / 32
    assert read("moe_held_load_max_over_mean.train.nemotron-h", made) == 2.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_find_nothing_without_what_this_pr_added(made, name):
    """A program without the kernels or the counters: ``None``, never 0 and
    never an error. The other hybrid decoder's trace has flash launches of its
    own (MLA's), so a program without a GQA layer is that trace less those."""
    _, kimi = S.load_extract(os.path.join(HERE, "data", "trace_kimi_train_host.json"))
    kimi = {"devices": [dict(dev, ops=[op for op in dev["ops"] if "flash_" not in op[0]])
                        for dev in kimi["devices"]]}
    assert kimi["devices"] and kimi["devices"][0]["modules"]
    bare = dict(made, trace=kimi, counters={})
    assert read(name, bare) is None
    assert read(name, dict(bare, trace=None)) is None
    assert read(name, dict(bare, trace={"devices": []})) is None
