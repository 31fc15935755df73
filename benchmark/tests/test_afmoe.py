"""What PR 35 added beside the other cells' files: the Trinity-Mini
configuration, weights, reference, FLOP counts, runner, control and readers.
The runner goes end to end at a tiny size on the CPU (``allow_cpu``: what it
prints names the platform and carries no metric)."""

import importlib
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
from lib import flops_afmoe as F
from lib import peaks
from lib import spans as S
from lib import weights as W
from lib import weights_afmoe as A

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny-afmoe.json")
CELL = "tiny-afmoe.train.tiny-seq256"
REAL_CELL = "trinity-mini.train.seq8192"
NEW_METRICS = ["mfu.train.afmoe", "window_flash_ms.train", "window_flash_roofline",
               "gqa_flash_roofline.afmoe", "moe_held_tokens_per_expert.train.afmoe",
               "moe_held_load_max_over_mean.train.afmoe"]
OPT = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("configs", "trinity-mini.json")


@pytest.fixture(scope="module")
def tiny():
    return load("tests", "data", "configs", "tiny-afmoe.json")


def argv(seed, trace=0):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), "--bench", TINY]


# -- the configuration and its weights ------------------------------------------

def test_the_cut_is_written_into_the_configuration(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types",
                              "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 1, 16, 25024)
    published = cfg["published"]
    assert set(published) == set(cfg["reduced"])
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == (32, 2, 128, 200192)
    types = published["layer_types"]
    assert types == ["sliding_attention", "sliding_attention", "sliding_attention",
                     "full_attention"] * 8
    assert cfg["layer_types"] == types[1:6]               # published layers 1-5, 0-based
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert "8 chips share each layer" in cfg["deployment"]["what"]
    assert "4 window : 1 global against 24 : 8" in cfg["deployment"]["layers_kept"]
    # no width changed
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]) == (
        2048, 6144, 1024, 32, 4, 128)
    assert (cfg["num_experts_per_tok"], cfg["num_shared_experts"], cfg["route_scale"],
            cfg["sliding_window"], cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        8, 1, 2.826, 2048, 10000, 1e-5)
    assert {"norm_places", "qk_norm", "rotary", "window", "output_gate", "embedding", "router",
            "load_balance_coeff", "param_dtype", "compute_dtype", "weights"} <= set(cfg["assumed"])
    entry = next(c for c in load("..", "BENCHMARK.json")["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_every_published_number_is_in_the_file_under_its_key(cfg):
    """The catalog row's ``config`` (``/opt/skills/guides/model-configs``),
    where it can be read: every number under the same key, but the reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


@pytest.mark.parametrize("layer,kinds,count", [
    (0, ("sliding", "dense"), 65_020_160), (1, ("sliding", "experts"), 134_488_448),
    (2, ("full", "experts"), 134_488_448), (4, ("sliding", "experts"), 134_488_448)])
def test_layer_kinds_and_parameter_counts(cfg, layer, kinds, count):
    assert A.kinds(cfg, layer) == kinds
    assert sum(math.prod(s) for s in A.layer_leaf_shapes(cfg, layer).values()) == count
    assert sum(math.prod(s) for s in A.attention_leaf_shapes(cfg).values()) == 27_263_232
    assert A.param_count(cfg) == 705_474_304            # 11.29 GB at 16 B a parameter
    assert "705,474,304" in cfg["why"]
    assert A.dims(cfg)["embed_scale"] == math.sqrt(2048)


def test_norm_scales_are_drawn_about_one_and_the_routers_bias_about_nought(tiny):
    """Every leaf is ``lib.weights.make_leaves``' by its name: the six norms a
    layer end in ``scale`` (1 + noise), the router's bias does not (noise)."""
    made = W.make_leaves(W.seed_key(2 ** 31 + 5), A.leaf_shapes(tiny))
    scales = [n for n in made if n.endswith("scale")]
    assert len(scales) == 4 * 6 + 1
    for name in scales:
        assert abs(float(jnp.mean(made[name])) - 1.0) < 0.03, name
    assert abs(float(jnp.mean(made["layer_1/mlp/router_bias"]))) < 0.02
    assert 0.01 < float(jnp.std(made["layer_1/mlp/router_bias"])) < 0.03


# -- FLOP and byte counts against hand counts --------------------------------------

def test_flop_counts_against_hand_counts(cfg):
    h, s = 2048, 8192
    proj = 2 * (3 * h * 4096 + 2 * h * 512)
    full_context, window_context = (s + 1) / 2, (2048 * 2049 // 2 + 6144 * 2048) / s
    assert F.attention_forward_flops_token(cfg, "full", s) == proj + 4 * full_context * 32 * 128
    assert F.attention_forward_flops_token(cfg, "sliding", s) == pytest.approx(
        proj + 4 * window_context * 32 * 128)
    assert F.expert_flops_assignment(cfg) == 2 * 3 * h * 1024
    assert F.ffn_forward_flops_token(cfg, "dense", 0.5) == 2 * 3 * h * 6144
    experts = 2 * (h * 128 + 3 * h * 1024) + 1.0 * 2 * 3 * h * 1024
    assert F.ffn_forward_flops_token(cfg, "experts", 1.0) == experts
    assert [F.layers_of(cfg, attention="sliding"), F.layers_of(cfg, attention="full"),
            F.layers_of(cfg, ffn="dense"), F.layers_of(cfg, ffn="experts"),
            F.layers_of(cfg, attention="full", ffn="experts")] == [4, 1, 1, 4, 1]
    forward = 5 * proj + 4 * (4 * window_context + full_context) * 32 * 128 \
        + 2 * 3 * h * 6144 + 4 * experts + 2 * h * 25024
    assert F.train_flops_token(cfg, s, 1.0) == pytest.approx(3 * forward)
    assert F.train_flops_token(cfg, s, 1.0) / 1e9 == pytest.approx(2.214, abs=0.001)


def test_the_windows_visible_scores_are_what_the_kernels_schedule_keeps(cfg):
    """``lib/flops_afmoe.py`` counts a window layer's FLOPs on the scores a row
    sees; the program's schedule computes more and throws the rest away
    (``_Schedule.counts``): computed less thrown away is that count, in all
    three kernels, at the cell's shape."""
    flash = importlib.import_module("pyspark_tf_gke_tpu.ops.pallas.flash_attention")
    s, window = 8192, cfg["sliding_window"]
    block = flash._pick_seq_block(s, flash.DEFAULT_BLOCK_Q)
    assert F.visible_scores(s, window) == 14_681_088
    assert F.visible_scores(s) == s * (s + 1) // 2 == F.visible_scores(s, s)
    for walks_rows in (False, True):
        computed, _, thrown = flash._schedule(s, block, block, True, walks_rows, window).counts()
        assert computed - thrown == F.visible_scores(s, window)
        computed, _, thrown = flash._schedule(s, block, block, True, walks_rows).counts()
        assert computed - thrown == F.visible_scores(s)
    # rows x heads x visible scores x 7 products of 2 x 128: 1.68 TFLOP a window layer
    assert F.flash_flops(cfg, 2, s, window) == 2 * 32 * 14_681_088 * 2 * 7 * 128
    assert F.flash_flops(cfg, 2, s, window) / 1e12 == pytest.approx(1.684, abs=0.001)
    assert F.flash_flops(cfg, 2, s) == 2 * 32 * (s * (s + 1) // 2) * 2 * 7 * 128
    assert F.flash_bytes(cfg, 2, s) == 2 * s * 128 * 2 * (6 * 32 + 6 * 4)
    p = peaks.peaks_for("TPU v5 lite")          # bound by FLOPs with and without the window
    assert F.flash_flops(cfg, 2, s, window) / p["bf16_flops"] > F.flash_bytes(cfg, 2, s) / p["hbm_bytes_s"]


# -- the reference -------------------------------------------------------------------

def test_reference_train_steps_are_grad_of_sum_ce_and_adam(tiny):
    from reference import afmoe as R

    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 256), 0, 256))
    out = R.train_steps(tiny, 5, [ids], OPT, steps=1, rows_block=1)
    w = R.weights(tiny, 5)
    loss, grads = jax.value_and_grad(lambda w_: R.sum_ce(w_, jnp.asarray(ids), tiny))(w)
    tokens = 2 * 255
    assert out["loss"][0] == pytest.approx(float(loss) / tokens, rel=1e-6)
    assert set(out["grad_norm"]) == set(grads) == set(out["delta_norm"])
    for name, g in grads.items():
        want = float(jnp.sqrt(jnp.sum(jnp.square(g / tokens))))
        assert out["grad_norm"][name] == pytest.approx(want, rel=1e-4, abs=1e-12), name
    # one Adam step moves every leaf that has a gradient by lr a weight
    assert out["delta_norm"]["layer_1/mlp/w_up"] == pytest.approx(
        3e-4 * math.sqrt(4 * 64 * 32), rel=0.05)
    assert out["delta_norm"]["layer_1/mlp/router_bias"] < 1e-7       # a buffer: no gradient


@pytest.mark.parametrize("kind,window", [("full", None), ("sliding", 24)])
def test_the_references_attention_is_the_whole_softmax_inside_its_window(tiny, kind, window):
    """Blocks of queries one after another (``lax.map``) against one softmax
    over the whole ``[S, S]`` matrix; K and V of 2 heads shared by 4; the
    rotation by the published formula (``rotate_half``), written out here."""
    from reference import afmoe as R
    from reference.kimi_linear import rms_norm

    d = dict(A.dims(tiny), window=24)
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    x = jax.random.normal(ks[0], (2, 64, 64))
    shapes = A.attention_leaf_shapes(tiny)
    w = {n: (1.0 if n.endswith("scale") else 0.0) + 0.2 * jax.random.normal(k, shapes[n])
         for n, k in zip(shapes, ks[1:])}
    got = R.attention(x, w, d, kind, lambda m: m, q_block=16)
    q = rms_norm((x @ w["q_proj/kernel"]).reshape(2, 64, 4, 16), w["q_norm/scale"], d["eps"])
    k = rms_norm((x @ w["k_proj/kernel"]).reshape(2, 64, 2, 16), w["k_norm/scale"], d["eps"])
    v = (x @ w["v_proj/kernel"]).reshape(2, 64, 2, 16)
    if kind == "sliding":
        inv = 1.0 / d["theta"] ** (np.arange(0, 16, 2) / 16)
        angles = np.arange(64)[:, None] * np.concatenate([inv, inv])[None]     # [S, 16]
        cos, sin = (jnp.asarray(f(angles), jnp.float32)[None, :, None, :] for f in (np.cos, np.sin))
        rotate_half = lambda m: jnp.concatenate([-m[..., 8:], m[..., :8]], axis=-1)
        q, k = q * cos + rotate_half(q) * sin, k * cos + rotate_half(k) * sin
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) / 4.0
    behind = np.arange(64)[:, None] - np.arange(64)[None, :]
    seen = (behind >= 0) & (behind < (window or 64))
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(v, 2, axis=2)).reshape(2, 64, 64)
    want = (o * jax.nn.sigmoid(x @ w["gate_proj/kernel"])) @ w["o_proj/kernel"]
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))


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
    # 2 x 256 tokens, top 4 of 16 experts, 4 held, 3 expert layers: about 512 a layer
    assert 700 < info["counters"]["moe_held_assignments"] < 2400
    assert set(info["setup_parts"]) == {"start_and_devices", "import_program", "init_state",
                                        "weights", "proof_steps", "warm"}
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["correct"] is True


def test_control_and_faults_are_judged_by_the_cells_limits():
    """The float8 control and the four planted faults all come out as not
    correct at the toy size too, each by the first gradient's norms
    (``grad1_gap``), which no schedule of the rate reaches."""
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import control_afmoe as control

    _, _, ctx = bench_run.prepare(argv(41), allow_cpu=True)
    spec = ctx["spec"]
    verdicts = control.judge(spec["config"], spec["cell"], spec["traffic"], 41,
                             spec["cell"]["train"]["rows_per_chip"])
    assert set(verdicts) == {"control_fp8", "fault_half_batch", "fault_window_ignored",
                             "fault_rotation_off", "fault_gate_off"}
    for name, verdict in verdicts.items():
        assert verdict["correct"] is False, name
    limit = spec["cell"]["limits"]["grad1_gap"]
    for name in ("fault_window_ignored", "fault_rotation_off", "fault_gate_off"):
        assert verdicts[name]["checks"]["grad1_gap"]["value"] > 3 * limit, name
    assert verdicts["fault_half_batch"]["checks"]["grad1_gap"]["value"] > 10 * limit


def test_the_runner_fails_at_once_where_the_program_lacks_the_family(monkeypatch):
    """With the benchmark's files laid over the parent commit the new cell has
    to exit non-zero soon: the runner asks ``models/hybrid_lm.py`` for the
    family's attention before it builds anything."""
    from pyspark_tf_gke_tpu.models import hybrid_lm
    from runners import train_afmoe as runner

    monkeypatch.delattr(hybrid_lm, "GatedAttention")
    built = []
    monkeypatch.setattr(runner, "build", lambda *a, **kw: built.append(a))
    with pytest.raises(ImportError):
        runner.run({"spec": {}, "seed": 1, "seconds": 1.0})
    assert not built


def test_the_cell_is_listed_where_it_reports():
    bench = load("..", "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "trinity-mini", "train.seq8192", 1)
    config = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert bench_run.cell_metrics(bench, REAL_CELL, "end_to_end") == ["train_tok_s", "setup_s"]
    per_layer = set(bench_run.cell_metrics(bench, REAL_CELL, "per_layer"))
    assert per_layer == set(NEW_METRICS) | {
        "device_idle.train", "step_ms_p50.train", "flash_fwd_ms.train", "flash_dq_ms.train",
        "flash_dkv_ms.train", "input_wait_ms_p50.train", "dispatch_ms_p50.train",
        "fit_self_ms.train", "idle_host_share.train", "setup_trace_lower_s.train",
        "setup_compile_load_s.train"}
    # the new readers report in this cell alone (a later PR's entries come after them)
    new = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == NEW_METRICS
    for m in new:
        assert m["workloads"] == [REAL_CELL] and m["moves"] == "train_tok_s"
    for name in per_layer:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py")), name
    spec = bench_run.load_cell(bench, REAL_CELL)
    assert spec["cell"]["runner"] == "train_afmoe"
    assert spec["cell"]["train"]["rows_per_chip"] * spec["traffic"]["seq_len"] == 16384
    assert spec["config"]["vocab_size"] % spec["cell"]["train"]["vocab_chunks"] == 0
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
    ms with the four window layers' launches (forward 4 ms twice with remat, dQ
    5, dK/dV 6) and the global layer's (forward 10 ms twice, dQ 11, dK/dV 14),
    named as ``ops/pallas/scope.py`` names them."""
    ms = 1_000_000
    mods, ops = [], []
    for step in range(2):
        t0 = step * 700 * ms
        mods.append(("jit_train_step(123)", t0, 600 * ms))
        launches = [("%flash_fwd.3", 10), ("%attention._causal_attend.flash_fwd.5", 10),
                    ("%attention._causal_attend.flash_dq.7", 11),
                    ("%attention._causal_attend.flash_dkv.9", 14)]
        for layer in range(4):
            launches += [(f"%window_flash_fwd.{layer}", 4),
                         (f"%attention._causal_attend.window_flash_fwd.{layer}", 4),
                         (f"%attention._causal_attend.window_flash_dq.{layer}", 5),
                         (f"%attention._causal_attend.window_flash_dkv.{layer}", 6)]
        for i, (name, dur) in enumerate(launches):
            ops.append((name + " = custom-call() tpu_custom_call", t0 + i * 20 * ms, dur * ms))
        ops.append(("%fusion.1 = fusion()", t0 + 450 * ms, 100 * ms))
    trace = {"devices": [{"name": "/device:TPU:0", "modules": mods, "ops": ops}]}
    return {"trace": trace, "cfg": cfg, "traffic": {"seq_len": 8192},
            "cell": load("cells", REAL_CELL + ".json"),
            "peaks": peaks.peaks_for("TPU v5 lite"), "chips": 1, "rows": 2,
            "tokens_per_step": 16384, "steps": 30, "window_s": 19.8,
            "counters": {"moe_held_assignments": 65536.0, "moe_held_load_max": 1536.0}}


def test_readers_on_a_trace_made_by_hand(made, cfg):
    assert read("window_flash_ms.train", made) == pytest.approx(4 * (4 + 4 + 5 + 6))
    assert read("window_flash_roofline", made) == pytest.approx(
        100 * 4 * F.flash_flops(cfg, 2, 8192, 2048) / 197e12 / 0.076)
    assert 0 < read("window_flash_roofline", made) < 100
    # the global layer's launches alone: the windowed ones are left out by name
    assert read("gqa_flash_roofline.afmoe", made) == pytest.approx(
        100 * F.flash_flops(cfg, 2, 8192) / 197e12 / 0.045)
    assert 0 < read("gqa_flash_roofline.afmoe", made) < 100
    # the accepted readers match the windowed launches too: all five layers' forwards
    assert read("flash_fwd_ms.train", made) == pytest.approx(2 * 10 + 4 * 2 * 4)
    assert read("flash_dq_ms.train", made) == pytest.approx(11 + 4 * 5)
    assert read("flash_dkv_ms.train", made) == pytest.approx(14 + 4 * 6)
    per_token = F.train_flops_token(cfg, 8192, 65536.0 / (4 * 16384))
    assert read("mfu.train.afmoe", made) == pytest.approx(
        100 * per_token * 30 * 16384 / (19.8 * 197e12))
    assert 0 < read("mfu.train.afmoe", made) < 100
    assert read("moe_held_tokens_per_expert.train.afmoe", made) == 65536.0 / 64
    assert read("moe_held_load_max_over_mean.train.afmoe", made) == 1.5


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_find_nothing_without_what_this_pr_added(made, name):
    """A program without the kernels or the counters: ``None``, never 0 and
    never an error. The other hybrid decoder's trace has flash launches of its
    own (MLA's), so a program without this family's layers is that trace less
    those."""
    _, kimi = S.load_extract(os.path.join(HERE, "data", "trace_kimi_train_host.json"))
    kimi = {"devices": [dict(dev, ops=[op for op in dev["ops"] if "flash_" not in op[0]])
                        for dev in kimi["devices"]]}
    assert kimi["devices"] and kimi["devices"][0]["modules"]
    bare = dict(made, trace=kimi, counters={})
    assert read(name, bare) is None
    assert read(name, dict(bare, trace=None)) is None
    assert read(name, dict(bare, trace={"devices": []})) is None


def test_a_trace_of_causal_launches_alone_has_no_windowed_kernel_to_read(made):
    """The parent's program at this cell's shapes would launch five causal
    layers: the windowed readers find nothing there, and do not raise."""
    causal = {"devices": [dict(dev, ops=[op for op in dev["ops"] if "window_" not in op[0]])
                          for dev in made["trace"]["devices"]]}
    ctx = dict(made, trace=causal)
    assert read("window_flash_ms.train", ctx) is None
    assert read("window_flash_roofline", ctx) is None
    assert read("gqa_flash_roofline.afmoe", ctx) is not None
