"""What PR 27 added beside the GPT-2 cell's files: the Kimi-Linear
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
from lib import flops_kimi_linear as F
from lib import peaks
from lib import spans as S
from lib import weights as W
from lib import weights_kimi_linear as K

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny-kimi.json")
CELL = "tiny-kimi.train.tiny-seq128"
REAL_CELL = "kimi-linear-48b-a3b.train.seq8192"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("configs", "kimi-linear-48b-a3b.json")


def argv(seed, trace=0):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace), "--bench", TINY]


# -- the configuration and its weights ------------------------------------------

def test_the_cut_is_written_into_the_configuration(cfg):
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert "32 chips share each layer" in cfg["deployment"]["what"]
    # no width changed
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]) == (
        2304, 9216, 1024)
    assert (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_experts_per_token"]) == (512, 128, 64, 128, 8)
    assert cfg["linear_attn_config"]["head_dim"] == 128
    assert {"gate_rank", "bias", "A_log", "dt_bias", "e_score_correction_bias"} <= set(cfg["assumed"])
    entry = next(c for c in load("..", "BENCHMARK.json")["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


@pytest.mark.parametrize("layer,kinds,millions", [
    (1, ("kda", "dense"), 103.2), (2, ("kda", "experts"), 103.8),
    (4, ("mla", "experts"), 93.4), (5, ("kda", "experts"), 103.8)])
def test_layer_kinds_and_parameter_counts(cfg, layer, kinds, millions):
    assert (K.attention_kind(cfg, layer), K.ffn_kind(cfg, layer)) == kinds
    count = sum(math.prod(s) for s in K.layer_leaf_shapes(cfg, layer).values())
    assert count / 1e6 == pytest.approx(millions, abs=0.05)
    assert K.param_count(cfg) == 602_434_432           # 9.64 GB at 16 B a parameter


def test_decay_leaves_are_drawn_as_the_family_initialises_them():
    key = W.seed_key(2 ** 31 + 5)
    a_log = K.make_leaf(key, "layer_0/attention/A_log", W.name_tag("a"), (4096,))
    dt_bias = K.make_leaf(key, "layer_0/attention/dt_bias", W.name_tag("b"), (4096,))
    a, dt = np.exp(np.asarray(a_log)), np.asarray(jax.nn.softplus(dt_bias))
    assert 1.0 <= a.min() < 1.5 and 15.0 < a.max() <= 16.0
    assert 1e-3 <= dt.min() < 2e-3 and 0.05 < dt.max() <= 0.1 + 1e-6
    # the log-decay of a token: about [-1.6, -0.001], so the state outlives a chunk
    assert -1.7 < -(a.max() * dt.max()) and -(a.min() * dt.min()) > -0.0011
    # every other leaf is lib/weights.py's
    other = K.make_leaf(key, "layer_0/attention/q_proj/kernel", W.name_tag("c"), (8, 8))
    same = W.make_leaf(key, "layer_0/attention/q_proj/kernel", W.name_tag("c"), (8, 8))
    assert np.array_equal(np.asarray(other), np.asarray(same))


# -- FLOP and byte counts against hand counts --------------------------------------

def test_flop_counts_against_hand_counts(cfg):
    h, wide = 2304, 32 * 128
    kda_dense = 2 * (4 * h * wide + 2 * (h * 128 + 128 * wide) + h * 32)
    kda = kda_dense + 2 * 3 * 4 * wide + 6 * 128 * 128 * 32
    assert F.attention_forward_flops_token(cfg, "kda", 100.0) == kda
    mla_dense = 2 * (h * 32 * 192 + h * 576 + 512 * 32 * 256 + 32 * 128 * h)
    assert F.attention_forward_flops_token(cfg, "mla", 100.0) == (
        mla_dense + 2 * 100 * 32 * 192 + 2 * 100 * 32 * 128)
    assert F.ffn_forward_flops_token(cfg, "dense", 0.0) == 2 * 3 * h * 9216
    assert F.expert_flops_assignment(cfg) == 2 * 3 * h * 1024
    assert F.ffn_forward_flops_token(cfg, "experts", 0.25) == (
        2 * (h * 256 + 3 * h * 1024) + 0.25 * 2 * 3 * h * 1024)
    assert F.expert_layers(cfg) == 4
    forward = (4 * kda + mla_dense + 2 * 4096.5 * 32 * 320 + 2 * 3 * h * 9216
               + 4 * F.ffn_forward_flops_token(cfg, "experts", 0.25) + 2 * h * 20480)
    assert F.train_flops_token(cfg, 8192, 0.25) == pytest.approx(3 * forward)
    assert F.train_flops_token(cfg, 8192, 0.25) / 1e9 == pytest.approx(2.304, abs=0.001)


def test_kernel_rooflines_count_what_the_algorithm_needs(cfg):
    tokens = 16384
    assert F.kda_flops(cfg, tokens) == 3 * 6 * 128 * 128 * 32 * tokens
    # q k v o in bf16, g and beta in float32, forward; backward reads q k v dO g
    # beta and writes dq dk dv dg dbeta
    gates = 4 * (4096 + 32)
    assert F.kda_bytes(cfg, tokens) == tokens * (
        (4 * 4096 * 2 + gates) + (4 * 4096 * 2 + gates) + (3 * 4096 * 2 + gates))
    assert F.mla_flash_flops(cfg, 2, 8192) == 2 * 32 * 8192 * 8192 * (
        (192 + 128) + (192 + 128 + 128 + 192 + 192))
    assert F.mla_flash_bytes(cfg, 2, 8192) == 2 * 32 * 8192 * 2 * (
        (192 + 192 + 128 + 128) + (192 + 192 + 128 + 128 + 128) + (192 + 192 + 128))


# -- the reference's layer-at-a-time backpropagation ---------------------------------

def test_reference_train_steps_are_grad_of_sum_ce_and_adam():
    from reference import kimi_linear as R

    tiny = load("tests", "data", "configs", "tiny-kimi.json")
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 128), 0, 256))
    opt = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    out = R.train_steps(tiny, 5, [ids], opt, steps=1, rows_block=1)
    w = R.weights(tiny, 5)
    loss, grads = jax.value_and_grad(lambda w_: R.sum_ce(w_, jnp.asarray(ids), tiny))(w)
    tokens = 2 * 127
    assert out["loss"][0] == pytest.approx(float(loss) / tokens, rel=1e-6)
    assert set(out["grad_norm"]) == set(grads) == set(out["delta_norm"])
    for name, g in grads.items():
        want = float(jnp.sqrt(jnp.sum(jnp.square(g / tokens))))
        assert out["grad_norm"][name] == pytest.approx(want, rel=1e-4, abs=1e-12), name
    # one Adam step moves every leaf that has a gradient by lr a weight
    moved = out["delta_norm"]["layer_1/mlp/w_gate"]
    assert moved == pytest.approx(3e-4 * math.sqrt(4 * 64 * 48), rel=0.05)
    assert out["delta_norm"]["layer_1/mlp/router_bias"] < 1e-7       # a buffer: no gradient


# -- the runner end to end, the control and the faults --------------------------------

def test_runner_end_to_end_at_a_tiny_size(capsys):
    result = bench_run.main(argv(51), allow_cpu=True)
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {} and "refused" in result
    assert result["correct"] and result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["checks"]) == {"loss3_gap", "grad1_gap", "delta3_gap",
                                     "compiles_in_window", "loss_not_finite"}
    info = result["info"]
    assert {"loss1_gap", "loss2_gap"} <= set(info)
    assert set(info["counters"]) == {"moe_held_assignments", "moe_held_load_max"}
    # 2 x 128 tokens, top 4 of 16 experts, 4 held, 4 expert layers: about 256 a layer
    assert 600 < info["counters"]["moe_held_assignments"] < 1500
    assert set(info["setup_parts"]) == {"start_and_devices", "import_program", "init_state",
                                        "weights", "proof_steps", "warm"}
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["correct"] is True


def test_control_and_faults_come_out_as_not_correct():
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import control_kimi_linear as control

    _, _, ctx = bench_run.prepare(argv(41), allow_cpu=True)
    spec = ctx["spec"]
    verdicts = control.judge(spec["config"], spec["cell"], spec["traffic"], 41,
                             spec["cell"]["train"]["rows_per_chip"])
    assert set(verdicts) == {"control_fp8", "fault_half_batch", "fault_kda_state_zeroed"}
    for name, v in verdicts.items():
        assert v["correct"] is False, name
    limit = spec["cell"]["limits"]["grad1_gap"]
    assert verdicts["fault_kda_state_zeroed"]["checks"]["grad1_gap"]["value"] > 10 * limit
    assert verdicts["fault_half_batch"]["checks"]["grad1_gap"]["value"] > 10 * limit


def test_a_program_whose_kda_loses_its_state_between_chunks_is_not_correct(monkeypatch):
    from pyspark_tf_gke_tpu.models import hybrid_lm
    from pyspark_tf_gke_tpu.ops.linear_attention import CHUNK, kda

    def forgetful(q, k, v, g, beta, **kw):
        return jnp.concatenate(
            [kda(*(x[:, i:i + CHUNK] for x in (q, k, v, g, beta)), **kw)
             for i in range(0, q.shape[1], CHUNK)], axis=1)

    monkeypatch.setattr(hybrid_lm, "kda", forgetful)
    result = bench_run.main(argv(52), allow_cpu=True)
    assert result["correct"] is False
    assert result["checks"]["grad1_gap"]["value"] > 0.1


def test_the_cell_is_listed_where_it_reports():
    bench = load("..", "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kimi-linear-48b-a3b", "train.seq8192", 1)
    assert bench_run.cell_metrics(bench, REAL_CELL, "end_to_end") == ["train_tok_s", "setup_s"]
    per_layer = set(bench_run.cell_metrics(bench, REAL_CELL, "per_layer"))
    assert {"mfu.train.kimi-linear", "kda_ms.train", "kda_roofline", "mla_flash_roofline",
            "moe_held_tokens_per_expert.train", "moe_held_load_max_over_mean.train",
            "step_ms_p50.train", "device_idle.train", "flash_fwd_ms.train"} <= per_layer
    assert not {"mfu.train", "flash_attention_roofline"} & per_layer     # GPT-2 keys
    for name in per_layer:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py")), name
    spec = bench_run.load_cell(bench, REAL_CELL)
    assert spec["cell"]["runner"] == "train_kimi_linear"
    assert spec["cell"]["train"]["rows_per_chip"] * spec["traffic"]["seq_len"] == 16384


# -- the readers on a recorded extract --------------------------------------------------

def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@pytest.fixture(scope="module")
def recorded(cfg):
    """Two traced steps of the cell on a TPU v5e (my chip run, PR 27, seed
    27001; ``lib/spans.py::save_extract``)."""
    host, trace = S.load_extract(os.path.join(HERE, "data", "trace_kimi_train_host.json"))
    return {"trace": trace, "host": host, "cfg": cfg, "traffic": {"seq_len": 8192},
            "cell": load("cells", REAL_CELL + ".json"),
            "peaks": peaks.peaks_for("TPU v5 lite"), "chips": 1, "rows": 2,
            "tokens_per_step": 16384, "steps": 17, "window_s": 19.0,
            "counters": {"moe_held_assignments": 15800.0, "moe_held_load_max": 890.0}}


@pytest.mark.parametrize("name,want", [
    ("kda_ms.train", 274.599), ("kda_roofline", 4.0694), ("mla_flash_roofline", 38.651),
    ("flash_fwd_ms.train", 26.551), ("flash_dq_ms.train", 17.419),
    ("flash_dkv_ms.train", 21.011), ("step_ms_p50.train", 1114.58),
    ("mfu.train.kimi-linear", 17.135), ("moe_held_tokens_per_expert.train", 493.75),
    ("moe_held_load_max_over_mean.train", 1.80253)])
def test_readers_on_the_recorded_extract(recorded, name, want):
    assert read(name, recorded) == pytest.approx(want, rel=1e-4)


def test_kernel_times_account_for_the_kernels_in_the_extract(recorded):
    """``kda_ms.train`` is eight forward launches (remat runs it twice a
    layer) and four backward ones a step; with the three flash readers it is
    all the program's own kernel time in the step."""
    dev = recorded["trace"]["devices"][0]
    ours = [e for e in dev["ops"] if "tpu_custom_call" in e[0] and "ragged-dot" not in e[0]]
    assert sum("kda_fwd" in e[0] for e in ours) == 16
    assert sum("kda_bwd" in e[0] for e in ours) == 8
    total = sum(e[2] for e in ours) / 1e6 / 2
    parts = sum(read(n, recorded) for n in ("kda_ms.train", "flash_fwd_ms.train",
                                            "flash_dq_ms.train", "flash_dkv_ms.train"))
    assert parts == pytest.approx(total, rel=1e-6)
    for name in ("kda_roofline", "mla_flash_roofline", "mfu.train.kimi-linear"):
        assert 0 < read(name, recorded) < 100


@pytest.mark.parametrize("name", ["kda_ms.train", "kda_roofline", "mla_flash_roofline",
                                  "mfu.train.kimi-linear", "moe_held_tokens_per_expert.train",
                                  "moe_held_load_max_over_mean.train"])
def test_readers_find_nothing_without_what_this_pr_added(recorded, name):
    """A program without the kernels or the counters (the parent): ``None``,
    never 0 and never an error."""
    _, gpt2 = S.load_extract(os.path.join(HERE, "data", "trace_train_host.json"))
    bare = dict(recorded, trace=gpt2, counters={})
    if name != "mla_flash_roofline":          # the flash kernels are older than this PR
        assert read(name, bare) is None
    assert read(name, dict(bare, trace=None)) is None
    assert read(name, dict(bare, trace={"devices": []})) is None
