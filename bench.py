"""North-star benchmark: flagship (CNN-B1) train step on real TPU.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Metric (per BASELINE.json): images/sec/chip for the reference's flagship
training workload — the 43.4M-param B1 CNN regressor
(``/root/reference/workloads/raw-tf/train_tf_ps.py:346-378``), batch 32,
256×320×3, trained with Adam/MSE. Step time (ms) and MFU (model FLOPs
utilization: analytic XLA-cost-model FLOPs per step ÷ chip peak bf16
FLOPs) are included in the JSON as extra fields.

``vs_baseline`` compares against the measured throughput of the
reference's own TensorFlow implementation of the same workload on CPU,
extrapolated to the reference baseline cluster's 16 vCPUs
(``tools/reference_baseline.json`` — the reference publishes no numbers,
and its baseline "TF pool" is CPU nodes; see tools/measure_reference_baseline.py).

All diagnostics go to stderr; stdout carries exactly the one JSON line.

Secondary workloads (BASELINE configs 4/5): ``python bench.py resnet50``
and ``python bench.py bert`` measure examples/sec/chip for ResNet-50
classification (batch 64, 224²) and BERT-base sequence classification
(batch 32, S=128); same JSON shape, ``vs_baseline`` null (the reference
has no such workloads to compare against). ``python bench.py vit`` is
ViT-Base over 16x16 patches (same batch as resnet50). ``python bench.py
io`` measures the native input pipeline (TFRecord shards → host
batches);
``python bench.py generate [--kv-heads N] [--int8] [--int8-kv] [--beams K]``
measures KV-cache decode tokens/sec on the serving path (GQA, weight-
only int8, int8 KV cache, beam search); ``python bench.py spec
[--gamma N]`` measures speculative decoding (lower + upper bounds).
``python bench.py cb`` compares continuous batching (slot engine,
train/continuous.py) against whole-batch serving on one request set
(``--spec``: the in-engine speculative-decoding A/B on a decode-heavy
mix — trained draft/target pair, token parity asserted).
``python bench.py all`` runs every workload of the matrix in turn,
appending each success to tools/bench_history.jsonl.

One process per chip: this entry point is a thin parent that imports no
jax and runs the measurement ONCE in a ``--run`` child (which owns the
chip and exits, releasing it). A device workload needs a TPU: off the
chip the child exits non-zero and the parent prints an error line with
no value — it neither falls back to the CPU nor replays an old trail
entry. ``--smoke`` is the explicit CPU plumbing mode (tiny shapes on the
8-device fake slice; its output says ``cpu``).
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

HISTORY_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "tools", "bench_history.jsonl")

# flagship metric name, shared by the live result and the outage error
# JSON so BENCH_rN artifacts key identically either way
CNN_METRIC = "cnn_b1_train_images_per_sec_per_chip"

RUN_TIMEOUT_S = 2400

# Peak dense bf16 FLOP/s per chip by device kind (public spec sheets;
# the scaling-book numbers). Used for the MFU denominator.
PEAK_BF16_FLOPS = {
    "v5 lite": 1.97e14,  # TPU v5e
    "v5e": 1.97e14,
    "v5p": 4.59e14,
    "v4": 2.75e14,
    "v6": 9.18e14,  # Trillium / v6e
    "v3": 1.23e14,
    "v2": 0.45e14,
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peak_flops_for(device_kind: str):
    kind = device_kind.lower()
    for key, peak in PEAK_BF16_FLOPS.items():
        if key in kind:
            return peak
    return None


def step_flops(trainer, state, batch):
    """Analytic FLOPs for one compiled train step, from XLA's cost model
    (computed from the optimized HLO without executing — lowering does
    not donate or consume ``state``). Returns None if the backend does
    not expose a cost analysis."""
    try:
        if trainer._train_step is None:
            trainer._build_steps()
        with trainer.mesh:
            compiled = trainer._train_step.lower(state, batch).compile()
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        flops = float(analysis.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception as exc:  # pragma: no cover - backend-dependent
        log(f"cost_analysis unavailable: {exc!r}")
        return None


def measure(trainer, state, batch, steps: int):
    """Shared warmup+measure protocol. All `steps` train steps run inside
    ONE dispatch (on-device lax.scan), so per-dispatch host latency is
    paid once and not per step. Full metric readback (np.asarray) is the
    completion barrier: JAX returns before the device finishes.
    Returns (state, per-step losses, elapsed seconds)."""
    log("compiling + warmup...")
    state, metrics = trainer.multi_step(state, batch, steps)
    np.asarray(metrics["loss"])

    log(f"measuring {steps} steps (single-dispatch scan)...")
    t0 = time.perf_counter()
    state, metrics = trainer.multi_step(state, batch, steps)
    losses = np.asarray(metrics["loss"])
    dt = time.perf_counter() - t0
    return state, losses, dt


def _throughput_pass(trainer, state, make_tbatch, tsteps: int, n_chips: int,
                     device_kind: str, actual_batch: int, unit: str) -> dict:
    """Shared disclosed-secondary measurement at a larger per-chip batch
    (the headline stays the BASELINE config's batch). ``make_tbatch`` is
    a thunk so the big-batch ALLOCATION is inside the guard too. Returns
    the max_throughput_* fields; {} on failure (OOM safety on small
    chips — the already-measured headline must survive)."""
    try:
        tbatch = make_tbatch()
        tflops = step_flops(trainer, state, tbatch)
        _, _, tdt = measure(trainer, state, tbatch, tsteps)
        tmfu = _mfu(tflops, tdt / tsteps, device_kind)
        return {
            f"max_throughput_{unit}_per_sec_per_chip": round(
                actual_batch * tsteps / tdt / n_chips, 2),
            "max_throughput_batch_size": actual_batch,
            "max_throughput_step_time_ms": round(tdt / tsteps * 1000.0, 3),
            "max_throughput_mfu": round(tmfu, 4) if tmfu is not None else None,
        }
    except Exception as exc:  # pragma: no cover - OOM safety on small chips
        log(f"throughput-batch measurement skipped: {exc!r}")
        return {}


def _mfu(flops_per_step, step_seconds: float, device_kind: str):
    """flops_per_step is XLA's per-device cost (the SPMD executable is
    analyzed per device), so no division by chip count here."""
    peak = peak_flops_for(device_kind)
    if flops_per_step is None or peak is None or step_seconds <= 0:
        return None
    return flops_per_step / (step_seconds * peak)


def build_workload(name: str, smoke: bool = False, batch_override: int = 0,
                   use_flash=None, seq_override=None, mu_dtype=None,
                   s2d: bool = False, optimizer: str = "adam",
                   norm_variant: str = "bn"):
    """(trainer, batch, batch_size, extra) for a named workload — the
    single construction point shared by the bench passes below and by
    ``tools/roofline.py``, so the analysis tool always explains exactly
    the program the bench measures."""
    import jax
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer

    mesh = make_mesh()
    n_chips = len(jax.devices())
    rng = np.random.default_rng(0)
    extra = {}
    if name == "cnn":
        from pyspark_tf_gke_tpu.models import CNNRegressor

        batch_size = batch_override or (8 if smoke else 32)
        model = CNNRegressor(num_outputs=2, flat=True, dtype=jnp.bfloat16)
        batch = {
            "image": rng.uniform(
                0, 1, (batch_size, 256, 320, 3)).astype(np.float32),
            "target": rng.uniform(
                0, 256, (batch_size, 2)).astype(np.float32),
        }
        # mu_dtype: the flagship is param/optimizer-traffic-bound at
        # batch 32 (tools/roofline.py analytic model); bf16 Adam
        # first moments halve that slice of the HBM stream. Disclosed
        # as a separate matrix entry — the headline keeps f32 parity.
        # --adafactor goes further: the factored second moment reduces
        # nu from a full param-shaped tensor to row+column vectors,
        # attacking the same bound stream harder (also a disclosed
        # variant; optimizer semantics differ from the Adam headline).
        if optimizer != "adam":
            from pyspark_tf_gke_tpu.train.harness import make_optimizer

            tx = make_optimizer(1e-3, "constant", total_steps=0,
                                optimizer=optimizer)
            trainer = Trainer(model, TASKS["regression"](), mesh, tx=tx)
        else:
            trainer = Trainer(model, TASKS["regression"](), mesh,
                              learning_rate=1e-3, mu_dtype=mu_dtype)
    elif name == "resnet50":
        from pyspark_tf_gke_tpu.models import ResNet50

        batch_size, hw = (8, 64) if smoke else (64, 224)
        batch_size = batch_override or batch_size
        # --s2d: the disclosed stem lever (see models/resnet.py
        # space_to_depth) — same output shapes and FLOP class, stem
        # contraction dim 4*4*12=192 instead of 7*7*3=147-with-3-wide
        # lanes; the next chip window A/Bs it against the plain headline.
        # --gn: the norm lever tools/mfu_probe.py measured (GroupNorm-32
        # ran within ~4% of the identity-norm floor's gap vs BN on the
        # live chip) — a DISCLOSED model-semantics variant, not a
        # drop-in: GN trains differently from BN.
        # --fused-bn: SAME BatchNorm semantics, restructured passes —
        # Pallas 1x1-conv kernels with stat epilogues + on-read
        # normalize (models/resnet.py FusedBottleneckBlock); parity
        # guarded by tests/test_fused_resnet.py.
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                         s2d_stem=s2d, norm_variant=norm_variant)
        batch = {
            "image": rng.uniform(0, 1, (batch_size, hw, hw, 3)).astype(np.float32),
            "label": rng.integers(0, 1000, (batch_size,)).astype(np.int32),
        }
        trainer = Trainer(model, TASKS["resnet"](), mesh, learning_rate=1e-3)
        if s2d:
            extra["stem"] = "space_to_depth_2x_4x4"
        if norm_variant != "bn":
            extra["norm_variant"] = norm_variant
    elif name == "vit":
        from pyspark_tf_gke_tpu.models import BertConfig, ViTClassifier

        batch_size, hw = (8, 32) if smoke else (64, 224)
        batch_size = batch_override or batch_size
        cfg_kwargs = (dict(hidden_size=64, num_layers=2, num_heads=4,
                           intermediate_size=128) if smoke else {})
        # ViT-Base = BERT-base encoder over 16x16 patches
        model = ViTClassifier(BertConfig(**cfg_kwargs), num_classes=1000,
                              patch_size=16, mesh=mesh)
        batch = {
            "image": rng.uniform(0, 1, (batch_size, hw, hw, 3)).astype(np.float32),
            "label": rng.integers(0, 1000, (batch_size,)).astype(np.int32),
        }
        trainer = Trainer(model, TASKS["vit"](), mesh, learning_rate=1e-3)
    elif name == "bert":
        from pyspark_tf_gke_tpu.models import BertConfig, BertForPretraining

        batch_size, seq = (8, 32) if smoke else (32, 128)
        batch_size = batch_override or batch_size
        if seq_override:
            seq = int(seq_override)
            # ~constant tokens/step, rounded up to a multiple of the data
            # shards so batch_sharding can split the leading dim.
            batch_size = max(batch_size * 128 // seq, 1)
            batch_size = -(-batch_size // n_chips) * n_chips
        cfg_kwargs = (dict(vocab_size=512, hidden_size=64, num_layers=2,
                           num_heads=4, intermediate_size=128)
                      if smoke else {})
        if seq > 512:
            cfg_kwargs["max_position_embeddings"] = seq
        if use_flash is not None:
            cfg_kwargs["use_flash"] = use_flash
        cfg = BertConfig(**cfg_kwargs)
        model = BertForPretraining(cfg, mesh=mesh)
        batch = {
            "input_ids": rng.integers(
                0, cfg.vocab_size, (batch_size, seq)).astype(np.int32),
            "attention_mask": np.ones((batch_size, seq), dtype=np.int32),
            "labels": rng.integers(0, 2, (batch_size,)).astype(np.int32),
        }
        trainer = Trainer(model, TASKS["bert_classification"](), mesh,
                          learning_rate=1e-4)
        from pyspark_tf_gke_tpu.models.bert import resolve_use_flash

        extra["flash"] = resolve_use_flash(cfg, seq)
        extra["seq_len"] = seq
    else:
        raise SystemExit(
            f"unknown workload {name!r}; use cnn | resnet50 | vit | bert "
            f"| generate | spec | io | router | replay")
    return trainer, batch, batch_size, extra


def main(batch_size: int = 32, steps: int = 100, throughput_batch: int = 128,
         throughput_steps: int = 40, mu_dtype=None,
         optimizer: str = "adam") -> dict:
    import jax

    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    devices = jax.devices()
    log(f"devices: {devices}")
    n_chips = len(devices)
    device_kind = devices[0].device_kind

    trainer, hbatch, batch_size, _ = build_workload("cnn",
                                                    batch_override=batch_size,
                                                    mu_dtype=mu_dtype,
                                                    optimizer=optimizer)
    mesh = trainer.mesh
    rng = np.random.default_rng(0)
    images, targets = hbatch["image"], hbatch["target"]

    state = trainer.init_state(make_rng(1337), {"image": images[:1], "target": targets[:1]})

    sharding = batch_sharding(mesh)
    batch = {
        "image": jax.device_put(images, sharding),
        "target": jax.device_put(targets, sharding),
    }

    flops = step_flops(trainer, state, batch)
    state, losses, dt = measure(trainer, state, batch, steps)

    step_ms = dt / steps * 1000.0
    images_per_sec = batch_size * steps / dt
    images_per_sec_per_chip = images_per_sec / n_chips
    mfu = _mfu(flops, dt / steps, device_kind)

    # Secondary: throughput-optimal batch. The B1 architecture is
    # latency-bound at batch 32 on a v5e (channel widths 3..64 against a
    # 128-wide MXU leave the chip idle between small kernels; measured
    # step time is nearly flat in batch), so a larger per-chip batch
    # raises images/sec ~linearly at the same step time. Reported
    # separately — the headline stays the reference's batch-32 config.
    tp = {}
    if throughput_batch and throughput_batch != batch_size:
        def make_tbatch():
            timages = rng.uniform(
                0, 1, (throughput_batch, 256, 320, 3)).astype(np.float32)
            ttargets = rng.uniform(
                0, 256, (throughput_batch, 2)).astype(np.float32)
            return {
                "image": jax.device_put(timages, sharding),
                "target": jax.device_put(ttargets, sharding),
            }

        tp = _throughput_pass(trainer, state, make_tbatch, throughput_steps,
                              n_chips, device_kind, throughput_batch,
                              unit="images")

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "reference_baseline.json"
    )
    vs_baseline = None
    if os.path.exists(baseline_path):
        with open(baseline_path) as fh:
            ref = json.load(fh)
        base = ref.get("images_per_sec_extrapolated_16vcpu") or ref.get("images_per_sec")
        if base:
            vs_baseline = images_per_sec_per_chip / base

    result = {
        "metric": CNN_METRIC,
        "value": round(images_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs_baseline, 3) if vs_baseline is not None else None,
        "step_time_ms": round(step_ms, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "flops_per_step": flops,
        "batch_size": batch_size,
        "n_chips": n_chips,
        "device_kind": device_kind,
        "workload": "CNN-B1 43.4M params, 256x320x3, "
                    + ("Adafactor" if optimizer == "adafactor" else "Adam")
                    + "+MSE, bf16 compute"
                    + (" + bf16 Adam moments" if mu_dtype is not None else ""),
        "baseline": "reference TF CNN-B1 on 16 vCPU (extrapolated; tools/reference_baseline.json)",
        **({"adam_mu_dtype": str(np.dtype(mu_dtype))}
           if mu_dtype is not None else {}),
        **({"optimizer": optimizer} if optimizer != "adam" else {}),
        **tp,
    }
    log(f"loss trajectory: {losses[0]:.3f} -> {losses[-1]:.3f}")
    return result


def bench_workload(name: str, steps: int = 50, smoke: bool = False,
                   use_flash=None, seq_override=None,
                   throughput_batch: int = 0, s2d: bool = False,
                   norm_variant: str = "bn") -> dict:
    """Secondary workloads: resnet50 / bert (BASELINE configs 4 and 5).
    ``smoke`` shrinks shapes so the plumbing runs on the CPU fake slice.
    ``use_flash`` (bert only): None = model default (flash auto on TPU at
    seq >= FLASH_MIN_SEQ), True/False forces the Pallas path on/off so
    the delta is measurable (``--flash`` / ``--no-flash``).
    ``seq_override`` (bert only, ``--seq N``): long-context variant —
    batch is scaled down to hold tokens/step constant.
    ``throughput_batch``: like the flagship's secondary pass — also
    measure at a larger per-chip batch (conv/matmul MFU on a v5e climbs
    with batch until the MXU tiles fill; the headline batch stays the
    BASELINE config's)."""
    import jax

    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    devices = jax.devices()
    n_chips = len(devices)
    device_kind = devices[0].device_kind

    trainer, batch, batch_size, extra = build_workload(
        name, smoke=smoke, use_flash=use_flash, seq_override=seq_override,
        s2d=s2d, norm_variant=norm_variant)
    state = trainer.init_state(make_rng(1337), batch)
    sharding = batch_sharding(trainer.mesh)
    global_batch = {k: jax.device_put(v, sharding) for k, v in batch.items()}

    flops = step_flops(trainer, state, global_batch)
    state, _, dt = measure(trainer, state, global_batch, steps)
    mfu = _mfu(flops, dt / steps, device_kind)

    scale = throughput_batch // batch_size if throughput_batch else 0
    if scale >= 2:
        # actual measured batch is batch_size*scale — report THAT, never
        # the requested number (a non-multiple request must not inflate
        # the recorded metric)
        actual = batch_size * scale
        extra.update(_throughput_pass(
            trainer, state,
            lambda: {k: jax.device_put(np.repeat(v, scale, axis=0), sharding)
                     for k, v in batch.items()},
            max(steps // 4, 2), n_chips, device_kind, actual,
            unit="examples"))
    elif throughput_batch:
        log(f"throughput batch {throughput_batch} < 2x the headline batch "
            f"{batch_size}; secondary pass skipped")

    return {
        "metric": f"{name}_train_examples_per_sec_per_chip",
        "value": round(batch_size * steps / dt / n_chips, 2),
        "unit": "examples/sec/chip",
        "vs_baseline": None,
        "step_time_ms": round(dt / steps * 1000.0, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "flops_per_step": flops,
        "batch_size": batch_size,
        "n_chips": n_chips,
        "device_kind": device_kind,
        **extra,
    }


def bench_spec_decode(smoke: bool = False, gamma: int = 4) -> dict:
    """Speculative decoding (models/speculative.py): GPT-small target +
    a 2-layer draft at half hidden. Random weights mean near-zero
    acceptance — the LOWER bound; a self-draft pass gives the perfect-
    draft upper bound; and a TRAINED draft/target pair
    (train/spec_fixture.py) reports the realistic middle as the
    ``trained_fixture`` block. What the bounds measure on hardware is
    the real cost of the chunk-verify forward vs per-token decode."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.models.speculative import speculative_generate
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    devices = jax.devices()
    device_kind = devices[0].device_kind
    if smoke:
        tcfg = CausalLMConfig(vocab_size=512, hidden_size=64, num_layers=2,
                              num_heads=4, intermediate_size=128,
                              max_seq_len=64, dtype=jnp.float32)
        dcfg = CausalLMConfig(vocab_size=512, hidden_size=32, num_layers=1,
                              num_heads=2, intermediate_size=64,
                              max_seq_len=64, dtype=jnp.float32)
        s_prompt, n_new = 16, 8
    else:
        tcfg = CausalLMConfig()  # GPT-small shape
        dcfg = CausalLMConfig(hidden_size=384, num_layers=2, num_heads=6,
                              intermediate_size=1536)
        # modest sizes: each speculative round host-syncs the accepted
        # count, so the workload is dispatch-bound by construction (the
        # per-round sync cost on a local chip is not measured)
        s_prompt, n_new = 64, 128
    target, draft = CausalLM(tcfg), CausalLM(dcfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, tcfg.vocab_size, (1, s_prompt)).astype(np.int32))
    tparams = nn.meta.unbox(
        jax.jit(target.init)(make_rng(1337), prompt[:, :8])["params"])
    dparams = nn.meta.unbox(
        jax.jit(draft.init)(make_rng(7), prompt[:, :8])["params"])

    def run(dm, dp):
        out, stats = speculative_generate(
            target, tparams, dm, dp, prompt, max_new_tokens=n_new,
            gamma=gamma, return_stats=True)
        np.asarray(out)  # completion barrier
        return stats

    run(draft, dparams)  # compile both round shapes
    t0 = time.perf_counter()
    stats = run(draft, dparams)
    dt = time.perf_counter() - t0

    run(target, tparams)  # perfect-draft upper bound (self-draft)
    t0 = time.perf_counter()
    stats_ub = run(target, tparams)
    dt_ub = time.perf_counter() - t0

    # Trained fixture (train/spec_fixture.py): a REAL draft/target pair
    # — both briefly trained on the same synthetic text — so the
    # reported acceptance sits meaningfully between the random-weights
    # lower bound and the self-draft 1.0 (round-3 verdict, Weak #5).
    from pyspark_tf_gke_tpu.train.spec_fixture import make_spec_fixture

    ft, ftp, fd, fdp, fprompt = make_spec_fixture(
        steps=60 if smoke else 1500)
    fn_new = 8 if smoke else 64

    def run_fixture():
        # highest matmul precision to match the fixture's training
        # numerics (see train/spec_fixture.py) — acceptance otherwise
        # degrades on TPU from bf16-pass f32 matmuls alone
        with jax.default_matmul_precision("highest"):
            out, stats = speculative_generate(
                ft, ftp, fd, fdp, fprompt, max_new_tokens=fn_new,
                gamma=gamma, return_stats=True)
            np.asarray(out)
        return stats

    run_fixture()  # compile
    t0 = time.perf_counter()
    fstats = run_fixture()
    fdt = time.perf_counter() - t0

    return {
        "metric": "causal_lm_speculative_tokens_per_sec",
        "value": round(n_new / dt, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "gamma": gamma,
        "acceptance_rate": round(stats["accepted"] / max(stats["proposed"], 1), 3),
        "tokens_per_round": round(stats["tokens_per_round"], 2),
        "upper_bound_tokens_per_sec": round(n_new / dt_ub, 1),
        "upper_bound_acceptance": round(
            stats_ub["accepted"] / max(stats_ub["proposed"], 1), 3),
        "trained_fixture": {
            "acceptance_rate": round(
                fstats["accepted"] / max(fstats["proposed"], 1), 3),
            "tokens_per_round": round(fstats["tokens_per_round"], 2),
            "tokens_per_sec": round(fn_new / fdt, 1),
            "detail": "2L-h64 target + 1L-h32 draft, both trained on "
                      "the same synthetic byte text "
                      "(train/spec_fixture.py)",
        },
        "new_tokens": n_new,
        "prompt_len": s_prompt,
        "device_kind": device_kind,
        "workload": (f"speculative decode: target {tcfg.num_layers}L "
                     f"h{tcfg.hidden_size} + draft {dcfg.num_layers}L "
                     f"h{dcfg.hidden_size} (random weights: lower bound; "
                     f"self-draft: upper bound; trained_fixture: the "
                     f"realistic middle)"),
    }


def bench_decode(smoke: bool = False, kv_heads=None, int8: bool = False,
                 num_beams: int = 0, int8_kv: bool = False) -> dict:
    """Serving-path throughput (BASELINE has no analog — this benches the
    framework's own KV-cache generation): one jitted prefill + scan
    decode on a GPT-small-shaped causal LM. Reports decode tokens/sec
    per chip and the prefill latency. ``--kv-heads N`` measures the GQA
    variant (smaller cache → less HBM traffic per decode step);
    ``--int8-kv`` stores the KV cache itself as int8 with per-(position,
    head) scales (models/causal_lm.py kv_cache_quant — the cache stream
    is the other decode bottleneck); ``--int8`` measures weight-only
    int8 quantized serving
    (ops/quant.py — 4× less weight-streaming traffic vs f32 params);
    ``--beams K`` measures beam-search decode (tokens/sec counts the
    selected sequence's tokens — compute is K× wider)."""
    import jax
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.models.causal_lm import _prefill
    from pyspark_tf_gke_tpu.utils.seeding import make_rng
    from flax import linen as nn

    devices = jax.devices()
    n_chips = len(devices)
    device_kind = devices[0].device_kind

    if smoke:
        cfg = CausalLMConfig(vocab_size=512, hidden_size=64, num_layers=2,
                             num_heads=4, intermediate_size=128,
                             max_seq_len=64, dtype=jnp.float32,
                             num_kv_heads=int(kv_heads) if kv_heads else None,
                             kv_cache_quant=int8_kv)
        batch, s_prompt, n_new = 2, 16, 8
    else:
        cfg = CausalLMConfig(
            num_kv_heads=int(kv_heads) if kv_heads else None,  # GPT-small shape
            kv_cache_quant=int8_kv)
        batch, s_prompt, n_new = 8, 128, 512

    model = CausalLM(cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, s_prompt)).astype(np.int32))
    variables = jax.jit(model.init)(make_rng(1337), prompt[:, :8])
    params = nn.meta.unbox(variables["params"])
    from pyspark_tf_gke_tpu.ops.quant import quantize_tree, tree_bytes

    dense_mb = tree_bytes(params) / 1e6
    if int8:
        params = jax.jit(quantize_tree)(params)
    params_mb = tree_bytes(params) / 1e6

    # Same completion barrier as measure(): every timing forces
    # np.asarray on a (small) result. Prefill and decode are timed
    # as separate dispatches (subtraction timing drowns in jitter at
    # small shapes).
    from pyspark_tf_gke_tpu.models.causal_lm import _decode

    rng_key = jax.random.PRNGKey(0)

    if num_beams:
        from pyspark_tf_gke_tpu.models.beam_search import _beam_decode

        if num_beams >= cfg.vocab_size:
            raise SystemExit(f"--beams {num_beams} must be < the model "
                             f"vocab ({cfg.vocab_size})")

        def run_decode(cache, last):
            toks, _ = _beam_decode(
                model, params, cache, last, max_new_tokens=n_new,
                num_beams=num_beams, eos_token_id=None,
                s_prompt=s_prompt, length_penalty=1.0)
            return toks
    else:
        def run_decode(cache, last):
            return _decode(
                model, params, cache, last, rng_key, jnp.float32(1.0), None,
                None, jnp.zeros((batch, 1), bool),
                max_new_tokens=n_new, greedy=True, eos_token_id=None,
                s_prompt=s_prompt, top_k=None)

    log("compiling prefill + decode...")
    cache, last = _prefill(model, params, prompt)
    np.asarray(last[:, :8])
    np.asarray(run_decode(cache, last))

    t0 = time.perf_counter()
    cache, last = _prefill(model, params, prompt)
    np.asarray(last[:, :8])  # tiny slice: completion barrier, not a 1MB transfer
    prefill_dt = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = run_decode(cache, last)
    np.asarray(out)
    decode_dt = time.perf_counter() - t0
    tokens = batch * n_new
    return {
        "metric": "causal_lm_decode_tokens_per_sec_per_chip",
        "value": round(tokens / decode_dt / n_chips, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "prefill_ms": round(prefill_dt * 1000.0, 2),
        "decode_step_ms": round(decode_dt / n_new * 1000.0, 3),
        "batch_size": batch,
        "prompt_len": s_prompt,
        "new_tokens": n_new,
        "kv_heads": cfg.kv_heads,
        "num_heads": cfg.num_heads,
        "int8_weights": int8,
        "int8_kv_cache": int8_kv,
        "num_beams": num_beams or None,
        "params_mb": round(params_mb, 1),
        "dense_params_mb": round(dense_mb, 1),
        "n_chips": n_chips,
        "device_kind": device_kind,
        "workload": (f"CausalLM {cfg.num_layers}L h{cfg.hidden_size} "
                     f"vocab {cfg.vocab_size}, "
                     + (f"beam-{num_beams} KV-cache decode" if num_beams
                        else "greedy KV-cache decode")),
    }


def _chaos_ab(model, params, slots: int, chunk: int, prompts, budgets,
              chaos_spec: str) -> dict:
    """Goodput + p99 A/B for ``cb --chaos``: the SAME concurrent
    request mix against a clean serving front and one with faults
    injected into its driver loop (``train/serve._ContinuousFront`` +
    ``resilience.FaultInjector.from_chaos_spec``). Failed requests
    (those killed by an engine rebuild) are excluded from goodput but
    INCLUDED in the latency population — a client that waited and then
    got a 500 still waited. The rebuild counter is read off a private
    registry so the A and B runs can't contaminate each other."""
    import threading as _threading

    from pyspark_tf_gke_tpu.obs.metrics import (MetricsRegistry,
                                                platform_families)
    from pyspark_tf_gke_tpu.train.resilience import FaultInjector
    from pyspark_tf_gke_tpu.train.serve import _ContinuousFront

    def run(spec: str) -> dict:
        reg = MetricsRegistry()
        fam = platform_families(reg)
        chaos = FaultInjector.from_chaos_spec(spec) if spec else None
        front = _ContinuousFront(model, params, eos_id=None,
                                 num_slots=slots, chunk=chunk,
                                 obs=fam, chaos=chaos)
        lock = _threading.Lock()
        lat_ms, ok_tokens, failures = [], [0], [0]
        t0 = time.perf_counter()

        def client(i: int) -> None:
            p = prompts[i % len(prompts)]
            b = int(budgets[i % len(budgets)])
            t = time.perf_counter()
            try:
                toks = front.submit_and_wait(p, b, timeout_s=600)
                with lock:
                    ok_tokens[0] += len(toks)
                    lat_ms.append((time.perf_counter() - t) * 1000.0)
            except Exception:  # noqa: BLE001 — failure IS the datum
                with lock:
                    failures[0] += 1
                    lat_ms.append((time.perf_counter() - t) * 1000.0)

        threads = [_threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        wall = time.perf_counter() - t0
        front.shutdown()
        lat_ms.sort()
        p99 = (lat_ms[min(len(lat_ms) - 1, int(0.99 * len(lat_ms)))]
               if lat_ms else 0.0)
        return {
            "goodput_tokens_per_sec": round(ok_tokens[0] / wall, 1),
            "p99_latency_ms": round(p99, 1),
            "ok_requests": len(lat_ms) - failures[0],
            "failed_requests": failures[0],
            "engine_rebuilds": int(
                fam["serve_engine_rebuilds_total"].value),
            "faults_fired": chaos.fired_faults if chaos else 0,
        }

    # warmup outside both timed runs: the front's jit programs are
    # module-level, so one tiny drained pass compiles for A and B alike
    warm = _ContinuousFront(model, params, eos_id=None, num_slots=slots,
                            chunk=chunk,
                            obs=platform_families(MetricsRegistry()))
    warm.submit_and_wait(prompts[0], 2, timeout_s=600)
    warm.shutdown()
    clean = run("")
    faulted = run(chaos_spec)
    return {
        "spec": chaos_spec,
        "clean": clean,
        "faulted": faulted,
        "goodput_ratio": round(
            faulted["goodput_tokens_per_sec"]
            / max(clean["goodput_tokens_per_sec"], 1e-9), 3),
        "p99_ratio": round(
            faulted["p99_latency_ms"]
            / max(clean["p99_latency_ms"], 1e-9), 3),
    }


def bench_continuous(smoke: bool = False, paged: bool = False,
                     chaos: bool = False, serial: bool = False) -> dict:
    """Continuous batching vs whole-batch serving on the SAME request
    set (train/continuous.py). The workload that separates them is
    budget variance: a whole-batch server runs every group for its
    longest member (idle slots burn decode steps), while the slot
    engine refills each KV slot the moment its request finishes.
    Useful-tokens/sec is the metric for BOTH sides — the engine's extra
    prefill dispatches and per-row scatter writes are inside its
    number, the baseline's idle-slot steps are inside its.

    ``serial=True`` (``cb --serial``) pins the headline to the
    UNPIPELINED loop (pipeline_depth 0) at the default chunk — the
    async-engine-core A/B reference: ``annotate_variant_regression``
    compares it against the committed pipelined ``cb`` baseline, and
    every ``cb`` entry additionally carries the in-run serial
    reference as ``serial_step_phases`` (the same-process, same-box
    half of the host-overhead A/B)."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.models.causal_lm import generate
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    devices = jax.devices()
    n_chips = len(devices)
    device_kind = devices[0].device_kind

    if smoke:
        cfg = CausalLMConfig(vocab_size=512, hidden_size=64, num_layers=2,
                             num_heads=4, intermediate_size=128,
                             max_seq_len=128, dtype=jnp.float32)
        slots, chunk, s_prompt, n_requests, lo, hi = 2, 4, 16, 5, 4, 16
    else:
        cfg = CausalLMConfig()  # GPT-small shape, as bench_decode
        slots, chunk, s_prompt, n_requests, lo, hi = 8, 16, 128, 32, 32, 512

    model = CausalLM(cfg)
    # --paged: the ENGINE runs the paged KV cache (global page pool +
    # block tables + the ragged paged_attention decode read,
    # ops/pallas/paged_attention.py) at the SAME slot count; the
    # whole-batch baseline and the parity oracle stay on the dense
    # layout (params are identical — the config only shapes the cache).
    # The pool is sized to full capacity (slots x max_pages_per_slot)
    # so throughput is comparable; the memory win is read off the
    # pages-in-use gauge, which tracks allocated tokens.
    eng_model = model
    if paged:
        import dataclasses as _dc

        page_size = 32 if smoke else 64
        pool = slots * (cfg.max_seq_len // page_size)
        eng_model = CausalLM(_dc.replace(
            cfg, kv_page_size=page_size, kv_num_pages=pool))
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, (n_requests, s_prompt)).astype(np.int32)
    budgets = rng.integers(lo, hi + 1, n_requests)
    variables = jax.jit(model.init)(
        make_rng(1337), jnp.asarray(prompts[:1, :8]))
    params = nn.meta.unbox(variables["params"])

    useful = int(budgets.sum())

    # -- whole-batch baseline: groups of `slots` in arrival order, each
    # group decodes to its LONGEST budget (idle-slot steps included in
    # its wall time), warmup group first so both sides time compiled
    # programs only.
    def run_whole_batch(max_new: int) -> float:
        """Timed whole-batch pass: groups of `slots` in arrival order,
        ragged tail padded to the full slot width (ONE compiled batch
        shape, same as a real fixed-batch server); pad rows' tokens
        are not counted in `useful`."""
        t0 = time.perf_counter()
        for g0 in range(0, n_requests, slots):
            group = prompts[g0:g0 + slots]
            if group.shape[0] < slots:
                pad = np.repeat(prompts[:1], slots - group.shape[0],
                                axis=0)
                group = np.concatenate([group, pad], axis=0)
            np.asarray(generate(model, params, jnp.asarray(group),
                                max_new_tokens=max_new))
        return time.perf_counter() - t0

    gb = jnp.asarray(prompts[:slots])
    np.asarray(generate(model, params, gb, max_new_tokens=int(hi)))
    base_dt = run_whole_batch(int(hi))
    # NOTE the baseline decodes max_new=hi for every group (a server
    # must compile ONE program, so it runs the worst-case budget; the
    # per-group max would recompile per group). Useful tokens only.
    base_tps = useful / base_dt / n_chips

    # -- continuous engine over the identical requests, two configs
    # (warmup: one tiny drained run compiles prefill bucket + chunk
    # program). The small-chunk unpipelined config preserves identity
    # with pre-round-4 trail entries; the tuned config (bigger chunk +
    # decode-ahead pipelining, train/continuous.py pipeline_depth) is
    # the HEADLINE: a bigger chunk amortizes per-dispatch host latency
    # and pipelining overlaps the readback with the next chunk's
    # compute (527 -> 1701 tok/s in the 2026-08 trail, taken at ~70 ms
    # dispatch latency; not measured on a local chip, where the
    # engine's no-padding advantage should weigh more).
    def run_engine(chunk_n: int, pipeline: int, adaptive: bool = False,
                   batch: bool = True, req_budgets=None,
                   schedule: str = "fifo"):
        req_budgets = budgets if req_budgets is None else req_budgets
        warm = ContinuousEngine(eng_model, params, num_slots=slots,
                                chunk=chunk_n, pipeline_depth=pipeline,
                                adaptive_chunk=adaptive, batch_admit=batch)
        # Compile coverage BEFORE timing: every batched-admission group
        # shape (k_pad 8/2/4 via group sizes 8, 2, 3) and — for the
        # adaptive scheduler — every chunk bucket the measured budgets
        # can trigger: one request whose budget is the sum of all
        # power-of-two buckets (2*chunk - 8) walks down through each.
        # Without this the adaptive and batch=True grid entries timed
        # XLA compiles, not the scheduler (round-5 code review).
        for group in (slots, 2, 3):
            for p in prompts[:group]:
                warm.submit(p, max_new_tokens=2)
            list(warm.run_until_drained())
        if adaptive:
            warm.submit(prompts[0], max_new_tokens=2 * chunk_n - 8)
            list(warm.run_until_drained())
        eng = ContinuousEngine(eng_model, params, num_slots=slots,
                               chunk=chunk_n, pipeline_depth=pipeline,
                               adaptive_chunk=adaptive, batch_admit=batch,
                               schedule=schedule)
        t0 = time.perf_counter()
        for p, b in zip(prompts, req_budgets):
            eng.submit(p, max_new_tokens=int(b))
        done = list(eng.run_until_drained())
        eng_dt = time.perf_counter() - t0
        got = sum(len(toks) for _, toks in done)
        want = int(req_budgets.sum())
        if got != want:
            raise RuntimeError(
                f"engine returned {got} tokens, expected {want}")
        st = eng.stats
        return got / eng_dt / n_chips, {
            "batch_admits": st["batch_admits"],
            "solo_admits": st["solo_admits"],
            # exact device-work count (sum of dispatched chunk sizes):
            # the timing-noise-immune half of the engine-vs-whole-batch
            # comparison — wall-clock swings run to run, the step
            # count does not
            "dispatched_steps": st["dispatched_steps"],
            # windowed step-phase decomposition (obs/stepstats.py):
            # host-overhead fraction + per-phase p50/p99 — the
            # ROADMAP item-4 baseline every trail entry now carries
            "step_phases": st["step_phases"],
            **({"paged": st["paged"]} if "paged" in st else {})}

    # the serial reference run's stats are kept: its step_phases block
    # (host_work_frac == host_overhead_frac on a serial loop) is the
    # in-run A/B anchor the pipelined headline is measured against
    base_cfg_tps, base_cfg_stats = run_engine(chunk, 0)
    if serial:
        # --serial: the headline IS the serial loop (the async-core
        # A/B reference; annotate_variant_regression scores it
        # against the committed pipelined `cb` baseline)
        tuned_chunk, tuned_depth, tuned_adaptive = chunk, 0, False
        tuned_sched, tuned_batch = "fifo", True
        eng_tps, admit_stats = base_cfg_tps, dict(base_cfg_stats)
        tried = {}
    elif smoke:
        tuned_chunk, tuned_depth, tuned_adaptive = chunk, 1, False
        tuned_sched, tuned_batch = "fifo", True
        eng_tps, admit_stats = run_engine(tuned_chunk, tuned_depth)
        tried = {}
    else:
        # Round-4 verdict Next #4: the 0.92x entry's named suspects are
        # per-chunk RTT not yet hidden by depth-1 decode-ahead. Sweep a
        # chunk x depth x scheduler grid and take the best MEASURED
        # config as the headline; every tried config is disclosed in
        # the result (no silent cherry-pick — the grid IS the
        # experiment). Round-5 lessons already in the grid: depth 2 at
        # fixed chunk LOSES (dead finished-slot decode grows with
        # depth x chunk); budget-aligned ADAPTIVE chunking lost at
        # ~70 ms dispatch latency (smaller chunks pay more dispatches
        # than the dead decode they save; not measured on a local
        # chip); BATCHED ADMISSION (one prefill op for a group of
        # admissions) gets an explicit in-run A/B because run-to-run
        # drift swamps cross-run comparisons of dispatch-bound configs.
        tried, stats_by = {}, {}
        best = (None, None, False, True, "fifo", -1.0, None)
        for chunk_n, depth, adaptive, batch, sched in (
                (64, 1, False, True, "fifo"),
                (128, 1, False, True, "fifo"),
                (128, 1, False, False, "fifo"),
                (128, 1, False, True, "longest"),
                (64, 2, True, True, "fifo"),
                (128, 2, True, True, "fifo")):
            tps, st = run_engine(chunk_n, depth, adaptive, batch,
                                 schedule=sched)
            key = (f"chunk{chunk_n}_depth{depth}"
                   + ("_adaptive" if adaptive else "")
                   + ("" if batch else "_nobatchadmit")
                   + ("_lpt" if sched == "longest" else ""))
            tried[key] = round(tps, 1)
            stats_by[key] = st
            if tps > best[5]:
                best = (chunk_n, depth, adaptive, batch, sched, tps, key)
        (tuned_chunk, tuned_depth, tuned_adaptive, tuned_batch,
         tuned_sched, eng_tps, best_key) = best
        admit_stats = stats_by[best_key]

    # -- high-variance mix: the workload continuous batching exists
    # for. Budgets span the model's whole decode headroom, so the
    # whole-batch server idles slots up to ~hi_hv steps per group while
    # the engine refills them. Disclosed as a SECONDARY result — the
    # primary mix stays comparable with the round-2..5 trail entries.
    high_variance = None
    if not smoke:
        hi_hv = cfg.max_seq_len - s_prompt
        budgets_hv = rng.integers(16, hi_hv + 1, n_requests)
        useful_hv = int(budgets_hv.sum())
        np.asarray(generate(model, params, gb, max_new_tokens=int(hi_hv)))
        base_hv_tps = useful_hv / run_whole_batch(int(hi_hv)) / n_chips
        eng_hv_tps, hv_stats = run_engine(
            tuned_chunk, tuned_depth, adaptive=tuned_adaptive,
            batch=tuned_batch, schedule=tuned_sched,
            req_budgets=budgets_hv)
        wb_hv_steps = -(-n_requests // slots) * int(hi_hv)
        high_variance = {
            "budget_range": [16, int(hi_hv)],
            "whole_batch_tokens_per_sec_per_chip": round(base_hv_tps, 1),
            "engine_tokens_per_sec_per_chip": round(eng_hv_tps, 1),
            "speedup_vs_whole_batch": round(eng_hv_tps / base_hv_tps, 3),
            "whole_batch_decode_steps": wb_hv_steps,
            "engine_decode_steps": hv_stats["dispatched_steps"],
            "device_step_ratio": round(
                wb_hv_steps / max(hv_stats["dispatched_steps"], 1), 3),
            "engine_config": {"chunk": tuned_chunk,
                              "pipeline_depth": tuned_depth,
                              "schedule": tuned_sched,
                              "adaptive_chunk": tuned_adaptive,
                              "batch_admit": tuned_batch, **hv_stats},
        }

    # Direct per-dispatch round-trip estimate: a trivial device op +
    # host readback, timed warm. This is the floor a chunk's collect
    # pays when decode-ahead cannot hide it — committed alongside the
    # speedup so the "is >1.0x possible over this link" arithmetic is
    # in the artifact, not in prose.
    one = jnp.zeros((1,), jnp.float32)
    add_one = jax.jit(lambda v: v + 1.0)
    np.asarray(add_one(one))
    t0 = time.perf_counter()
    rtt_n = 10
    for _ in range(rtt_n):
        np.asarray(add_one(one))
    rtt_ms = (time.perf_counter() - t0) / rtt_n * 1000.0

    # -- prefix-cache study: time-to-first-token for a long shared
    # prefix + short suffix, cold vs warmed (the shared-system-prompt
    # serving pattern). Engine with 1 slot + chunk 1 so the measured
    # span is prefill + ONE decode step both ways.
    plen = 16 if smoke else 384
    slen = 4 if smoke else 64
    prefix = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
    suffix = rng.integers(0, cfg.vocab_size, slen).astype(np.int32)
    full = np.concatenate([prefix, suffix])

    def first_token_ms(engine):
        engine.submit(full, max_new_tokens=1)
        t0 = time.perf_counter()
        while not engine.step():
            pass
        return (time.perf_counter() - t0) * 1000.0

    cold_eng = ContinuousEngine(model, params, num_slots=1, chunk=1)
    first_token_ms(cold_eng)  # compile both programs
    cold_ms = first_token_ms(cold_eng)
    warm_eng = ContinuousEngine(model, params, num_slots=1, chunk=1,
                                prefix_cache_size=1)
    warm_eng.warm_prefix(prefix)
    first_token_ms(warm_eng)  # compile the extension program
    warm_ms = first_token_ms(warm_eng)

    # -- --chaos: goodput/p99 under injected engine faults vs clean.
    # The fault steps are DRIVER-LOOP iterations (so the count scales
    # with load, not wall time); the A/B answers "what does one engine
    # rebuild cost the fleet" in the two units that matter — surviving
    # tokens/sec and tail latency.
    chaos_ab = None
    if chaos:
        spec = ("fail@4,slow@8:0.05" if smoke
                else "fail@40,fail@120,slow@80:0.25")
        chaos_ab = _chaos_ab(eng_model, params, slots, chunk,
                             prompts, budgets, spec)

    return {
        "metric": "continuous_batching_tokens_per_sec_per_chip",
        "value": round(eng_tps, 1),
        "unit": "useful_tokens/sec/chip",
        "vs_baseline": None,
        "whole_batch_tokens_per_sec_per_chip": round(base_tps, 1),
        "speedup_vs_whole_batch": round(eng_tps / base_tps, 3),
        "unpipelined_small_chunk_tokens_per_sec_per_chip": round(
            base_cfg_tps, 1),
        "unpipelined_chunk": chunk,
        "pipeline_depth": tuned_depth,
        "adaptive_chunk": tuned_adaptive,
        "schedule": tuned_sched,
        "batch_admit": tuned_batch,
        "admit_stats": admit_stats,
        # --paged identity: page-pool accounting vs the dense layout's
        # fixed num_slots x max_seq_len rows (the obs gauge
        # serve_kv_cache_bytes_per_layer tracks the in-use number live)
        **({"paged_kv": {
            "page_size": eng_model.cfg.kv_page_size,
            "pages_total": eng_model.cfg.kv_num_pages,
            "peak_pages_in_use": admit_stats.get(
                "paged", {}).get("peak_pages_in_use"),
            "page_alloc_failures": admit_stats.get(
                "paged", {}).get("page_alloc_failures"),
            "peak_kv_bytes_per_layer": (
                admit_stats.get("paged", {}).get("peak_pages_in_use", 0)
                * admit_stats.get("paged", {}).get(
                    "page_bytes_per_layer", 0)),
            "dense_kv_bytes_per_layer": (
                2 * slots * cfg.max_seq_len * cfg.kv_heads
                * (cfg.head_dim * 1 + 4 if cfg.kv_cache_quant  # +f32 scales
                   else cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)),
        }} if paged else {}),
        # The noise-immune half of the comparison: the engine retires
        # the same request mix in FEWER device decode steps than the
        # compiled-once whole-batch server (which runs every group to
        # the worst-case budget); when wall-clock is dominated by
        # dispatch latency x chunk count (dispatch_rtt_ms is measured
        # alongside), a step_ratio > 1 with speedup < 1 localizes the
        # residue to host dispatch, not the scheduler.
        "device_step_accounting": {
            "whole_batch_decode_steps": -(-n_requests // slots) * int(hi),
            "engine_decode_steps": admit_stats["dispatched_steps"],
            "step_ratio": round(
                (-(-n_requests // slots) * int(hi))
                / max(admit_stats["dispatched_steps"], 1), 3),
        },
        # the headline config's step-phase summary (host-overhead
        # fraction + per-phase p50/p99), surfaced top-level so
        # tools/trail_report.py renders the host/device split per
        # entry (popped from admit_stats — one copy per trail line)
        "step_phases": admit_stats.pop("step_phases", None),
        # the serial reference run's phase summary, captured in the
        # SAME process on the SAME box: host_overhead_frac here vs the
        # headline's is the async-core overlap A/B (on a serial loop
        # host_work_frac == host_overhead_frac by construction)
        "serial_step_phases": base_cfg_stats.get("step_phases"),
        "serial_headline": bool(serial),
        "tuning_grid": tried,  # every config measured for the headline
        **({"high_variance": high_variance}
           if high_variance is not None else {}),
        **({"chaos": chaos_ab} if chaos_ab is not None else {}),
        "dispatch_rtt_ms": round(rtt_ms, 2),
        "prefix_study": {
            "prefix_len": plen, "suffix_len": slen,
            "first_token_cold_ms": round(cold_ms, 2),
            "first_token_warm_ms": round(warm_ms, 2),
            "speedup": round(cold_ms / warm_ms, 3) if warm_ms else None,
        },
        "num_slots": slots,
        "chunk": tuned_chunk,  # the headline value's config
        "n_requests": n_requests,
        "budget_range": [int(lo), int(hi)],
        "prompt_len": s_prompt,
        "n_chips": n_chips,
        "device_kind": device_kind,
        "workload": (f"CausalLM {cfg.num_layers}L h{cfg.hidden_size} "
                     f"slot-engine vs whole-batch serving"),
    }


def bench_chunked_prefill(smoke: bool = False) -> dict:
    """``cb --chunked-prefill``: the head-of-line-blocking A/B. A mixed
    prompt-length request set (mostly short prompts, periodic LONG
    ones) runs through the PAGED slot engine at equal slot count twice:
    chunked prefill + step-token budget ON (long prompts admit in
    bounded pieces, decode chunks interleave) vs OFF (every admission
    is a monolithic prefill that stalls all live slots for the whole
    prompt). Streaming callbacks timestamp every token-group delivery;
    TBT samples are the gaps between consecutive deliveries per request
    (the first delivery is TTFT and excluded). Reported: useful
    tokens/sec/chip both ways plus p50/p99 TBT — the tail is what
    chunking exists to flatten; throughput must stay within a few
    percent (the same device work, rescheduled)."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    devices = jax.devices()
    n_chips = len(devices)
    device_kind = devices[0].device_kind

    if smoke:
        cfg = CausalLMConfig(vocab_size=512, hidden_size=64, num_layers=2,
                             num_heads=4, intermediate_size=128,
                             max_seq_len=256, dtype=jnp.float32)
        slots, chunk, n_requests = 2, 4, 6
        short_len, long_len, budget = 16, 100, 8
        page_size, prefill_chunk, step_budget = 32, 32, 40
    else:
        cfg = CausalLMConfig(max_seq_len=2048)  # GPT-small, long context
        slots, chunk, n_requests = 8, 16, 32
        short_len, long_len, budget = 64, 1024, 64
        page_size, prefill_chunk, step_budget = 64, 256, 384

    import dataclasses as _dc

    model = CausalLM(cfg)
    pool = slots * (cfg.max_seq_len // page_size)
    eng_model = CausalLM(_dc.replace(
        cfg, kv_page_size=page_size, kv_num_pages=pool))
    rng = np.random.default_rng(0)
    # mixed arrival pattern: every 4th request is a LONG prompt — each
    # long admission lands while the short ones are mid-decode, which
    # is exactly the stall the unchunked engine exposes
    lens = [long_len if i % 4 == 3 else short_len
            for i in range(n_requests)]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    variables = jax.jit(model.init)(
        make_rng(1337), jnp.asarray(prompts[0][None, :8]))
    params = nn.meta.unbox(variables["params"])
    useful = budget * n_requests

    from pyspark_tf_gke_tpu.train import continuous as _cont

    def jit_cache_size() -> int:
        """Total compiled-program count across the engine's module-
        level jits — the acceptance criterion's 'zero steady-state
        recompiles' is measured, not asserted: warmup compiles
        everything, the timed run must add nothing."""
        return sum(
            f._cache_size() for f in (
                _cont._prefill_padded_batch, _cont._decode_chunk,
                _cont._paged_prefill_chunk, _cont._activate_slot_paged,
                _cont._insert_slot_paged, _cont._insert_slots_batch_paged,
                _cont._paged_zeros_state, _cont._clear_live_paged))

    def run(chunked: bool):
        kw = (dict(prefill_chunk=prefill_chunk,
                   step_token_budget=step_budget) if chunked else {})
        eng = ContinuousEngine(eng_model, params, num_slots=slots,
                               chunk=chunk, **kw)
        arrivals = []  # per request: [t0, t1, ...] delivery timestamps
        jits0 = jit_cache_size()

        t0 = time.perf_counter()
        for p in prompts:
            ts = []
            arrivals.append(ts)
            # the driver thread runs callbacks synchronously — append
            # is the whole cost, timestamps are delivery times
            eng.submit(p, max_new_tokens=budget,
                       on_tokens=lambda _t, ts=ts: ts.append(
                           time.perf_counter()))
        done = list(eng.run_until_drained())
        dt = time.perf_counter() - t0
        got = sum(len(toks) for _, toks in done)
        if got != useful:
            raise RuntimeError(
                f"engine returned {got} tokens, expected {useful}")
        gaps = []
        for ts in arrivals:
            gaps += [(b - a) * 1000.0 for a, b in zip(ts, ts[1:])]
        gaps.sort()

        def pct(p):
            return (round(gaps[min(len(gaps) - 1,
                                   int(p * len(gaps)))], 2)
                    if gaps else None)

        return {
            "tokens_per_sec_per_chip": round(got / dt / n_chips, 1),
            "tbt_p50_ms": pct(0.50),
            "tbt_p99_ms": pct(0.99),
            "tbt_max_ms": round(gaps[-1], 2) if gaps else None,
            "tbt_samples": len(gaps),
            "prefill_chunks": eng.stats["prefill_chunks"],
            "dispatched_steps": eng.stats["dispatched_steps"],
            "step_phases": eng.stats["step_phases"],
            "steady_state_recompiles": jit_cache_size() - jits0,
        }

    # warmup: compile both sides' program sets outside the timed runs —
    # both prompt buckets, the k_pad=2 batched admission the short
    # prompts trigger, the chunked side's piece width, and a
    # full-budget decode so the budget scheduler's bucketed chunk
    # sizes compile
    for chunked in (False, True):
        warm_kw = (dict(prefill_chunk=prefill_chunk,
                        step_token_budget=step_budget) if chunked else {})
        warm = ContinuousEngine(eng_model, params, num_slots=slots,
                                chunk=chunk, **warm_kw)
        for p in (prompts[0], prompts[1], prompts[3]):
            warm.submit(p, max_new_tokens=2)
        list(warm.run_until_drained())
        warm.submit(prompts[3], max_new_tokens=budget)
        warm.submit(prompts[0], max_new_tokens=budget)
        list(warm.run_until_drained())
    off = run(chunked=False)
    on = run(chunked=True)
    return {
        "metric": "continuous_batching_chunked_prefill_tokens_per_sec_per_chip",
        "value": on["tokens_per_sec_per_chip"],
        "unit": "useful_tokens/sec/chip",
        "vs_baseline": None,
        "chunked": on,
        "unchunked": off,
        "tokens_ratio": round(
            on["tokens_per_sec_per_chip"]
            / max(off["tokens_per_sec_per_chip"], 1e-9), 3),
        "tbt_p99_ratio": (round(on["tbt_p99_ms"] / off["tbt_p99_ms"], 3)
                          if on["tbt_p99_ms"] and off["tbt_p99_ms"]
                          else None),
        # the headline (chunked) side's step-phase summary, surfaced
        # top-level so tools/trail_report.py renders the host/device
        # split for this entry (both sides keep theirs nested)
        "step_phases": on["step_phases"],
        "prefill_chunk_tokens": prefill_chunk,
        "step_token_budget": step_budget,
        "num_slots": slots,
        "chunk": chunk,
        "n_requests": n_requests,
        "prompt_lens": [short_len, long_len],
        "budget": budget,
        "paged_kv": {"page_size": page_size, "pages_total": pool},
        "n_chips": n_chips,
        "device_kind": device_kind,
        "workload": (f"CausalLM {cfg.num_layers}L h{cfg.hidden_size} "
                     f"paged slot-engine, mixed {short_len}/{long_len}-"
                     f"token prompts: chunked prefill A/B"),
    }


def bench_prefix_cache(smoke: bool = False) -> dict:
    """``cb --prefix-cache``: the shared-prefix serving A/B. A fleet of
    requests sharing one LONG system prompt × short unique suffixes
    (the millions-of-users shape the router's prefix affinity exists
    for) runs through the PAGED slot engine twice: radix prefix cache
    ON (the warmed prefix stays resident as refcounted pages; every
    admission shares them copy-on-write and prefills its unique suffix
    only) vs OFF (every request re-prefills from token 0). Reported:
    useful tokens/sec both ways, the engine's ``prefill_tokens_computed``
    counter (the acceptance criterion: ON must be ∝ unique-suffix
    tokens — the shared prefix prefilled ONCE, at the warm), the hit
    rate, and token-exact parity between the two runs (reuse must be
    invisible in the output). Host-measurable: the win is prefill-FLOP
    elision, not a device effect — a CPU-measured ratio is a lower
    bound for chips where prefill is compute-bound."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    devices = jax.devices()
    n_chips = len(devices)
    device_kind = devices[0].device_kind

    if smoke:
        cfg = CausalLMConfig(vocab_size=512, hidden_size=64, num_layers=2,
                             num_heads=4, intermediate_size=128,
                             max_seq_len=256, dtype=jnp.float32)
        slots, chunk, n_requests = 2, 4, 6
        shared_len, suffix_len, budget = 96, 12, 8
        page_size, prefill_chunk = 32, 64
    else:
        # sized to measure on a HOST too (the ratio is backend-agnostic
        # — prefill elision): a mid-size model where prefill dominates,
        # exactly the shared-system-prompt regime
        cfg = CausalLMConfig(vocab_size=1024, hidden_size=128,
                             num_layers=4, num_heads=8, num_kv_heads=4,
                             intermediate_size=512, max_seq_len=1024,
                             dtype=jnp.float32)
        slots, chunk, n_requests = 4, 8, 16
        shared_len, suffix_len, budget = 512, 32, 16
        page_size, prefill_chunk = 64, 128

    import dataclasses as _dc

    pool = slots * (cfg.max_seq_len // page_size) + (
        shared_len // page_size + 2)  # live slots + resident prefix
    eng_model = CausalLM(_dc.replace(
        cfg, kv_page_size=page_size, kv_num_pages=pool))
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, shared_len).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, suffix_len).astype(np.int32)])
        for _ in range(n_requests)]
    variables = jax.jit(CausalLM(cfg).init)(
        make_rng(1337), jnp.asarray(prompts[0][None, :8]))
    params = nn.meta.unbox(variables["params"])
    useful = budget * n_requests

    def run(cached: bool):
        kw = dict(prefill_chunk=prefill_chunk)
        if cached:
            kw["prefix_cache_size"] = pool
        eng = ContinuousEngine(eng_model, params, num_slots=slots,
                               chunk=chunk, **kw)
        t0 = time.perf_counter()
        if cached:
            # the production shape: the shared system prompt is warmed
            # once (POST /v1/warm; the first completion would seed it
            # too) — INSIDE the timed window, so the ON side pays for
            # its one shared-prefix prefill
            eng.warm_prefix(shared)
        rids = [eng.submit(p, max_new_tokens=budget) for p in prompts]
        done = dict(eng.run_until_drained())
        dt = time.perf_counter() - t0
        got = sum(len(done[r]) for r in rids)
        if got != useful:
            raise RuntimeError(
                f"engine returned {got} tokens, expected {useful}")
        stats = eng.stats
        pc = stats.get("prefix_cache") or {}
        return {
            "tokens_per_sec_per_chip": round(got / dt / n_chips, 1),
            "prefill_tokens_computed": stats["prefill_tokens_computed"],
            "hits": pc.get("hits", 0),
            "hit_tokens": pc.get("hit_tokens", 0),
            "evictions": pc.get("evictions", 0),
            "resident_pages": pc.get("resident_pages", 0),
            "step_phases": stats["step_phases"],
        }, [done[r] for r in rids]

    # warmup compiles both program sets outside the timed runs (piece
    # widths, suffix-piece width on a hit, decode chunks, warm pieces)
    for cached in (False, True):
        warm_kw = dict(prefill_chunk=prefill_chunk)
        if cached:
            warm_kw["prefix_cache_size"] = pool
        warm = ContinuousEngine(eng_model, params, num_slots=slots,
                                chunk=chunk, **warm_kw)
        if cached:
            warm.warm_prefix(shared)
        for p in (prompts[0], prompts[1]):
            warm.submit(p, max_new_tokens=2)
        list(warm.run_until_drained())
    off, toks_off = run(cached=False)
    on, toks_on = run(cached=True)
    if toks_on != toks_off:
        raise RuntimeError(
            "prefix-cache run diverged from the cache-off run — page "
            "sharing corrupted decode")
    unique_suffix_tokens = n_requests * suffix_len
    return {
        "metric": "continuous_batching_prefix_cache_tokens_per_sec_per_chip",
        "value": on["tokens_per_sec_per_chip"],
        "unit": "useful_tokens/sec/chip",
        "vs_baseline": None,
        "cached": on,
        "uncached": off,
        "tokens_ratio": round(
            on["tokens_per_sec_per_chip"]
            / max(off["tokens_per_sec_per_chip"], 1e-9), 3),
        # the structural claim: computed prefill ∝ unique suffix (the
        # shared prefix prefilled once at the warm, not per request)
        "prefill_computed_on": on["prefill_tokens_computed"],
        "prefill_computed_off": off["prefill_tokens_computed"],
        "prefill_computed_ideal": shared_len + unique_suffix_tokens,
        "step_phases": on["step_phases"],  # headline (cached) side —
        #   trail_report's host-overhead column reads this
        "token_parity": True,
        "shared_prefix_tokens": shared_len,
        "suffix_tokens": suffix_len,
        "num_slots": slots,
        "chunk": chunk,
        "n_requests": n_requests,
        "budget": budget,
        "prefill_chunk_tokens": prefill_chunk,
        "paged_kv": {"page_size": page_size, "pages_total": pool},
        "n_chips": n_chips,
        "device_kind": device_kind,
        "workload": (f"CausalLM {cfg.num_layers}L h{cfg.hidden_size} "
                     f"paged slot-engine, {shared_len}-token shared "
                     f"prefix x {suffix_len}-token suffixes: radix "
                     "prefix cache A/B"),
    }


def bench_spec_cb(smoke: bool = False, spec_tokens: int = 5) -> dict:
    """``cb --spec``: the in-engine speculative-decoding A/B on a
    decode-heavy mix. The draft/target pair mirrors the regime
    speculation actually deploys in: a 12-layer target (deep enough
    that one 1-layer draft forward is genuinely cheap next to a
    verify — the 70B-target/1B-draft cost gap, scaled down) and a
    draft DISTILLED on the target's own greedy rollouts
    (sequence-level distillation — the standard draft-training recipe,
    and the reason acceptance holds deep into a long generation
    instead of drifting off the training distribution). Short
    in-distribution prompts with large budgets run through the PAGED
    slot engine twice: ``spec_tokens`` draft/verify speculation ON vs
    OFF at identical engine settings (same slots/chunk/adaptive — the
    only delta is speculation). Greedy token parity between the two
    runs is ASSERTED (the acceptance rule's contract), and the report
    carries the measured accept rate next to the throughput ratio.
    Host-measurable: the win is verify-forwards-per-token elision — on
    chips, where the decode step is HBM-bound and the verify chunk's
    extra columns ride ~free, the CPU ratio is a lower bound."""
    import jax
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.models.causal_lm import generate
    from pyspark_tf_gke_tpu.train.continuous import ContinuousEngine
    from pyspark_tf_gke_tpu.train.spec_fixture import (_pack_rows,
                                                       _train_lm)

    devices = jax.devices()
    n_chips = len(devices)
    device_kind = devices[0].device_kind

    if smoke:
        steps, distill_steps, n_requests, budget = 120, 200, 4, 48
        distill_rows = 16
    else:
        steps, distill_steps, n_requests, budget = 800, 1200, 8, 128
        distill_rows = 64
    slots, chunk = 2, 64
    skew, plen, page_size = 0.8, 16, 32
    common = dict(vocab_size=259, max_seq_len=256, dtype=jnp.float32)
    tcfg = CausalLMConfig(hidden_size=64, num_layers=12, num_heads=4,
                          intermediate_size=128, **common)
    dcfg = CausalLMConfig(hidden_size=32, num_layers=1, num_heads=2,
                          intermediate_size=64, **common)
    rows = _pack_rows(64, n_rows=32, seed=0, skew=skew)
    target, draft = CausalLM(tcfg), CausalLM(dcfg)
    # highest matmul precision throughout: the pair trains there
    # (train/spec_fixture.py's backend-robustness lesson) and decode
    # must match or near-argmax ties flip and acceptance loses meaning
    with jax.default_matmul_precision("highest"):
        tparams = _train_lm(target, rows, steps, lr=3e-3, seed=0)
        # distill the draft on the TARGET'S OWN greedy rollouts: the
        # student optimizes exactly the acceptance objective, on
        # policy, so agreement survives generation depth
        seeds = _pack_rows(8, n_rows=distill_rows, seed=3, skew=skew)
        rollouts = np.asarray(generate(
            target, tparams, jnp.asarray(seeds), max_new_tokens=56))
        dparams = _train_lm(draft, rollouts, distill_steps, lr=3e-3,
                            seed=1)

    import dataclasses as _dc

    pool = slots * (tcfg.max_seq_len // page_size)
    paged = CausalLM(_dc.replace(tcfg, kv_page_size=page_size,
                                 kv_num_pages=pool))
    prompts = [np.asarray(r) for r in _pack_rows(
        plen, n_rows=n_requests, seed=5, skew=skew)]
    useful = budget * n_requests

    def run(spec: bool):
        kw = dict(adaptive_chunk=True)
        if spec:
            kw.update(spec_tokens=spec_tokens, draft_model=draft,
                      draft_params=dparams)

        def go():
            eng = ContinuousEngine(paged, tparams, num_slots=slots,
                                   chunk=chunk, **kw)
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p, max_new_tokens=budget)
            done = dict(eng.run_until_drained())
            return eng, time.perf_counter() - t0, done

        go()  # full warmup pass: every rounds bucket / admit width the
        #       timed schedule will touch compiles here
        best = None
        for _ in range(2):  # best-of-2 on a shared-core host
            eng, dt, done = go()
            if best is None or dt < best[1]:
                best = (eng, dt, done)
        eng, dt, done = best
        got = sum(len(t) for t in done.values())
        if got != useful:
            raise RuntimeError(
                f"engine returned {got} tokens, expected {useful}")
        stats = eng.stats
        out = {
            "tokens_per_sec_per_chip": round(got / dt / n_chips, 1),
            "dispatched_work_tokens": stats["dispatched_steps"],
            "step_phases": stats["step_phases"],
        }
        if spec:
            out["spec"] = stats["spec"]
        return out, [done[r] for r in sorted(done)]

    with jax.default_matmul_precision("highest"):
        off, toks_off = run(spec=False)
        on, toks_on = run(spec=True)
    if toks_on != toks_off:
        raise RuntimeError(
            "speculative run diverged from the plain engine — the "
            "greedy acceptance rule is broken")
    return {
        "metric": "continuous_batching_spec_tokens_per_sec_per_chip",
        "value": on["tokens_per_sec_per_chip"],
        "unit": "useful_tokens/sec/chip",
        "vs_baseline": None,
        "spec": on,
        "plain": off,
        "tokens_ratio": round(
            on["tokens_per_sec_per_chip"]
            / max(off["tokens_per_sec_per_chip"], 1e-9), 3),
        "accept_rate": on["spec"]["accept_rate"],
        "step_phases": on["step_phases"],  # headline (spec) side —
        #   trail_report's host-overhead column reads this
        "spec_tokens": spec_tokens,
        "token_parity": True,
        "num_slots": slots,
        "chunk": chunk,
        "n_requests": n_requests,
        "prompt_len": plen,
        "budget": budget,
        "fixture_steps": steps,
        "distill_steps": distill_steps,
        "paged_kv": {"page_size": page_size, "pages_total": pool},
        "n_chips": n_chips,
        "device_kind": device_kind,
        "workload": (f"CausalLM {tcfg.num_layers}L h{tcfg.hidden_size} "
                     f"target + {dcfg.num_layers}L h{dcfg.hidden_size} "
                     f"draft (distilled on target rollouts, skew "
                     f"{skew}), paged slot-engine decode-heavy mix: "
                     f"in-engine speculative decoding A/B at "
                     f"k={spec_tokens}"),
    }


def bench_io(smoke: bool = False) -> dict:
    """Input-pipeline throughput on the native IO plane: TFRecord shards
    → ``native.ExamplePool`` → shuffled host batches at the BERT
    fine-tune schema (config 5's data plane). Reports rows/sec so the
    feed rate can be compared against the model's consumption rate
    (bert examples/sec × chips)."""
    import tempfile

    from pyspark_tf_gke_tpu.data import native_tfrecord as ntr
    from pyspark_tf_gke_tpu.data.tfrecord import schema_for

    n_shards = 2 if smoke else 8
    rows_per_shard = 200 if smoke else 5000
    seq, batch_size = 128, 32
    rng = np.random.default_rng(0)
    total = n_shards * rows_per_shard

    arrays = {
        "input_ids": rng.integers(0, 30522, (total, seq)).astype(np.int64),
        "label": rng.integers(0, 2, (total,)).astype(np.int64),
    }
    schema = schema_for(arrays)

    with tempfile.TemporaryDirectory() as td:
        # write A/B: serial (the pre-pipeline baseline, 24k rows/sec on
        # the committed trail) vs one-worker-thread-per-shard. Outputs
        # are byte-identical (tests pin it); only the wall clock moves.
        t_s0 = time.perf_counter()
        serial_paths = ntr.write_tfrecord_shards(
            arrays, os.path.join(td, "serial"), num_shards=n_shards,
            num_workers=1)
        write_serial_dt = time.perf_counter() - t_s0
        for p in serial_paths:
            os.remove(p)  # page cache aside, keep the read set single

        prefix = os.path.join(td, "bench")
        t_w0 = time.perf_counter()
        # explicit one-thread-per-shard (the default caps at cpu_count,
        # which would silently fall back to serial on a 1-vCPU host and
        # A/B nothing)
        ntr.write_tfrecord_shards(arrays, prefix, num_shards=n_shards,
                                  num_workers=n_shards)
        write_dt = time.perf_counter() - t_w0

        def read_all() -> int:
            rows = 0
            for batch in ntr.read_tfrecord_batches(
                f"{prefix}-*.tfrecord", schema, batch_size,
                shuffle=True, repeat=False,
                process_index=0, process_count=1,
            ):
                rows += len(batch["label"])
            return rows

        read_all()  # warmup (page cache, thread-pool spinup)
        t0 = time.perf_counter()
        n = read_all()
        read_dt = time.perf_counter() - t0

    return {
        "metric": "io_native_tfrecord_rows_per_sec",
        "value": round(n / read_dt, 1),
        "unit": "rows/sec",
        "vs_baseline": None,
        "rows": n,
        "shards": n_shards,
        "seq_len": seq,
        "batch_size": batch_size,
        "native": ntr.native_available(),
        "write_rows_per_sec": round(total / write_dt, 1),
        "write_rows_per_sec_serial": round(total / write_serial_dt, 1),
        "write_parallel_speedup": round(write_serial_dt / write_dt, 2),
        "write_workers": n_shards,
        "host_cpus": os.cpu_count(),
    }


def bench_router(smoke: bool = False) -> dict:
    """``python bench.py router``: the replica-router A/B. One router +
    two CPU replica subprocesses vs direct single-server traffic on the
    same request mix — throughput and p99 quantify the gateway hop and
    the 2x capacity; a kill-one-replica goodput run quantifies what the
    hedge/failover path saves when a pod dies mid-traffic.

    Host-only by design (like ``io``): the replicas are pinned to the
    CPU backend in their OWN subprocesses (the contract under test is
    routing, not decode speed), so this measurement needs no TPU and
    the bench parent does no jax device work at all.
    Launch scaffolding lives in ``router/localfleet.py`` (shared with
    ``smoke_check --router`` and the test soak)."""
    import shutil
    import signal
    import tempfile
    import threading

    from pyspark_tf_gke_tpu.router.localfleet import (
        export_tiny_bundle,
        free_port,
        launch_replica,
        launch_router,
        post_generate,
        wait_healthy,
    )

    n_requests = 16 if smoke else 64
    workers = 4 if smoke else 8
    max_new = 8

    def post(url, prompt, timeout=120.0):
        return post_generate(url, prompt, max_new_tokens=max_new,
                             timeout_s=timeout)

    def drive(url, n, kill_proc_at=None):
        """n requests over `workers` concurrent client threads; returns
        (ok, lost, wall_s, latencies_ms). ``kill_proc_at``: (proc,
        request_index) — SIGKILL that replica when the index dispatches
        (the failover goodput run)."""
        lat, errors = [], []
        idx_lock = threading.Lock()
        state = {"next": 0}

        def worker():
            while True:
                with idx_lock:
                    i = state["next"]
                    if i >= n:
                        return
                    state["next"] += 1
                    if kill_proc_at is not None \
                            and i == kill_proc_at[1] \
                            and kill_proc_at[0].poll() is None:
                        kill_proc_at[0].send_signal(signal.SIGKILL)
                t0 = time.perf_counter()
                try:
                    post(url, f"bench request {i}")
                    lat.append((time.perf_counter() - t0) * 1000.0)
                except Exception as exc:  # noqa: BLE001 — counted
                    errors.append((i, repr(exc)))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker)
                   for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.perf_counter() - t0
        return len(lat), len(errors), wall, sorted(lat)

    def pct(xs, q):
        return round(xs[min(len(xs) - 1, int(q * (len(xs) - 1)))], 1) \
            if xs else None

    tmp = tempfile.mkdtemp(prefix="bench-router-")
    procs, router_proc = [], None
    try:
        bundle = export_tiny_bundle(os.path.join(tmp, "bundle"))
        ports = [free_port(), free_port()]
        router_port = free_port()
        procs = [launch_replica(bundle, p) for p in ports]
        router_proc = launch_router(ports, router_port,
                                    extra_args=("--hedge-max-ms", "500"))
        direct_url = f"http://127.0.0.1:{ports[0]}"
        router_url = f"http://127.0.0.1:{router_port}"
        deadline = time.time() + 300
        for p in ports:
            wait_healthy(f"http://127.0.0.1:{p}", deadline)
        wait_healthy(router_url, deadline)
        # warm each replica DIRECTLY: routed warms can all land on one
        # replica (affinity hash on an idle fleet), leaving the other
        # to pay its first-request JIT compile inside the timed routed
        # run — which would charge a compile stall to routed_p99_ms
        for prompt in ("warm a", "warm b", "warm c", "warm d"):
            for p in ports:
                post(f"http://127.0.0.1:{p}", prompt)

        ok_d, lost_d, wall_d, lat_d = drive(direct_url, n_requests)
        ok_r, lost_r, wall_r, lat_r = drive(router_url, n_requests)
        # failover goodput: kill replica[1] a third of the way in; the
        # router must keep goodput near 1.0 (hedge/re-route), where a
        # client pinned to the dead server would lose the remainder
        ok_f, lost_f, wall_f, lat_f = drive(
            router_url, n_requests,
            kill_proc_at=(procs[1], n_requests // 3))
    finally:
        for p in [router_proc, *procs]:
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)
        shutil.rmtree(tmp, ignore_errors=True)

    routed_rps = ok_r / wall_r if wall_r else 0.0
    direct_rps = ok_d / wall_d if wall_d else 0.0
    return {
        "metric": "router_requests_per_sec",
        "value": round(routed_rps, 2),
        "unit": "requests/sec",
        "vs_baseline": None,
        "direct_requests_per_sec": round(direct_rps, 2),
        "speedup_vs_direct": round(routed_rps / direct_rps, 3)
        if direct_rps else None,
        "direct_p50_ms": pct(lat_d, 0.50),
        "direct_p99_ms": pct(lat_d, 0.99),
        "routed_p50_ms": pct(lat_r, 0.50),
        "routed_p99_ms": pct(lat_r, 0.99),
        "failover": {
            "requests": n_requests,
            "ok": ok_f,
            "lost": lost_f,
            "goodput": round(ok_f / n_requests, 3),
            "p99_ms": pct(lat_f, 0.99),
            "wall_s": round(wall_f, 2),
        },
        "n_requests": n_requests,
        "client_workers": workers,
        "max_new_tokens": max_new,
        "n_replicas": 2,
        "replica_slots": 2,
        "workload": ("1 router + 2 CPU BundleServer replicas vs direct "
                     "single-server; kill-one-replica goodput"),
    }


def bench_disagg(smoke: bool = False) -> dict:
    """``python bench.py disagg``: the prefill/decode disaggregation
    A/B. Two identical 2-replica CPU fleets behind the real router on
    the PAGED tiny bundle:

    * MIXED — both replicas ``--role mixed``, no handoff: long-prompt
      admissions prefill on whichever decode-serving replica the
      router picks (the RECOMPUTE baseline — exactly what a
      continuation splice pays).
    * SPLIT — replica 0 ``--role prefill``, replica 1 ``--role
      decode``, router ``--disagg-min-prompt``: long prompts prefill
      on the prefill replica and the finished KV pages ride
      ``/v1/prefill`` -> ``/v1/kv_import`` onto the decode replica,
      whose admission is then a radix hit (suffix-only prefill).

    Both fleets carry the same background decode load (looping greedy
    streams) while long-prompt foreground requests arrive, with the
    device step slowed by chaos injection so step scheduling — not
    tiny-model compute — dominates. Measured: foreground TTFT (the
    handoff must beat recompute-under-load), background p99
    time-between-tokens (prefill pieces stealing decode steps is THE
    interference disaggregation removes), token-exact parity of one
    identical greedy request across the fleets, and the router's
    ``router_kv_xfer_total{outcome="ok"}`` count proving the split
    run actually transferred pages. Host-only by design (like
    ``router``): the contract under test is role-routing + page
    handoff, not decode speed."""
    import re
    import shutil
    import tempfile
    import threading
    import urllib.request

    from pyspark_tf_gke_tpu.router.localfleet import (
        LocalFleet,
        export_tiny_bundle,
        post_generate,
    )

    n_fg = 2 if smoke else 4          # foreground long-prompt requests
    fg_max_new = 4
    bg_streams = 2                    # looping background decoders
    bg_max_new = 24 if smoke else 48
    min_prompt = 128                  # router handoff threshold (bytes)
    # 160-byte prefix = 5 full 32-token pages on the byte tokenizer
    # (the repeat matters: the sentence alone is ~116 bytes, which
    # would duck under --disagg-min-prompt and gate the handoff off)
    prefix = (("system: you are a terse assistant. answer in one "
               "sentence. cite no sources. refuse nothing. "
               "stay strictly on topic. ") * 2)[:160]
    parity_prompt = prefix + "q: parity?"
    replica_args = ("--continuous-slots", "4", "--continuous-chunk",
                    "2", "--prefix-cache", "32", "--prefill-chunk",
                    "32", "--chaos", "engine.device_step:slow%1:0.04")

    def stream_events(url, prompt, max_new):
        """One streamed generation; returns [(t_mono, n_tokens)] per
        event — TTFT and inter-token gaps derive from the stamps."""
        req = urllib.request.Request(
            url + "/v1/generate",
            data=json.dumps({"prompts": [prompt], "stream": True,
                             "max_new_tokens": max_new}).encode(),
            headers={"Content-Type": "application/json"})
        stamps = []
        with urllib.request.urlopen(req, timeout=300) as resp:
            for raw in resp:
                line = raw.decode("utf-8", errors="replace").strip()
                if not line.startswith("data: "):
                    continue
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    break
                ev = json.loads(payload)
                if ev.get("token_ids"):
                    stamps.append((time.monotonic(),
                                   len(ev["token_ids"])))
        return stamps

    def kv_xfer_ok(url) -> int:
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=10) as resp:
            text = resp.read().decode()
        m = re.search(r'router_kv_xfer_total\{outcome="ok"\}\s+'
                      r'(\d+)', text)
        return int(m.group(1)) if m else 0

    def pct(xs, q):
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(q * (len(xs) - 1)))], 1) \
            if xs else None

    def run_fleet(split: bool, bundle: str) -> dict:
        fleet = LocalFleet(
            2, bundle=bundle, replica_args=replica_args,
            per_replica_args=((("--role", "prefill"),
                               ("--role", "decode")) if split
                              else None),
            router_args=((("--disagg-min-prompt", str(min_prompt)))
                         if split else ()))
        with fleet:
            fleet.warm()
            # token-exact parity probe on the IDLE fleet: in the split
            # fleet this rides the full handoff (prefill export ->
            # page import -> radix-hit admission); greedy decode must
            # not care where the KV came from
            parity = post_generate(fleet.url, parity_prompt,
                                   max_new_tokens=8, timeout_s=300.0)
            parity_text = parity["completions"][0]["completion"]

            stop = threading.Event()
            gaps, bg_lock = [], threading.Lock()

            def background(i):
                # short prompts (below the handoff threshold) looping
                # until the foreground phase ends: sustained decode
                # load on the non-prefill pool
                while not stop.is_set():
                    stamps = stream_events(
                        fleet.url, f"background stream {i} ",
                        bg_max_new)
                    with bg_lock:
                        gaps.extend(
                            (b[0] - a[0]) * 1000.0
                            for a, b in zip(stamps, stamps[1:]))

            threads = [threading.Thread(target=background, args=(i,))
                       for i in range(bg_streams)]
            for t in threads:
                t.start()
            time.sleep(1.5)  # let the streams occupy decode slots
            ttft = []
            try:
                for i in range(n_fg):
                    # unique long prompts: no radix reuse across
                    # foreground requests — each pays a full prefill
                    # (mixed) or a full handoff (split)
                    prompt = f"fg {i:03d} " + prefix
                    t0 = time.monotonic()
                    stamps = stream_events(fleet.url, prompt,
                                           fg_max_new)
                    if stamps:
                        ttft.append((stamps[0][0] - t0) * 1000.0)
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=300)
            xfers = kv_xfer_ok(fleet.url)
        return {"ttft_ms": [round(t, 1) for t in ttft],
                "ttft_p50_ms": pct(ttft, 0.50),
                "bg_tbt_p99_ms": pct(gaps, 0.99),
                "bg_gaps": len(gaps),
                "parity_text": parity_text,
                "kv_xfer_ok": xfers}

    tmp = tempfile.mkdtemp(prefix="bench-disagg-")
    try:
        bundle = export_tiny_bundle(os.path.join(tmp, "bundle"),
                                    paged=True)
        mixed = run_fleet(split=False, bundle=bundle)
        split = run_fleet(split=True, bundle=bundle)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    parity_ok = mixed["parity_text"] == split["parity_text"]
    ttft_speedup = (round(mixed["ttft_p50_ms"] / split["ttft_p50_ms"],
                          3)
                    if mixed["ttft_p50_ms"] and split["ttft_p50_ms"]
                    else None)
    tbt_ratio = (round(mixed["bg_tbt_p99_ms"]
                       / split["bg_tbt_p99_ms"], 3)
                 if mixed["bg_tbt_p99_ms"] and split["bg_tbt_p99_ms"]
                 else None)
    return {
        "metric": "disagg_ttft_p50_ms",
        "value": split["ttft_p50_ms"],
        "unit": "ms",
        "vs_baseline": None,
        "recompute_ttft_p50_ms": mixed["ttft_p50_ms"],
        "ttft_speedup_vs_recompute": ttft_speedup,
        "split_bg_tbt_p99_ms": split["bg_tbt_p99_ms"],
        "mixed_bg_tbt_p99_ms": mixed["bg_tbt_p99_ms"],
        "bg_tbt_p99_ratio_mixed_over_split": tbt_ratio,
        "token_parity": parity_ok,
        "kv_xfer_ok": split["kv_xfer_ok"],
        "kv_xfer_ok_mixed": mixed["kv_xfer_ok"],  # must stay 0
        "detail": {"mixed": mixed, "split": split},
        "n_foreground": n_fg,
        "bg_streams": bg_streams,
        "disagg_min_prompt": min_prompt,
        "workload": ("1 prefill + 1 decode CPU replicas + router KV "
                     "handoff vs 2 mixed replicas (RECOMPUTE); "
                     "long-prompt TTFT + background TBT under load"),
    }


def bench_replay(smoke: bool = False) -> dict:
    """``python bench.py replay``: the scenario-sweep workload — ≥3
    distinct trace-spec scenarios replayed open-loop against a local
    CPU fleet (2 replicas + the real router), each scored against
    declarative SLOs; the flash-crowd run is additionally predicted by
    the offline capacity model and checked for agreement within the
    documented band (docs/REPLAY.md), and a live ``/traces`` export is
    round-tripped through spec extraction. Host-only like ``router``:
    replicas are CPU-pinned subprocesses and the bench parent stays
    jax-free.

    Two fleet phases share one bundle export: phase A (global
    ``--max-queue-depth`` bound, no tenant spec) runs steady /
    flash-crowd / shared-prefix + the capacity check — the global
    bound is exactly what the capacity model simulates; phase B
    (tenant spec + quotas) runs the adversarial tenant flood, where
    the assertion is per-tenant ISOLATION (light tenant unharmed, all
    sheds per-tenant)."""
    import tempfile
    import shutil
    import urllib.request

    from pyspark_tf_gke_tpu.replay.capacity import (
        FleetModel,
        calibrate_rates,
        check_agreement,
        predict,
    )
    from pyspark_tf_gke_tpu.replay.driver import replay_spec
    from pyspark_tf_gke_tpu.replay.extract import (
        parse_traces,
        spec_from_traces,
    )
    from pyspark_tf_gke_tpu.replay.generators import synth_spec
    from pyspark_tf_gke_tpu.replay.slo import evaluate_slo
    from pyspark_tf_gke_tpu.replay.spec import SpecRequest, WorkloadSpec
    from pyspark_tf_gke_tpu.router.localfleet import (
        LocalFleet,
        export_tiny_bundle,
    )

    # documented prediction-vs-replay band (docs/REPLAY.md): CPU smoke
    # on a 1-vCPU box — the model predicts queueing SHAPE on measured
    # service rates, not scheduler jitter
    P99_BAND, SHED_ABS, SHED_REL = 5.0, 5, 0.5
    QUEUE_DEPTH = 6
    speedup = 2.0
    scale = 0.5 if smoke else 1.0

    def scenario_summary(name, spec, report, slo):
        verdict = evaluate_slo(report, slo)
        return {
            "scenario": name,
            "n_requests": len(spec.requests),
            "outcomes": report["outcomes"],
            "sheds": report["sheds"],
            "goodput": report["goodput"],
            "ttft_p99_ms": report["ttft_ms"]["p99"],
            "tbt_p99_ms": report["tbt_ms"]["p99"],
            "latency_p99_ms": report["latency_ms"]["p99"],
            "sched_lag_p99_ms": report["sched_lag_ms"]["p99"],
            "tenants": {t: v["ok_rate"]
                        for t, v in report["tenants"].items()},
            "slo_pass": verdict["pass"],
            "slo_failed": [c["name"] for c in verdict["checks"]
                           if not c["ok"]],
        }, report


    tmp = tempfile.mkdtemp(prefix="bench-replay-")
    scenarios, agreement, extract_rt, calibration = [], None, None, None
    try:
        bundle = export_tiny_bundle(os.path.join(tmp, "bundle"))
        # sample EVERYTHING on both hops: the router decides the
        # sampled flag at ingress and the replicas honor it, so a
        # default-sampled router would starve the /traces export the
        # round-trip below feeds on
        trace_args = ("--trace-sample", "1.0", "--trace-slow-ms", "0")

        # ---- phase A: global admission bound -------------------------
        # ONE slot per replica: the capacity check wants textbook
        # queueing (arrivals vs serial service), and parallel slots on
        # a shared-core host add GIL/scheduler cliffs the model
        # rightly refuses to parameterize
        with LocalFleet(2, bundle=bundle, router_args=trace_args,
                        replica_args=(*trace_args,
                                      "--continuous-slots", "1",
                                      "--max-queue-depth",
                                      str(QUEUE_DEPTH))) as fleet:
            fleet.warm()
            # calibrate ONE replica directly at burst-level
            # concurrency with the throughput read (total_slots=1):
            # the capacity model's decode rate must be the rate a
            # replica sustains UNDER load, every host cost folded in
            # (see calibrate_rates)
            calibration = calibrate_rates(fleet.replica_urls[0],
                                          prompt_tokens=20,
                                          output_tokens=16,
                                          concurrency=4,
                                          total_slots=1)
            steady = synth_spec(
                "steady", seed=11, duration_s=8 * scale, rate_rps=2.0,
                prompt_tokens=24, output_tokens=8, max_seq_len=64,
                deadline_ms=10000.0)
            s, _ = scenario_summary(
                "steady", steady,
                replay_spec(steady, fleet.url, speedup=speedup),
                {"goodput_min": 0.9, "errors_max": 0,
                 "ttft_p99_ms": 5000.0})
            scenarios.append(s)

            prefix = synth_spec(
                "shared_prefix", seed=13, duration_s=8 * scale,
                rate_rps=2.0, prompt_tokens=32, output_tokens=8,
                max_seq_len=64, prefix_frac=0.75)
            s, _ = scenario_summary(
                "shared_prefix", prefix,
                replay_spec(prefix, fleet.url, speedup=speedup),
                {"goodput_min": 0.9, "errors_max": 0})
            scenarios.append(s)

            # the routed flash crowd: a dense Poisson burst through
            # the real router. Overload through the gateway is a
            # STORM — replica 429s back replicas off, so the router's
            # own verdicts (no_reroute_target / no_replicas) surface
            # alongside queue_full; all sheds of the same event, not
            # errors. Runs LAST in this fleet: the backoff it leaves
            # behind must not bleed into another scenario.
            crowd = synth_spec(
                "flash_crowd", seed=7, duration_s=10 * scale,
                rate_rps=1.5, prompt_tokens=24, output_tokens=24,
                max_seq_len=64, deadline_ms=15000.0, burst_mult=30.0,
                burst_frac=0.15)
            s, crowd_report = scenario_summary(
                "flash_crowd", crowd,
                replay_spec(crowd, fleet.url, speedup=speedup),
                {"errors_max": 0,
                 "shed_reasons_allowed": ["queue_full",
                                          "no_reroute_target",
                                          "no_replicas"]})
            scenarios.append(s)

            # the capacity check: the flash crowd in its SHARP limit —
            # an instantaneous WALL of simultaneous arrivals sized
            # past one replica's admission capacity (1 slot + 6 queue
            # = 7), replayed DIRECTLY against a replica. The model's
            # contract is the replica's /loadz admission math, which
            # this makes deterministic arithmetic (capacity admits,
            # the rest shed queue_full); the router's Retry-After
            # backoff amplifier under simultaneous arrival is a
            # thread race the model reproduces only in expectation,
            # so the ASSERTED band runs without it. Replica 1 is
            # used after it reports idle — the routed crowd's tail
            # must not inflate the wall's queue.
            wall_n = 18
            wall = WorkloadSpec("flash_crowd_wall", requests=[
                SpecRequest(offset_s=0.0, prompt_tokens=24,
                            output_tokens=24)
                for _ in range(wall_n)]).validate()
            # wait for the WHOLE fleet to quiesce, not just the wall's
            # target: replica 0 still grinding the routed crowd's
            # backlog steals the shared core, which both spreads the
            # wall's open-loop submits and inflates its service times
            fleet.wait_idle()
            wall_report = replay_spec(wall, fleet.replica_urls[1])
            model = FleetModel(
                replicas=1, slots_per_replica=1, kv_pages=None,
                max_queue_depth=QUEUE_DEPTH,
                prefill_tokens_per_sec=calibration[
                    "prefill_tokens_per_sec"],
                decode_tokens_per_sec=calibration[
                    "decode_tokens_per_sec"])
            predicted = predict(model, wall)
            agreement = check_agreement(
                predicted, wall_report, p99_band=P99_BAND,
                shed_band_abs=SHED_ABS, shed_band_rel=SHED_REL)
            agreement["wall_n"] = wall_n
            agreement["predicted_p99_ms"] = (
                predicted["latency_ms"]["p99"])
            agreement["predicted_sheds"] = (
                predicted["outcomes"]["shed"])
            agreement["measured_outcomes"] = wall_report["outcomes"]
            if not agreement["ok"]:
                # the agreement IS part of the flash-crowd scenario's
                # contract (the ISSUE's acceptance criterion): an
                # out-of-band model must not leave a green headline in
                # the evidence trail
                s["slo_pass"] = False
                s["slo_failed"] = [*s["slo_failed"],
                                   "capacity_agreement"]

            # /traces -> spec round trip off replica 0's live ring
            with urllib.request.urlopen(
                    fleet.replica_urls[0]
                    + "/traces?format=jsonl&n=1024",
                    timeout=30) as resp:
                payload = resp.read()
            traces = parse_traces(payload)
            respec = spec_from_traces(traces, name="rt")
            extract_rt = {
                "traces_seen": len(traces),
                "spec_requests": len(respec.requests),
                "replayable": bool(respec.requests),
                "observed": respec.meta.get("observed_outcomes"),
            }

        # ---- phase B: tenant isolation under an adversarial flood ----
        with LocalFleet(
                2, bundle=bundle, router_args=trace_args,
                replica_args=(*trace_args, "--max-queue-depth", "8",
                              "--tenants",
                              "light=3,flood=1:60:120,*=2")) as fleet:
            fleet.warm()
            flood = synth_spec(
                "tenant_flood", seed=17, duration_s=9 * scale,
                rate_rps=1.2, prompt_tokens=24, output_tokens=8,
                max_seq_len=64, flood_mult=6.0)
            s, flood_report = scenario_summary(
                "tenant_flood", flood,
                replay_spec(flood, fleet.url, speedup=speedup),
                {"errors_max": 0,
                 "shed_reasons_allowed": ["tenant_quota",
                                          "tenant_queue_full"]})
            # the isolation claim itself: the light tenant rides
            # through the flood unharmed
            light = flood_report["tenants"].get("light") or {}
            s["light_ok_rate"] = light.get("ok_rate")
            if (light.get("ok_rate") or 0) < 0.9:
                s["slo_pass"] = False
                s["slo_failed"] = [*s["slo_failed"],
                                   "light_tenant_ok_rate"]
            scenarios.append(s)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    passed = sum(1 for s in scenarios if s["slo_pass"])
    return {
        "metric": "replay_scenarios_passed",
        "value": passed,
        "unit": "scenarios",
        "vs_baseline": None,
        "total_scenarios": len(scenarios),
        "speedup": speedup,
        "n_replicas": 2,
        # phase A (the capacity-checked fleet) runs 1 slot/replica by
        # design; phase B keeps the localfleet default of 2
        "replica_slots": {"phase_a": 1, "phase_b": 2},
        "band": {"p99_mult": P99_BAND, "shed_abs": SHED_ABS,
                 "shed_rel": SHED_REL},
        "calibration": calibration,
        "scenarios": scenarios,
        "capacity_agreement": agreement,
        "extract_roundtrip": extract_rt,
        "workload": ("trace-replay scenario sweep: 4 synthetic specs "
                     "vs 2-replica CPU localfleet + router, SLO-"
                     "scored, flash-crowd capacity prediction checked "
                     "in band, /traces export round-tripped to a "
                     "replayable spec"),
    }


def _chaos_alert_timeline(router_url: str, t0_wall: float,
                          kill_at: float, restart_after: float) -> dict:
    """Fold the router watchtower's ``/alertz`` transition history into
    a trail-ready alert timeline: fire/resolve offsets (seconds from
    the chaos schedule's start anchor) and the measured detection /
    resolve latencies for the ``replica_down`` alert the SIGKILL must
    trip. Polls briefly so the resolve (restart re-admission +
    --alert-clear) can land after the replay's tail."""
    import urllib.request

    firing: list = ["?"]
    body: dict = {}
    deadline = time.time() + 20.0
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(router_url + "/alertz?n=256",
                                        timeout=5) as resp:
                body = json.loads(resp.read())
        except OSError:
            break
        firing = [n for n in body.get("firing", [])
                  if n.startswith("replica_down:")]
        if not firing:
            break
        time.sleep(0.5)
    events = []
    fire_off = resolve_off = None
    for rec in body.get("history", []):
        if not rec["alert"].startswith("replica_down:"):
            continue
        off = round(rec["wall"] - t0_wall, 3)
        events.append({"alert": rec["alert"], "to": rec["to"],
                       "offset_s": off})
        if rec["to"] == "firing" and fire_off is None:
            fire_off = off
        if rec["to"] == "resolved":
            resolve_off = off
    return {
        "events": events,
        "fired_offset_s": fire_off,
        "resolved_offset_s": resolve_off,
        "detection_latency_s": (round(fire_off - kill_at, 3)
                                if fire_off is not None else None),
        "resolve_latency_s": (
            round(resolve_off - (kill_at + restart_after), 3)
            if resolve_off is not None else None),
        "still_firing": firing,
    }


def bench_chaos(smoke: bool = False, stream_mix: bool = False) -> dict:
    """``python bench.py chaos``: goodput recovery after a replica kill
    during a flash-crowd replay — the chaos plane's headline scenario
    (docs/CHAOS.md). A seeded flash crowd replays open-loop through the
    real router against a 2-replica CPU fleet while the chaos schedule
    SIGKILLs replica 1 mid-crowd and restarts it; the measurement is
    the ok-rate in three windows (pre-kill / outage / post-restart),
    the durability closure (every request exactly one terminal
    outcome), and the post-scenario invariant verdicts on both
    replicas. Host-only like ``router``/``replay``: needs no TPU.

    ``--stream`` (``stream_mix``): the streaming-mix variant — a
    steady decode-heavy mix of LONG streamed generations sized so open
    streams straddle the kill, measuring **stream outage goodput**:
    the ok-rate of streams IN FLIGHT or arriving during the outage
    window. Before PR 15 these were guaranteed losses (error terminal
    + [DONE]); with the router's journal + continuation splice the
    target is 1.0 — plus the zero-lost-streams gate (no
    eof-without-[DONE] anywhere, ``chaos.invariants
    .check_stream_report``)."""
    from pyspark_tf_gke_tpu.chaos.invariants import (
        check_replica,
        check_report,
        check_stream_report,
        goodput_windows,
    )
    from pyspark_tf_gke_tpu.chaos.runner import ScheduleRunner
    from pyspark_tf_gke_tpu.chaos.spec import synth_chaos
    from pyspark_tf_gke_tpu.replay.driver import replay_spec
    from pyspark_tf_gke_tpu.replay.generators import synth_spec
    from pyspark_tf_gke_tpu.router.localfleet import LocalFleet

    scale = 0.5 if smoke else 1.0
    duration = 18.0 * scale
    kill_at = 6.0 * scale
    restart_after = 5.0 * scale
    if stream_mix:
        # decode-heavy: 24-token streams (prompt 16 + 24 <= 64) at a
        # steady rate a 2-slot replica pair absorbs — the measurement
        # is stream CONTINUITY through the kill, not shed behavior.
        # Decode is paced (30ms/step chaos inject, the smoke gate's
        # trick) so streams take ~0.5s+ and reliably STRADDLE the
        # kill — otherwise the splice path could go unexercised and
        # 1.0 would be vacuous (router_stream_resumes in the entry
        # proves it fired)
        spec = synth_spec("steady", seed=31, duration_s=duration,
                          rate_rps=2.5, prompt_tokens=16,
                          output_tokens=40, max_seq_len=64)
        schedule = synth_chaos(
            "kill_mid_stream", seed=31, duration_s=duration,
            replicas=2, kill_at_s=kill_at, restart_s=restart_after,
            victim=1, name="bench-kill-mid-stream")
        replica_args = ("--max-queue-depth", "12", "--chaos",
                        "engine.device_step:slow%1:0.05")
    else:
        spec = synth_spec("flash_crowd", seed=23, duration_s=duration,
                          rate_rps=2.0, prompt_tokens=16,
                          output_tokens=8, max_seq_len=64,
                          burst_mult=4.0, burst_frac=0.3)
        from pyspark_tf_gke_tpu.chaos.spec import (
            ChaosEvent,
            ChaosSchedule,
        )

        schedule = ChaosSchedule("bench-kill-one", seed=23, events=[
            ChaosEvent(offset_s=kill_at, action="kill",
                       target="replica:1", restart_s=restart_after),
        ]).validate()
        replica_args = ("--continuous-slots", "1",
                        "--max-queue-depth", "6")
    trace_args = ("--trace-sample", "1.0", "--trace-slow-ms", "0")
    # fleet watchtower knobs, tightened so the replica_down alert's
    # full fire -> resolve cycle fits inside the bench run: the trail
    # entry commits the measured detection latency (ISSUE 16's
    # chaos-native acceptance evidence)
    alert_args = ("--probe-interval", "0.3", "--alert-for", "0",
                  "--alert-clear", "2")
    router_resumes = None
    with LocalFleet(2, router_args=(*trace_args, *alert_args),
                    replica_args=(*trace_args, *replica_args)) as fleet:
        fleet.warm()
        runner = ScheduleRunner(schedule, fleet)
        t0_wall = time.time()  # the runner's offset anchor, wall-clock
        with runner:
            report = replay_spec(spec, fleet.url, speedup=1.0,
                                 include_requests=True)
        closure = check_report(report, len(spec.requests))
        fleet.wait_idle(timeout_s=60)
        invariants = [check_replica(u) for u in fleet.replica_urls]
        alert_timeline = _chaos_alert_timeline(fleet.url, t0_wall,
                                               kill_at, restart_after)
        if stream_mix:
            # how many mid-stream deaths the router actually spliced
            # over — the non-vacuousness proof next to goodput 1.0
            import urllib.request as _ur

            with _ur.urlopen(fleet.url + "/metrics", timeout=10) as r:
                mtext = r.read().decode()
            router_resumes = {
                outcome: int(float(line.rsplit(" ", 1)[1]))
                for line in mtext.splitlines()
                for outcome in [line.partition('outcome="')[2]
                                .partition('"')[0]]
                if line.startswith("router_stream_resumes_total{")}
    wins = goodput_windows(
        report, [0.0, kill_at, kill_at + restart_after, duration + 1.0])
    pre, outage, post = wins
    out = {
        "metric": ("chaos_stream_outage_goodput" if stream_mix
                   else "chaos_recovered_goodput"),
        "value": outage["ok_rate"] if stream_mix else post["ok_rate"],
        "unit": "ok_rate",
        "vs_baseline": None,
        "n_requests": len(spec.requests),
        "outcomes": report["outcomes"],
        "sheds": report["sheds"],
        "goodput_overall": report["goodput"],
        "goodput_windows": wins,
        "pre_kill_ok_rate": pre["ok_rate"],
        "outage_ok_rate": outage["ok_rate"],
        "chaos_actions": runner.actions,
        # the watchtower's view of the same scenario: replica_down
        # fire/resolve offsets on the schedule's clock -> the measured
        # alert detection latency, committed with the goodput evidence
        "alert_timeline": alert_timeline,
        "terminal_closure": closure,
        "replica_invariants": invariants,
        "schedule": {"name": schedule.name, "seed": schedule.seed,
                     "kill_at_s": kill_at,
                     "restart_after_s": restart_after},
        "workload": ("replica SIGKILL + restart during a flash-crowd "
                     "replay vs 2-replica CPU localfleet + router: "
                     "windowed goodput (pre/outage/post), exactly-one-"
                     "terminal closure, post-scenario invariant "
                     "checks (docs/CHAOS.md)"),
    }
    if stream_mix:
        streams = check_stream_report(report)
        out["stream_closure"] = streams
        out["stream_resumes_client"] = report.get("stream_resumes", 0)
        out["router_stream_resumes"] = router_resumes
        out["workload"] = (
            "streaming-mix chaos: 24-token greedy streams straddling "
            "a replica SIGKILL + restart vs 2-replica CPU localfleet "
            "+ router — outage-window stream goodput (router journal "
            "+ continuation splice; zero eof-without-[DONE] gate, "
            "docs/SERVING.md 'Stream failover & resume')")
    return out


def bench_autopilot(smoke: bool = False) -> dict:
    """``python bench.py autopilot``: the closed-loop fleet controller
    A/B'd against a static max-size fleet, plus its chaos scenario —
    the evidence run behind docs/AUTOPILOT.md. Host-only like
    ``router``/``replay``/``chaos``.

    Phase A (diurnal A/B): one compressed sinusoidal "day" replayed
    twice against the same bundle — (1) an autopilot fleet that BOOTS
    with one replica (min 1 / max 3, LocalFleetActuator through the
    router's token-gated admin plane, capacity model CALIBRATED
    against a live replica first); (2) a static fleet pinned at the
    max size. Decode is paced (chaos ``slow`` inject) so the diurnal
    peak genuinely overloads one replica and the scale signals carry
    information. The claim: BOTH runs hold the SLO, and the autopilot
    run spends strictly fewer replica-minutes (measured by the
    watchtower's ``replica_minutes`` accumulator over the replay
    window). The static run doubles as the capacity-model anchor:
    ``predict()`` on the calibrated model is checked against its
    measured report within the documented PR-10 agreement band.

    Phase B (chaos): a flash-crowd replay under the autopilot while a
    ``kill_mid_scaleup`` schedule SIGKILLs a boot replica at the
    burst's midpoint — i.e. while the controller is scaling up — and
    restarts it later. Gates: every request reaches EXACTLY one
    terminal outcome (``check_report``), the per-replica invariant
    audits come back green, and the decision ring shows no decision
    applied twice."""
    import shutil
    import tempfile
    import urllib.request

    from pyspark_tf_gke_tpu.chaos.invariants import (
        check_replica,
        check_report,
    )
    from pyspark_tf_gke_tpu.chaos.runner import ScheduleRunner
    from pyspark_tf_gke_tpu.chaos.spec import synth_chaos
    from pyspark_tf_gke_tpu.replay.capacity import (
        FleetModel,
        calibrate_rates,
        check_agreement,
        predict,
    )
    from pyspark_tf_gke_tpu.replay.driver import replay_spec
    from pyspark_tf_gke_tpu.replay.generators import synth_spec
    from pyspark_tf_gke_tpu.replay.slo import evaluate_slo
    from pyspark_tf_gke_tpu.router.autopilot import (
        Autopilot,
        LocalFleetActuator,
    )
    from pyspark_tf_gke_tpu.router.localfleet import (
        LocalFleet,
        export_tiny_bundle,
    )

    scale = 0.5 if smoke else 1.0
    duration = 48.0 * scale
    MAX_REPLICAS = 3
    TOKEN = "bench-autopilot"
    # same prediction-vs-replay band as bench_replay (docs/REPLAY.md)
    P99_BAND, SHED_ABS, SHED_REL = 5.0, 5, 0.5
    # decode paced at 50 ms/step so one 1-slot replica saturates near
    # the diurnal peak (~2.2 rps x ~0.5 s service) — the scale signals
    # must carry real information, not CPU-tiny-model noise
    replica_args = ("--continuous-slots", "1", "--max-queue-depth",
                    "32", "--chaos", "engine.device_step:slow%1:0.05")
    router_args = ("--admin-token", TOKEN,
                   "--probe-interval", "0.3", "--probe-timeout", "1.0",
                   "--fail-threshold", "2",
                   "--alert-for", "0", "--alert-clear", "2.0")
    diurnal = synth_spec("diurnal", seed=41, duration_s=duration,
                         rate_rps=1.2, prompt_tokens=16,
                         output_tokens=8, max_seq_len=64)
    diurnal_slo = {"goodput_min": 0.9, "errors_max": 0,
                   "shed_reasons_allowed": ["queue_full",
                                            "no_reroute_target",
                                            "no_replicas"]}

    def _fleet_rollup(url):
        with urllib.request.urlopen(url + "/fleetz", timeout=5) as r:
            return json.loads(r.read()).get("fleet") or {}

    def _rm(url):
        return float(_fleet_rollup(url).get("replica_minutes") or 0.0)

    def _mk_autopilot(fleet, model, **kw):
        def source():
            with urllib.request.urlopen(fleet.url + "/fleetz",
                                        timeout=5) as r:
                fz = json.loads(r.read())
            with urllib.request.urlopen(fleet.url + "/alertz",
                                        timeout=5) as r:
                az = json.loads(r.read())
            return fz, az

        return Autopilot(
            model, source=source,
            actuator=LocalFleetActuator(fleet, admin_token=TOKEN),
            tick_s=1.0, **kw)

    def _decision_summary(ap):
        acts = [d for d in ap.decisions if d["action"] != "none"]
        return {
            "decisions": len(ap.decisions),
            "scale_ups_applied": sum(
                1 for d in acts
                if d["action"] == "scale_up" and d["applied"]),
            "scale_downs_applied": sum(
                1 for d in acts
                if d["action"] == "scale_down" and d["applied"]),
            "vetoes": sorted({v for d in ap.decisions
                              for v in d["vetoes"]}),
            "peak_desired": max(
                (d["plan"]["replicas_needed"] for d in ap.decisions),
                default=0),
        }

    tmp = tempfile.mkdtemp(prefix="bench-autopilot-")
    calibration = None
    try:
        bundle = export_tiny_bundle(os.path.join(tmp, "bundle"))

        # ---- phase A1: the autopilot fleet rides the diurnal ---------
        with LocalFleet(1, bundle=bundle, router_args=router_args,
                        replica_args=replica_args) as fleet:
            fleet.warm()
            # the model the controller plans with is MEASURED, slowdown
            # and host costs folded in (PR-10 calibration contract)
            calibration = calibrate_rates(fleet.replica_urls[0],
                                          prompt_tokens=20,
                                          output_tokens=16,
                                          concurrency=4, total_slots=1)
            model = FleetModel(
                replicas=1, slots_per_replica=1, max_queue_depth=32,
                prefill_tokens_per_sec=calibration[
                    "prefill_tokens_per_sec"],
                decode_tokens_per_sec=calibration[
                    "decode_tokens_per_sec"])
            ap = _mk_autopilot(fleet, model, min_replicas=1,
                               max_replicas=MAX_REPLICAS,
                               stabilization_s=4.0, cooldown_s=6.0)
            rm0 = _rm(fleet.url)
            ap.start()
            try:
                ap_report = replay_spec(diurnal, fleet.url,
                                        speedup=1.0)
            finally:
                ap.stop()
            ap_minutes = _rm(fleet.url) - rm0
            ap_verdict = evaluate_slo(ap_report, diurnal_slo)
            ap_decisions = _decision_summary(ap)

        # ---- phase A2: the static max-size fleet, same day -----------
        with LocalFleet(MAX_REPLICAS, bundle=bundle,
                        router_args=router_args,
                        replica_args=replica_args) as fleet:
            fleet.warm()
            rm0 = _rm(fleet.url)
            st_report = replay_spec(diurnal, fleet.url, speedup=1.0)
            st_minutes = _rm(fleet.url) - rm0
            st_verdict = evaluate_slo(st_report, diurnal_slo)
        predicted = predict(
            FleetModel(
                replicas=MAX_REPLICAS, slots_per_replica=1,
                max_queue_depth=32,
                prefill_tokens_per_sec=calibration[
                    "prefill_tokens_per_sec"],
                decode_tokens_per_sec=calibration[
                    "decode_tokens_per_sec"]),
            diurnal)
        agreement = check_agreement(
            predicted, st_report, p99_band=P99_BAND,
            shed_band_abs=SHED_ABS, shed_band_rel=SHED_REL)
        agreement["predicted_p99_ms"] = predicted["latency_ms"]["p99"]
        agreement["measured_p99_ms"] = st_report["latency_ms"]["p99"]

        # ---- phase B: kill a replica mid-scale-up --------------------
        crowd_dur = 30.0 * scale
        crowd = synth_spec("flash_crowd", seed=29, duration_s=crowd_dur,
                           rate_rps=1.0, prompt_tokens=16,
                           output_tokens=8, max_seq_len=64,
                           burst_mult=8.0, burst_frac=0.3)
        schedule = synth_chaos(
            "kill_mid_scaleup", seed=29, duration_s=crowd_dur,
            replicas=2, kill_at_s=0.5 * crowd_dur,
            restart_s=0.25 * crowd_dur, name="bench-kill-mid-scaleup")
        with LocalFleet(2, bundle=bundle, router_args=router_args,
                        replica_args=replica_args) as fleet:
            fleet.warm()
            model = FleetModel(
                replicas=2, slots_per_replica=1, max_queue_depth=32,
                prefill_tokens_per_sec=calibration[
                    "prefill_tokens_per_sec"],
                decode_tokens_per_sec=calibration[
                    "decode_tokens_per_sec"])
            # stabilization pinned past the run: phase B's story is the
            # kill during scale-UP; drains are phase A's (and the smoke
            # gate's) story, and a mid-chaos drain would tear down the
            # very replicas the invariant audit wants to interrogate
            ap = _mk_autopilot(fleet, model, min_replicas=2,
                               max_replicas=MAX_REPLICAS,
                               stabilization_s=10 * crowd_dur,
                               cooldown_s=6.0)
            runner = ScheduleRunner(schedule, fleet)
            ap.start()
            try:
                with runner:
                    chaos_report = replay_spec(crowd, fleet.url,
                                               speedup=1.0,
                                               include_requests=True)
            finally:
                ap.stop()
            closure = check_report(chaos_report, len(crowd.requests))
            fleet.wait_idle(timeout_s=60)
            invariants = [check_replica(u) for u in fleet.replica_urls]
            chaos_decisions = _decision_summary(ap)
            ids = [d["id"] for d in ap.decisions]
            chaos_decisions["ids_unique"] = len(ids) == len(set(ids))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    minutes_ratio = (round(ap_minutes / st_minutes, 4)
                     if st_minutes > 0 else None)
    ok = bool(
        ap_verdict["pass"] and st_verdict["pass"]
        and minutes_ratio is not None and minutes_ratio < 1.0
        and agreement["ok"] and closure["ok"]
        and all(inv["ok"] for inv in invariants)
        and chaos_decisions["ids_unique"])
    return {
        "metric": "autopilot_minutes_vs_static",
        "value": minutes_ratio,
        "unit": "ratio",
        "vs_baseline": None,
        "pass": ok,
        "n_requests": {"diurnal": len(diurnal.requests),
                       "flash_crowd": len(crowd.requests)},
        "calibration": calibration,
        "diurnal": {
            "autopilot": {
                "replica_minutes": round(ap_minutes, 4),
                "goodput": ap_report["goodput"],
                "outcomes": ap_report["outcomes"],
                "latency_p99_ms": ap_report["latency_ms"]["p99"],
                "slo_pass": ap_verdict["pass"],
                "slo_failed": [c["name"] for c in ap_verdict["checks"]
                               if not c["ok"]],
                "decisions": ap_decisions,
            },
            "static": {
                "replicas": MAX_REPLICAS,
                "replica_minutes": round(st_minutes, 4),
                "goodput": st_report["goodput"],
                "outcomes": st_report["outcomes"],
                "latency_p99_ms": st_report["latency_ms"]["p99"],
                "slo_pass": st_verdict["pass"],
                "slo_failed": [c["name"] for c in st_verdict["checks"]
                               if not c["ok"]],
            },
        },
        "capacity_agreement": agreement,
        "chaos": {
            "schedule": {"name": schedule.name, "seed": schedule.seed,
                         "kill_at_s": 0.5 * crowd_dur,
                         "restart_after_s": 0.25 * crowd_dur},
            "outcomes": chaos_report["outcomes"],
            "sheds": chaos_report["sheds"],
            "goodput": chaos_report["goodput"],
            "terminal_closure": closure,
            "replica_invariants": invariants,
            "decisions": chaos_decisions,
        },
        "workload": ("closed-loop autopilot vs static max-size fleet "
                     "on a compressed diurnal day (SLO + replica-"
                     "minutes A/B, calibrated capacity model checked "
                     "in the PR-10 band), then a flash-crowd replay "
                     "with a replica SIGKILLed mid-scale-up — exactly-"
                     "one-terminal closure + invariant audits "
                     "(docs/AUTOPILOT.md)"),
    }


# ---- orchestrator ----------------------------------------------------------


_VALUE_FLAGS = ("--seq", "--kv-heads", "--beams", "--gamma")


def _positionals(argv) -> list:
    """Positional args with flags AND their values stripped (so
    ``--seq 2048`` never masquerades as the workload name)."""
    out, skip = [], False
    for a in argv:
        if skip:
            skip = False
        elif a in _VALUE_FLAGS:
            skip = True
        elif not a.startswith("--"):
            out.append(a)
    return out


def _normalize_argv(argv) -> list:
    """Canonical identity of a bench invocation: drop the flags that
    don't change WHAT is measured, name the bare flagship explicitly,
    and sort flags (keeping value flags paired) so an operator's
    hand-typed flag order still matches the matrix entry. Two cnn
    variants (e.g. ``--bf16-moments``) normalize differently — they are
    different measurements. ``--smoke`` is KEPT: a tiny-shape smoke
    measurement is its own identity (recordable via ``--history``),
    and it must never be looked up as — or stand in for — the
    full-shape entry (the variant-regression guard matches on this
    identity)."""
    drop = ("--no-history", "--history")
    pos, pairs = [], []
    i = 0
    args = list(argv)
    while i < len(args):
        a = args[i]
        if a in drop:
            i += 1
        elif a in _VALUE_FLAGS:
            pairs.append((a, args[i + 1] if i + 1 < len(args) else ""))
            i += 2
        elif a.startswith("--"):
            pairs.append((a, ""))
            i += 1
        else:
            pos.append(a)
            i += 1
    out = pos or ["cnn"]
    for flag, val in sorted(pairs):
        out.append(flag)
        if val:
            out.append(val)
    return out


def _load_history() -> list:
    """Parse the evidence trail once, per-line tolerant: one truncated
    line (a crash mid-append — exactly the outage scenario this serves)
    must not discard every valid measurement before it."""
    entries = []
    try:
        with open(HISTORY_PATH) as fh:
            for ln in fh:
                try:
                    e = json.loads(ln)
                except ValueError:
                    continue
                if isinstance(e, dict) and "ts" in e and "result" in e:
                    entries.append(e)
    except OSError:
        pass
    return entries


def _latest_history(argv):
    """Most recent committed evidence-trail entry for EXACTLY this
    invocation (normalized argv match — a ``cnn --bf16-moments`` entry
    must never stand in for the f32 parity flagship). None if the trail
    has none. The variant-regression guard's baseline lookup."""
    want = _normalize_argv(argv)
    for entry in reversed(_load_history()):
        if _normalize_argv(entry.get("argv", []) or []) == want:
            return entry
    return None


def _error_json(argv, stage: str, detail: str, rc: int = 1) -> dict:
    norm = _normalize_argv(argv)
    workload = norm[0]
    return {
        "metric": CNN_METRIC if workload == "cnn"
        else f"{workload}_bench",
        "value": None,
        "unit": "images/sec/chip" if workload == "cnn" else "examples/sec/chip",
        "vs_baseline": None,
        # full normalized argv so two variants of one workload (e.g.
        # cnn vs cnn --bf16-moments) stay distinguishable in error lines
        "argv": norm,
        # the failing command's exit context, compact and first-class —
        # NOT a raw output tail: a driver artifact records whatever this
        # line says, and a blob doesn't parse. detail is clamped so the
        # WHOLE line stays inside a tail -c 2000 window.
        "error": {"stage": stage, "detail": detail[-600:], "rc": rc,
                  "cmd": "python bench.py " + " ".join(norm)},
    }


# Kernel/config VARIANTS of a committed baseline workload, for the
# regression guard below: same metric, same unit, same workload shape —
# only the lever under test differs, so value ratios are meaningful.
# (Workloads that change the SHAPE — bert --seq, cb --chunked-prefill's
# mixed prompt mix — are deliberately absent.)
VARIANT_BASELINES = {
    "resnet50 --fused-bn": ["resnet50"],
    "resnet50 --fused-bn3": ["resnet50"],
    "resnet50 --gn": ["resnet50"],
    "resnet50 --nf": ["resnet50"],
    "resnet50 --s2d": ["resnet50"],
    "cnn --bf16-moments": ["cnn"],
    "cnn --adafactor": ["cnn"],
    "cb --paged": ["cb"],
    # the async engine core's A/B pair: the serial (unpipelined) loop
    # measured against the committed pipelined `cb` baseline — a
    # serial run ABOVE the pipelined baseline would mean the overlap
    # is hurting, the exact inversion this guard exists to flag
    "cb --serial": ["cb"],
    "generate --kv-heads 2": ["generate"],
    "generate --int8 --kv-heads 2": ["generate", "--kv-heads", "2"],
    "generate --int8 --int8-kv --kv-heads 2":
        ["generate", "--int8", "--kv-heads", "2"],
}

REGRESSION_THRESHOLD = 0.9  # variant >10% below baseline -> flagged


def annotate_variant_regression(argv, result: dict) -> None:
    """A/B guard for variant workloads: compare a just-measured variant
    against its baseline workload's latest COMMITTED trail entry, emit
    a delta line (stderr), and attach ``vs_variant_baseline`` — with
    ``"regression": true`` when the variant lands more than 10% below.
    Motivation: ``resnet50 --fused-bn`` once recorded 1481 ex/s
    against the 2431 plain baseline with no flag raised anywhere —
    a 0.61x kernel-variant regression that only a human diffing trail
    entries could catch. Mutates ``result`` in place; silently a no-op
    when there is no baseline entry or the units mismatch (a guard must
    never block the measurement it guards)."""
    if "--smoke" in argv or result.get("value") is None:
        return
    key = " ".join(_normalize_argv(argv))
    base_argv = VARIANT_BASELINES.get(key)
    if base_argv is None:
        return
    base = _latest_history(base_argv)
    if base is None:
        return
    r = base.get("result") or {}
    base_value = r.get("value")
    if not base_value or r.get("unit") != result.get("unit"):
        return
    ratio = float(result["value"]) / float(base_value)
    ab = {
        "baseline_argv": " ".join(_normalize_argv(base_argv)),
        "baseline_value": base_value,
        "baseline_ts": base.get("ts"),
        "ratio": round(ratio, 3),
    }
    regressed = ratio < REGRESSION_THRESHOLD
    if regressed:
        ab["regression"] = True
        result["regression"] = True
    result["vs_variant_baseline"] = ab
    log(f"variant A/B: {key} = {result['value']} {result.get('unit')} "
        f"vs [{ab['baseline_argv']}] = {base_value} -> {ab['ratio']}x"
        + (" REGRESSION (>10% below committed baseline)"
           if regressed else ""))


def append_history(argv, result: dict,
                   host_load_pre: Optional[float] = None) -> None:
    """Append a successful measurement to the committed evidence trail.

    Numbers that exist only as markdown claims cannot be checked, so
    every successful run is recorded verbatim — full result JSON + UTC
    timestamp + argv — the moment it completes, into
    ``tools/bench_history.jsonl`` (committed). README/PARITY cite these
    entries by timestamp. ``--smoke`` runs (tiny-shape plumbing checks)
    and explicit ``--no-history`` runs are not measurements and are not
    recorded — EXCEPT a smoke run invoked with an explicit
    ``--history`` opt-in: ROADMAP's environment note makes CPU-smoke
    A/Bs the perf oracle on this box, and some baselines (the item-4
    ``step_phases`` host-overhead fraction) are only capturable that
    way. The recorded argv keeps ``--smoke`` (a smoke measurement is
    its own identity — it must never stand in for the full one) but
    drops the ``--history`` marker (it doesn't change what was
    measured)."""
    if result.get("value") is None or "--no-history" in argv:
        return
    if "--smoke" in argv and "--history" not in argv:
        return
    entry = {
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "argv": [a for a in argv if a != "--history"],
        "result": result,
    }
    # Host-contention disclosure: dispatch-bound step times on this
    # 1-vCPU host inflate under concurrent compilation (the 2026-08-02
    # cnn entry measured 1,898 img/s vs ~3,470 idle because a test run
    # shared the core). Record the 1-minute load average both as the
    # measurement STARTED (host_load_1m_pre, sampled by the runner
    # before the workload subprocess launched) and at append time
    # (host_load_1m) — a competitor that exits before the run finishes
    # dilutes out of the post-run average but is still visible in the
    # pre sample, so contention DURING the run is captured, not only
    # contention that survives to append (ADVICE.md round 5). loadavg
    # ~1 = this process alone; >~1.5 = something else was competing —
    # on EITHER sample.
    try:
        entry["host_load_1m"] = round(os.getloadavg()[0], 2)
    except OSError:  # pragma: no cover - non-POSIX
        pass
    if host_load_pre is not None:
        entry["host_load_1m_pre"] = round(float(host_load_pre), 2)
    try:
        # The obs event-trail primitive: ONE O_APPEND write per line, so
        # a capture racing a second bench process interleaves whole
        # lines, never torn ones.
        from pyspark_tf_gke_tpu.obs.events import append_jsonl_line

        append_jsonl_line(HISTORY_PATH, entry)
        log(f"history: appended to {HISTORY_PATH}")
    except OSError as exc:  # pragma: no cover - read-only checkouts
        log(f"history append failed: {exc!r}")


# Matrix order = capture priority for `bench.py all`: the flagship leads
# (parity anchor + vs_baseline); then the high-information block —
# workloads with no trail entry yet and trail-backed workloads whose
# IMPLEMENTATION changed since their last entry; then the
# already-measured re-confirmations. Identity is per-workload argv —
# order never affects what a trail entry means.
ALL_WORKLOADS = (
    ["cnn"],
    # --- high-information block (unmeasured or changed-since-entry) ---
    # the round-4 verdict's named fix: Pallas 1x1-conv kernels absorbing
    # the BatchNorm passes (same BN semantics, fused pass structure)
    ["resnet50", "--fused-bn"],
    # ...and the full form: the stride-1 3x3 convs are Pallas too
    # (norm1 never materializes; norm2 stats from the conv epilogue)
    ["resnet50", "--fused-bn3"],
    ["resnet50", "--gn"],  # disclosed norm-semantics lever (mfu_probe)
    # normalizer-free variant: scaled WS convs, the activation-norm HBM
    # pass deleted outright (the lever PARITY's fused negative points at)
    ["resnet50", "--nf"],
    ["cnn", "--adafactor"],  # factored-second-moment traffic lever
    ["cb"],  # continuous batching: chunk x depth autotune vs whole-batch
    # serial A/B reference for the async engine core: identical engine
    # with the one-deep pipeline disabled (pipeline_depth=0), headline
    # pinned to the unpipelined loop — the committed denominator for
    # the host-overhead claim and the inversion guard's variant side
    ["cb", "--serial"],
    # paged KV cache A/B: same slot count, engine on the page pool +
    # ragged paged_attention decode; cache bytes tracked by pages in use
    ["cb", "--paged"],
    # chaos A/B: goodput + p99 with faults injected into the serving
    # driver loop vs clean — what one engine rebuild costs the endpoint
    ["cb", "--chaos"],
    # chunked-prefill A/B: mixed prompt lengths through the paged
    # engine, pieces + step budget vs monolithic prefill — p50/p99
    # time-between-tokens is the tail this exists to flatten
    ["cb", "--chunked-prefill"],
    # radix prefix-cache A/B: shared system prompt x unique suffixes,
    # refcounted page sharing vs re-prefill-from-zero — computed
    # prefill tokens must be ∝ unique suffix only (host-measurable:
    # the win is prefill-FLOP elision, backend-agnostic)
    ["cb", "--prefix-cache"],
    # in-engine speculative decoding A/B: trained target/draft pair,
    # decode-heavy mix, k draft proposals + one multi-query verify per
    # slot-round vs plain decode at equal settings — token parity
    # asserted, accept rate reported (host-measurable: the win is
    # verify-forwards-per-token elision; the CPU ratio is a lower
    # bound for HBM-bound chips)
    ["cb", "--spec"],
    # replica-router data plane: 1 router + 2 CPU replicas vs direct,
    # plus the kill-one-replica failover goodput (host-only, like io)
    ["router"],
    # trace-replay scenario sweep: ≥3 synthetic specs vs a 2-replica
    # CPU localfleet, SLO-scored, flash-crowd capacity prediction
    # checked in band, /traces export round-tripped (host-only)
    ["replay"],
    # chaos durability: replica SIGKILL + restart during a flash-crowd
    # replay — windowed goodput recovery, exactly-one-terminal closure,
    # post-scenario invariant checks (host-only)
    ["chaos"],
    # streaming-mix chaos: long greedy streams straddling the kill —
    # outage-window STREAM goodput through the router's journal +
    # continuation splice (zero lost streams; host-only)
    ["chaos", "--stream"],
    # prefill/decode disaggregation A/B: role-split fleet + KV-page
    # handoff over the router vs mixed fleet (RECOMPUTE) — long-prompt
    # TTFT and background decode TBT under load, token parity asserted
    # (host-only)
    ["disagg"],
    # closed-loop autopilot A/B: diurnal day vs static max-size fleet
    # (SLO + replica-minutes, capacity model in band) + flash-crowd
    # with a replica killed mid-scale-up (host-only)
    ["autopilot"],
    ["spec"],  # device-loop tok/s + the 0.75-skew fixture's acceptance
    ["generate", "--beams", "4"],  # broadcast-select reorder rebuild A/B
    # --- measured re-confirmations ---
    ["resnet50"],
    ["cnn", "--bf16-moments"],  # disclosed optimizer-traffic lever
    ["resnet50", "--s2d"],  # disclosed stem-layout lever
    ["vit"],
    ["bert"],
    ["bert", "--seq", "2048"],
    ["bert", "--no-flash", "--seq", "2048"],
    ["generate"],
    ["generate", "--kv-heads", "2"],
    ["generate", "--kv-heads", "2", "--int8"],
    ["generate", "--kv-heads", "2", "--int8", "--int8-kv"],
    ["io"],
)


# workloads that never touch a device: io is pure TFRecord I/O, and the
# router/replay/chaos/autopilot/disagg fleets are CPU-pinned
# subprocesses by design — they run anywhere. Every other workload is a
# DEVICE workload: without --smoke its --run child requires a TPU.
HOST_ONLY_WORKLOADS = ("io", "router", "replay", "chaos", "autopilot",
                       "disagg")


def orchestrate_all(extra) -> int:
    """Run EVERY bench workload back to back, appending each successful
    measurement to the history trail (tools/bench_history.jsonl). Emits
    one JSON line per workload on stdout and a final summary line; rc=0
    if every workload measured."""
    failures = 0
    for argv in ALL_WORKLOADS:
        log(f"=== bench matrix: {' '.join(argv)} ===")
        failures += 1 if orchestrate([*argv, *extra]) else 0
    print(json.dumps(
        {"metric": "bench_all", "value": len(ALL_WORKLOADS) - failures,
         "unit": "workloads_measured", "vs_baseline": None,
         "total": len(ALL_WORKLOADS), "failures": failures}))
    return 1 if failures else 0


def orchestrate(argv) -> int:
    """Run one workload ONCE in a ``--run`` child and print its JSON
    line. The child owns the chip for its lifetime; this parent stays
    off jax. A child that fails (no TPU for a device workload, a
    compiler error, a timeout) yields an error line with ``value: null``
    and exit code 1 — never a retry on another backend, never an old
    trail entry."""
    positionals = _positionals(argv)
    workload = positionals[0] if positionals else "cnn"
    if workload == "all":
        return orchestrate_all([a for a in argv if a != "all"])

    cmd = [sys.executable, os.path.abspath(__file__), "--run", *argv]
    # loadavg as the measurement STARTS: contention early in a long
    # run, or from a competitor that exits before append time, is
    # invisible in the append-time sample alone (ADVICE.md round 5)
    try:
        pre_load = os.getloadavg()[0]
    except OSError:  # pragma: no cover - non-POSIX
        pre_load = None
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=RUN_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        detail = f"bench run timed out after {RUN_TIMEOUT_S}s"
        log(f"[run] {detail}")
        print(json.dumps(_error_json(list(argv), "run", detail)))
        return 1
    sys.stderr.write(proc.stderr)
    line = next(
        (ln for ln in reversed(proc.stdout.splitlines())
         if ln.startswith("{")), None)
    if proc.returncode == 0 and line:
        try:
            result = json.loads(line)
        except ValueError as exc:
            log(f"history: stdout line was not JSON, not recorded: "
                f"{exc!r}")
            print(line)
            return 0
        # variant regression guard BEFORE print/append: the flag
        # must reach both the stdout artifact and the trail entry.
        # Tolerant: a malformed baseline entry must never cost the
        # just-measured result (minutes of chip time).
        try:
            annotate_variant_regression(argv, result)
        except Exception as exc:  # noqa: BLE001
            log(f"variant A/B guard failed (ignored): {exc!r}")
        print(json.dumps(result))
        append_history(argv, result, host_load_pre=pre_load)
        return 0
    detail = f"rc={proc.returncode}: {proc.stderr.strip()[-800:]}"
    log(f"[run] failed: {detail}")
    print(json.dumps(_error_json(list(argv), "run", detail,
                                 rc=proc.returncode)))
    return 1


def _claim_device(workload: str, smoke: bool) -> None:
    """``--run`` child, before any backend use: pick the platform the
    invocation is allowed to measure on. ``--smoke`` pins the CPU fake
    slice; a device workload requires a TPU and exits non-zero off it (a
    CPU timing is never written under a device metric's name)."""
    if smoke:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
        return
    if workload in HOST_ONLY_WORKLOADS:
        return
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py {workload}: a device workload measures on a TPU; "
            f"JAX found {dev.platform!r} ({dev.device_kind}). Use --smoke "
            f"for the CPU plumbing check.")


def run_bench(argv) -> dict:
    args = _positionals(argv)
    smoke = "--smoke" in argv
    workload = args[0] if args else "cnn"
    if "--bf16-moments" in argv and workload != "cnn":
        # a silently-ignored flag would record a mislabeled identity
        # into the evidence trail (argv IS the measurement identity)
        raise SystemExit("--bf16-moments applies to the cnn workload only")
    if "--adafactor" in argv and workload != "cnn":
        raise SystemExit("--adafactor applies to the cnn workload only")
    if "--paged" in argv and workload != "cb":
        raise SystemExit("--paged applies to the cb workload only")
    if "--chaos" in argv and workload != "cb":
        raise SystemExit("--chaos applies to the cb workload only")
    if "--chunked-prefill" in argv and workload != "cb":
        raise SystemExit("--chunked-prefill applies to the cb workload only")
    if "--chunked-prefill" in argv and ("--paged" in argv
                                        or "--chaos" in argv):
        raise SystemExit("--chunked-prefill is its own A/B (the engine "
                         "under it is already paged)")
    if "--serial" in argv and workload != "cb":
        raise SystemExit("--serial applies to the cb workload only")
    if "--serial" in argv and any(f in argv for f in (
            "--paged", "--chaos", "--chunked-prefill", "--prefix-cache",
            "--spec")):
        raise SystemExit("--serial is the async-core A/B reference "
                         "(unpipelined loop) of the plain cb workload")
    if "--prefix-cache" in argv and workload != "cb":
        raise SystemExit("--prefix-cache applies to the cb workload only")
    if "--prefix-cache" in argv and ("--paged" in argv or "--chaos" in argv
                                     or "--chunked-prefill" in argv):
        raise SystemExit("--prefix-cache is its own A/B (the engine under "
                         "it is already paged + chunked)")
    if "--spec" in argv and workload != "cb":
        raise SystemExit("--spec applies to the cb workload only "
                         "(the standalone `spec` workload benches "
                         "models/speculative.py)")
    if "--spec" in argv and any(f in argv for f in (
            "--paged", "--chaos", "--chunked-prefill", "--prefix-cache")):
        raise SystemExit("--spec is its own A/B (the engine under it is "
                         "already paged)")
    if "--s2d" in argv and workload != "resnet50":
        raise SystemExit("--s2d applies to the resnet50 workload only")
    if "--gn" in argv and workload != "resnet50":
        raise SystemExit("--gn applies to the resnet50 workload only")
    if "--fused-bn" in argv and workload != "resnet50":
        raise SystemExit("--fused-bn applies to the resnet50 workload only")
    if "--fused-bn3" in argv and workload != "resnet50":
        raise SystemExit("--fused-bn3 applies to the resnet50 workload only")
    if ("--fused-bn" in argv or "--fused-bn3" in argv) and "--gn" in argv:
        raise SystemExit("--fused-bn/--fused-bn3 and --gn are exclusive")
    if "--fused-bn" in argv and "--fused-bn3" in argv:
        raise SystemExit("--fused-bn and --fused-bn3 are exclusive variants")
    if "--nf" in argv:
        if workload != "resnet50":
            raise SystemExit("--nf applies to the resnet50 workload only")
        if any(f in argv for f in ("--gn", "--fused-bn", "--fused-bn3")):
            raise SystemExit("--nf is exclusive with the other norm variants")
    if workload == "cnn":
        mu = None
        if "--bf16-moments" in argv:
            import jax.numpy as jnp

            mu = jnp.bfloat16
        opt = "adafactor" if "--adafactor" in argv else "adam"
        if mu is not None and opt != "adam":
            raise SystemExit(
                "--bf16-moments is an Adam lever; pick one of "
                "--bf16-moments / --adafactor")
        # --smoke shrinks the flagship run too (small batch, few steps,
        # no secondary throughput-batch pass; batch stays divisible by
        # the fake slice's 8 devices).
        return (main(batch_size=8, steps=2, throughput_batch=0,
                     mu_dtype=mu, optimizer=opt)
                if smoke else main(mu_dtype=mu, optimizer=opt))
    if workload == "io":
        return bench_io(smoke=smoke)
    if workload == "router":
        return bench_router(smoke=smoke)
    if workload == "replay":
        return bench_replay(smoke=smoke)
    if workload == "chaos":
        return bench_chaos(smoke=smoke, stream_mix="--stream" in argv)
    if workload == "autopilot":
        return bench_autopilot(smoke=smoke)
    if workload == "disagg":
        return bench_disagg(smoke=smoke)
    if workload == "cb":
        if "--chunked-prefill" in argv:
            return bench_chunked_prefill(smoke=smoke)
        if "--prefix-cache" in argv:
            return bench_prefix_cache(smoke=smoke)
        if "--spec" in argv:
            return bench_spec_cb(smoke=smoke)
        return bench_continuous(smoke=smoke, paged="--paged" in argv,
                                chaos="--chaos" in argv,
                                serial="--serial" in argv)
    if workload == "spec":
        gamma = 4
        if "--gamma" in argv:
            try:
                gamma = int(argv[argv.index("--gamma") + 1])
                if gamma < 1:
                    raise ValueError
            except (IndexError, ValueError):
                raise SystemExit("usage: bench.py spec --gamma <positive int>")
        return bench_spec_decode(smoke=smoke, gamma=gamma)
    if workload == "generate":
        kv = None
        if "--kv-heads" in argv:
            try:
                kv = int(argv[argv.index("--kv-heads") + 1])
                if kv <= 0:
                    raise ValueError
            except (IndexError, ValueError):
                raise SystemExit(
                    "usage: bench.py generate --kv-heads <positive int>")
        beams = 0
        if "--beams" in argv:
            try:
                beams = int(argv[argv.index("--beams") + 1])
                if beams < 1:
                    raise ValueError
            except (IndexError, ValueError):
                raise SystemExit("usage: bench.py generate --beams <positive int>")
        return bench_decode(smoke=smoke, kv_heads=kv, int8="--int8" in argv,
                            num_beams=beams, int8_kv="--int8-kv" in argv)
    use_flash = True if "--flash" in argv else (False if "--no-flash" in argv else None)
    seq = None
    if "--seq" in argv:
        try:
            seq = int(argv[argv.index("--seq") + 1])
        except (IndexError, ValueError):
            raise SystemExit("usage: bench.py bert --seq <int>  (e.g. --seq 2048)")
    # resnet50 and vit get the same disclosed throughput-batch secondary
    # as the flagship (batch 256 vs the BASELINE config's 64)
    tb = 256 if (workload in ("resnet50", "vit") and not smoke) else 0
    return bench_workload(workload, steps=2 if smoke else 50, smoke=smoke,
                          use_flash=use_flash, seq_override=seq,
                          throughput_batch=tb, s2d="--s2d" in argv,
                          norm_variant=("gn" if "--gn" in argv
                                        else "fused3" if "--fused-bn3" in argv
                                        else "fused" if "--fused-bn" in argv
                                        else "nf" if "--nf" in argv
                                        else "bn"))


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--run" in argv:
        argv = [a for a in argv if a != "--run"]
        from pyspark_tf_gke_tpu.utils.compile_cache import (
            enable_compile_cache,
        )

        enable_compile_cache()
        _claim_device((_positionals(argv) or ["cnn"])[0], "--smoke" in argv)
        print(json.dumps(run_bench(argv)))
    else:
        sys.exit(orchestrate(argv))
