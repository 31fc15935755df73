"""Causal-LM pretraining entry point: raw text → packed tokens → decoder.

Completes the model-family matrix the same way ``bert_finetune`` does
for the encoder: text files (local or ``gs://``) stream through
``data.text`` (tokenize → eos-pack → shuffle → batch), the model is the
decoder-only ``models/causal_lm.py`` (flash attention on TPU, GQA
optional), and the loss is either the dense next-token cross-entropy or
the chunked large-vocab loss (``ops/chunked_ce.py``, ``--vocab-chunks``)
that never materializes ``[B, S, V]`` logits.

No counterpart in the reference (no language models — SURVEY §2b); run
artifacts (history.json, orbax checkpoints, heartbeat) follow the same
conventions as the other entry points, so the k8s manifests and
resilience machinery apply unchanged.
"""

from __future__ import annotations

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from pyspark_tf_gke_tpu.data.text import get_tokenizer, lm_batches
from pyspark_tf_gke_tpu.models import CausalLM, CausalLMConfig
from pyspark_tf_gke_tpu.parallel.distributed import initialize_distributed
from pyspark_tf_gke_tpu.parallel.mesh import mesh_from_spec
from pyspark_tf_gke_tpu.train.harness import (
    finalize_run,
    local_batch_size,
    make_checkpoint,
    make_heartbeat,
    OPTIMIZERS,
    make_optimizer,
)
from pyspark_tf_gke_tpu.train.resilience import run_with_recovery
from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
from pyspark_tf_gke_tpu.utils.config import _env_bool, parse_mesh_shape
from pyspark_tf_gke_tpu.utils.logging import banner, get_logger
from pyspark_tf_gke_tpu.utils.seeding import make_rng

logger = get_logger("train.lm_pretrain")


# --arch -> the ``model_type`` its --model-config file has to state
HYBRID_ARCHS = {"kimi-linear": "kimi_linear", "nemotron-h": "nemotron_h", "afmoe": "afmoe"}


def parse_args(argv=None) -> argparse.Namespace:
    e = os.environ.get
    p = argparse.ArgumentParser(
        description="Pretrain a decoder-only causal LM on raw text files"
    )
    p.add_argument("--data-pattern", default=e("DATA_PATTERN", ""),
                   help="glob of text files, e.g. 'gs://bucket/corpus/*.txt' "
                        "(or token shards with --data-format tokens)")
    p.add_argument("--data-format", default=e("DATA_FORMAT", "text"),
                   choices=["text", "tokens"],
                   help="text = raw files tokenized host-side; tokens = "
                        "packed-token TFRecord shards from the Spark ETL "
                        "bridge (etl/text_bridge.py), read with the native "
                        "IO plane")
    p.add_argument("--eval-pattern", default=e("EVAL_PATTERN", ""),
                   help="optional glob of held-out text files; per-epoch "
                        "val_loss and val_perplexity land in history")
    p.add_argument("--eval-batches", type=int, default=int(e("EVAL_BATCHES", "16")),
                   help="number of validation batches per epoch")
    p.add_argument("--tokenizer", default=e("TOKENIZER", "byte"),
                   help="'byte' (built-in, vocab 259) or an HF tokenizer "
                        "name/path (e.g. 'gpt2')")
    p.add_argument("--seq-len", type=int, default=int(e("SEQ_LEN", "512")))
    p.add_argument("--hidden-size", type=int, default=int(e("HIDDEN_SIZE", "768")))
    p.add_argument("--num-layers", type=int, default=int(e("NUM_LAYERS", "12")))
    p.add_argument("--num-heads", type=int, default=int(e("NUM_HEADS", "12")))
    p.add_argument("--num-kv-heads", type=int, default=int(e("NUM_KV_HEADS", "0")),
                   help=">0 enables grouped-query attention (1 = MQA)")
    p.add_argument("--kv-cache-quant", action="store_true",
                   default=e("KV_CACHE_QUANT", "") == "1",
                   help="exported bundle serves with an int8 KV cache "
                        "(per-row scales; 4x less decode cache traffic "
                        "vs f32, stacks with GQA)")
    p.add_argument("--pos-embedding", default=e("POS_EMBEDDING") or None,
                   choices=["learned", "rope"],
                   help="rope = rotary q/k embeddings (no position table, "
                        "better length extrapolation); default learned")
    p.add_argument("--norm", default=e("NORM") or None,
                   choices=["layernorm", "rmsnorm"])
    p.add_argument("--ffn", default=e("FFN") or None,
                   choices=["gelu", "swiglu"])
    p.add_argument("--arch", default=e("ARCH", ""),
                   choices=["", "gpt2", "llama", *HYBRID_ARCHS],
                   help="architecture preset: gpt2 = learned+layernorm+gelu "
                        "(the defaults); llama = rope+rmsnorm+swiglu; "
                        "kimi-linear, nemotron-h and afmoe = the hybrid decoder "
                        "of models/hybrid_lm.py (kimi-linear: KDA + MLA layers, "
                        "dense + expert FFNs; nemotron-h: one mixer a layer, "
                        "Mamba-2, GQA or relu2 experts; afmoe: gated GQA in a "
                        "window with rotary positions beside global layers "
                        "without, sandwich norms, dense + expert FFNs), sized "
                        "by --model-config")
    p.add_argument("--model-config", default=e("MODEL_CONFIG", ""),
                   help="configuration file with the family's published keys "
                        "(--arch kimi-linear: e.g. benchmark/configs/"
                        "kimi-linear-48b-a3b.json; --arch nemotron-h: e.g. "
                        "benchmark/configs/nemotron-3-nano-30b-a3b.json; "
                        "--arch afmoe: e.g. benchmark/configs/trinity-mini.json); it "
                        "gives every size, the vocabulary among them")
    p.add_argument("--doc-masking", action="store_true",
                   default=_env_bool("DOC_MASKING", False),
                   help="confine attention within document boundaries in "
                        "packed rows (segment ids from the packer; text "
                        "format only)")
    p.add_argument("--intermediate-size", type=int,
                   default=int(e("INTERMEDIATE_SIZE", "3072")))
    p.add_argument("--vocab-chunks", type=int, default=int(e("VOCAB_CHUNKS", "0")),
                   help=">0 uses the chunked large-vocab cross-entropy "
                        "(ops/chunked_ce.py) with this many vocab chunks")
    p.add_argument("--remat", action="store_true", default=e("REMAT", "") == "1")
    p.add_argument("--epochs", type=int, default=int(e("EPOCHS", "1")))
    p.add_argument("--steps-per-epoch", type=int, default=int(e("STEPS_PER_EPOCH", "100")))
    p.add_argument("--batch-size", type=int, default=int(e("BATCH_SIZE", "16")),
                   help="GLOBAL batch size across all chips")
    p.add_argument("--learning-rate", type=float, default=float(e("LEARNING_RATE", "3e-4")))
    p.add_argument("--ema-decay", type=float, default=float(e("EMA_DECAY", "0")),
                   help=">0 maintains an EMA of params alongside training")
    p.add_argument("--optimizer", default=e("OPTIMIZER", "adam"),
                   choices=list(OPTIMIZERS),
                   help="adamw + warmup_cosine is the standard transformer "
                        "recipe; adam (the prior default) stays default "
                        "for backward-compatible loss curves")
    p.add_argument("--weight-decay", type=float,
                   default=float(e("WEIGHT_DECAY", "0.0")))
    p.add_argument("--lr-schedule", default=e("LR_SCHEDULE", "constant"),
                   choices=["constant", "cosine", "warmup_cosine"])
    p.add_argument("--warmup-steps", type=int, default=int(e("WARMUP_STEPS", "0")))
    p.add_argument("--grad-clip-norm", type=float,
                   default=float(e("GRAD_CLIP_NORM", "0.0")))
    p.add_argument("--export-bundle", default=e("EXPORT_BUNDLE", ""),
                   help="directory to export a serving bundle into after "
                        "training (EMA weights if enabled; int8 by default)")
    p.add_argument("--export-dense", action="store_true",
                   default=_env_bool("EXPORT_DENSE", False),
                   help="skip int8 quantization in the exported bundle")
    p.add_argument("--seed", type=int, default=int(e("SEED", "1337")))
    p.add_argument("--mesh-shape", default=e("MESH_SHAPE", ""),
                   help='e.g. "dp=2,fsdp=2" | "" → all chips on dp')
    p.add_argument("--dcn-mesh-shape", default=e("DCN_MESH_SHAPE", ""),
                   help='multi-slice: axes spanning DCN (e.g. "dp=2"); '
                        "--mesh-shape then gives the intra-slice axes")
    p.add_argument("--output-dir", default=e("OUTPUT_DIR", "./lm-pretrain"))
    p.add_argument("--checkpoint-every-steps", type=int,
                   default=int(e("CHECKPOINT_EVERY_STEPS", "0")))
    p.add_argument("--async-checkpoint", action="store_true",
                   default=_env_bool("ASYNC_CHECKPOINT", False))
    p.add_argument("--resume", action="store_true", default=_env_bool("RESUME", False))
    p.add_argument("--compute-dtype", default=e("COMPUTE_DTYPE", "bfloat16"),
                   choices=["bfloat16", "float32"])
    p.add_argument("--num-processes", type=int, default=int(e("NUM_PROCESSES", "1")))
    p.add_argument("--process-id", type=int, default=int(e("PROCESS_ID", "-1")))
    p.add_argument("--coordinator-addr", default=e("COORDINATOR_ADDR", ""))
    p.add_argument("--coordinator-port", type=int, default=int(e("COORDINATOR_PORT", "8476")))
    p.add_argument("--max-restarts", type=int, default=int(e("MAX_RESTARTS", "0")))
    p.add_argument("--heartbeat-every-steps", type=int,
                   default=int(e("HEARTBEAT_EVERY_STEPS", "10")))
    p.add_argument("--heartbeat-file", default=e("HEARTBEAT_FILE", ""),
                   help="node-local heartbeat path for the k8s exec probe "
                        "(default: <output-dir>/heartbeat-{process_index}.json)")
    return p.parse_args(argv)


def _hybrid_config(args, tokenizer, dtype):
    """``--arch kimi-linear`` / ``nemotron-h`` / ``afmoe``: every size from
    ``--model-config``, a file of that family; the tokenizer's ids have to fit
    the file's (possibly sliced) vocabulary."""
    import json

    from pyspark_tf_gke_tpu.models.hybrid_lm import config_from_file

    with open(args.model_config) as f:
        file = json.load(f)
    if file.get("model_type") != HYBRID_ARCHS[args.arch]:
        raise SystemExit(
            f"--arch {args.arch} takes a --model-config of model_type "
            f"{HYBRID_ARCHS[args.arch]!r}; {args.model_config} states "
            f"{file.get('model_type')!r}")
    cfg = config_from_file(file, dtype=dtype, remat=args.remat)
    if tokenizer.vocab_size > cfg.vocab_size:
        raise SystemExit(
            f"tokenizer {args.tokenizer!r} has {tokenizer.vocab_size} ids, the "
            f"model configuration's vocabulary {cfg.vocab_size}")
    return cfg


def main(argv=None) -> dict:
    args = parse_args(argv)
    if not args.data_pattern:
        raise SystemExit("--data-pattern is required (glob of text files)")
    if args.doc_masking and args.data_format == "tokens":
        raise SystemExit("--doc-masking needs the text data format "
                         "(token shards carry no segment ids)")
    # Architecture resolution: explicit flags (None = unset) vs the
    # --arch preset. A flag that disagrees with the preset is an error
    # (silently discarding either side trains the wrong architecture for
    # a whole job); checked before any backend init so it fails fast.
    presets = {"llama": {"pos_embedding": "rope", "norm": "rmsnorm",
                         "ffn": "swiglu"},
               "gpt2": {"pos_embedding": "learned", "norm": "layernorm",
                        "ffn": "gelu"},
               "": {}}
    builtin = {"pos_embedding": "learned", "norm": "layernorm", "ffn": "gelu"}
    hybrid = args.arch in HYBRID_ARCHS
    if hybrid != bool(args.model_config):
        raise SystemExit(f"--arch {' / '.join(HYBRID_ARCHS)} and --model-config go together")
    if hybrid and (args.doc_masking or args.export_bundle):
        raise SystemExit(f"--arch {args.arch} trains only: no --doc-masking (the "
                         "recurrent state, KDA's or the state-space scan's, is not "
                         "reset inside a row) and no --export-bundle (no decode "
                         "path) yet")
    preset = presets.get(args.arch, {})
    for name, default in builtin.items():
        explicit = getattr(args, name)
        if explicit is None:
            setattr(args, name, preset.get(name, default))
        elif name in preset and explicit != preset[name]:
            raise SystemExit(
                f"--arch {args.arch} sets --{name.replace('_', '-')} "
                f"{preset[name]}, conflicting with the explicit "
                f"--{name.replace('_', '-')} {explicit}; drop --arch and "
                "set the architecture flags individually")
    initialize_distributed(
        num_processes=args.num_processes,
        process_id=args.process_id,
        coordinator_addr=args.coordinator_addr,
        coordinator_port=args.coordinator_port,
    )
    banner(logger, f"Causal-LM pretraining: {args.data_pattern}")

    tokenizer = get_tokenizer(args.tokenizer)
    dtype = jnp.bfloat16 if args.compute_dtype == "bfloat16" else jnp.float32
    cfg = _hybrid_config(args, tokenizer, dtype) if hybrid else CausalLMConfig(
        vocab_size=tokenizer.vocab_size,
        hidden_size=args.hidden_size,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        num_kv_heads=args.num_kv_heads or None,
        pos_embedding=args.pos_embedding,
        norm=args.norm,
        ffn=args.ffn,
        intermediate_size=args.intermediate_size,
        max_seq_len=args.seq_len,
        dtype=dtype,
        remat=args.remat,
        kv_cache_quant=args.kv_cache_quant,
    )
    mesh = mesh_from_spec(parse_mesh_shape(args.mesh_shape),
                          parse_mesh_shape(args.dcn_mesh_shape))
    if hybrid:
        from pyspark_tf_gke_tpu.models.hybrid_lm import HybridLM

        model = HybridLM(cfg, mesh=mesh)
    else:
        model = CausalLM(cfg, mesh=mesh)
    task = TASKS["causal_lm"](vocab_chunks=args.vocab_chunks or None)
    tx = make_optimizer(
        args.learning_rate, schedule=args.lr_schedule,
        total_steps=args.epochs * args.steps_per_epoch,
        warmup_steps=args.warmup_steps, optimizer=args.optimizer,
        weight_decay=args.weight_decay, grad_clip_norm=args.grad_clip_norm)
    trainer = Trainer(model, task, mesh, tx=tx, ema_decay=args.ema_decay)

    local_bs = local_batch_size(args.batch_size)

    def batches():
        if args.data_format == "tokens":
            from pyspark_tf_gke_tpu.data.native_tfrecord import (
                read_tfrecord_batches,
            )
            from pyspark_tf_gke_tpu.etl.text_bridge import validate_shard_meta

            validate_shard_meta(args.data_pattern, args.tokenizer,
                                args.seq_len, tokenizer.vocab_size)
            # reader already yields int32 (int_dtype default)
            yield from read_tfrecord_batches(
                args.data_pattern, {"input_ids": ("int", (args.seq_len,))},
                local_bs, seed=args.seed)
            return
        yield from lm_batches(
            args.data_pattern, tokenizer, args.seq_len, local_bs,
            seed=args.seed,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            with_segments=args.doc_masking,
        )

    val_batches = None
    if args.eval_pattern:
        import itertools

        from pyspark_tf_gke_tpu.utils.fs import fs_glob

        eval_files = fs_glob(args.eval_pattern)
        if not eval_files:
            # Fail a typo'd eval path at startup, not at the end of
            # epoch 1 (where run_with_recovery would retry it).
            raise SystemExit(f"--eval-pattern matches no files: "
                             f"{args.eval_pattern!r}")
        if jax.process_count() > 1 and len(eval_files) % jax.process_count():
            # SPMD eval steps are collective: a host whose round-robin
            # stripe holds fewer eval files than its peers would skip
            # collective steps the others run — a silent desync/hang.
            # Every host sees the same glob, so this check fires (and
            # exits) consistently everywhere.
            raise SystemExit(
                f"--eval-pattern matched {len(eval_files)} files, which "
                f"does not divide evenly across {jax.process_count()} "
                f"hosts; uneven per-host eval file counts desynchronize "
                f"collective eval steps. Repack the eval set so every "
                f"host gets the same number of files.")

        def val_batches():
            # Fresh deterministic pass each epoch, capped at --eval-batches
            # (unshuffled: a fixed eval set makes val_loss comparable
            # across epochs). An empty pass — e.g. striping gave this
            # host no eval files — skips validation instead of killing a
            # healthy training run. (Multi-host note: give every host
            # the same number of eval files; SPMD eval steps are
            # collective, so uneven batch counts would desynchronize.)
            def gen():
                try:
                    yield from itertools.islice(
                        lm_batches(args.eval_pattern, tokenizer,
                                   args.seq_len, local_bs, seed=args.seed,
                                   repeat=False, shuffle_buffer=1,
                                   process_index=jax.process_index(),
                                   process_count=jax.process_count(),
                                   # validate the objective being
                                   # trained: same masking as training
                                   with_segments=args.doc_masking),
                        args.eval_batches)
                except ValueError as exc:
                    logger.warning("validation skipped: %s", exc)

            return gen()

    state = trainer.init_state(make_rng(args.seed), next(batches()))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(state.params))
    logger.info("Model: %d params (%.1fM), vocab=%d, mesh=%s", n_params,
                n_params / 1e6, cfg.vocab_size, dict(mesh.shape))

    def attempt_run(attempt: int) -> dict:
        nonlocal state
        ckpt, state = make_checkpoint(
            args.output_dir, args.checkpoint_every_steps, state,
            args.resume or attempt > 0,
            async_save=args.async_checkpoint,
        )
        try:
            state, history = trainer.fit(
                state, batches(), args.epochs, args.steps_per_epoch,
                val_batches=val_batches,
                # validate the weights the bundle will ship: EMA if enabled
                val_use_ema=args.ema_decay > 0,
                checkpoint_manager=ckpt,
                heartbeat=make_heartbeat(args.output_dir,
                                         args.heartbeat_every_steps,
                                         args.heartbeat_file),
            )
            if "val_loss" in history:
                history["val_perplexity"] = [
                    float(np.exp(min(l, 30.0))) for l in history["val_loss"]]
            finalize_run(ckpt, state, history, args.output_dir,
                         model_name="causal-lm")
        finally:
            ckpt.close()
        return history

    history = run_with_recovery(attempt_run, max_restarts=args.max_restarts)
    if args.export_bundle:
        # ALL processes participate: quantize is a collective jit over
        # sharded params and the orbax save is a collective write (the
        # bundle gates its config.json to process 0 internally).
        from pyspark_tf_gke_tpu.train.export import export_serving_bundle

        weights = state.ema_params if state.ema_params is not None else state.params
        export_serving_bundle(cfg, weights, args.export_bundle,
                              quantize=not args.export_dense,
                              tokenizer_spec=args.tokenizer)
        logger.info("Exported serving bundle to %s", args.export_bundle)
    return history


if __name__ == "__main__":
    from pyspark_tf_gke_tpu.obs.compiles import install_compile_listener
    from pyspark_tf_gke_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    install_compile_listener()
    main(sys.argv[1:])
