"""BERT fine-tune entry point fed by TFRecord shards — BASELINE.json
config 5 ("BERT-base fine-tune fed by PySpark-preprocessed TFRecord
shards").

The input contract is the ETL bridge schema (``etl.tfrecord_bridge`` on
the Spark side): one Example per row with ``input_ids`` /
``attention_mask`` int64 features of length ``seq_len`` and an int64
``label``. Shards are read with the **native IO plane**
(``data.native_tfrecord`` → C++ reader, zero tensorflow dependency on
TPU hosts), distributed over hosts by file; the model is the annotated
BERT encoder (``models/bert.py``), and all mesh axes work — dp/fsdp/tp
for the standard fine-tune, sp (ring or Ulysses) for long-sequence
variants, ep when the config enables MoE.

No counterpart exists in the reference (no attention models, no ETL→DL
bridge — SURVEY §2b/§7); the run artifacts (history.json, checkpoints)
follow the same conventions as the CSV/image CLI.
"""

from __future__ import annotations

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from pyspark_tf_gke_tpu.data.native_tfrecord import read_tfrecord_batches
from pyspark_tf_gke_tpu.models import BertConfig, BertForPretraining
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

logger = get_logger("train.bert_finetune")


def parse_args(argv=None) -> argparse.Namespace:
    e = os.environ.get
    p = argparse.ArgumentParser(
        description="Fine-tune BERT on TFRecord shards produced by the Spark ETL bridge"
    )
    p.add_argument("--data-pattern", default=e("DATA_PATTERN", ""),
                   help="glob of TFRecord shards, e.g. 'gs://bucket/shards/train-*.tfrecord'")
    p.add_argument("--seq-len", type=int, default=int(e("SEQ_LEN", "128")))
    p.add_argument("--objective", default=e("OBJECTIVE", "classification"),
                   choices=["classification", "mlm"],
                   help="classification = fine-tune on the label column; "
                        "mlm = masked-LM pretraining on the token stream")
    p.add_argument("--mlm-prob", type=float, default=float(e("MLM_PROB", "0.15")))
    p.add_argument("--num-labels", type=int, default=int(e("NUM_LABELS", "2")))
    p.add_argument("--vocab-size", type=int, default=int(e("VOCAB_SIZE", "30522")))
    p.add_argument("--hidden-size", type=int, default=int(e("HIDDEN_SIZE", "768")))
    p.add_argument("--num-layers", type=int, default=int(e("NUM_LAYERS", "12")))
    p.add_argument("--num-heads", type=int, default=int(e("NUM_HEADS", "12")))
    p.add_argument("--intermediate-size", type=int, default=int(e("INTERMEDIATE_SIZE", "3072")))
    p.add_argument("--sp-impl", default=e("SP_IMPL", "ring"), choices=["ring", "ulysses"])
    p.add_argument("--num-experts", type=int, default=int(e("NUM_EXPERTS", "0")),
                   help=">0 turns every --moe-every'th FFN into an expert-parallel MoE")
    p.add_argument("--moe-every", type=int, default=int(e("MOE_EVERY", "2")))
    p.add_argument("--remat", action="store_true", default=e("REMAT", "") == "1")
    p.add_argument("--epochs", type=int, default=int(e("EPOCHS", "1")))
    p.add_argument("--steps-per-epoch", type=int, default=int(e("STEPS_PER_EPOCH", "100")))
    p.add_argument("--batch-size", type=int, default=int(e("BATCH_SIZE", "32")),
                   help="GLOBAL batch size across all chips")
    p.add_argument("--learning-rate", type=float, default=float(e("LEARNING_RATE", "2e-5")))
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
    p.add_argument("--seed", type=int, default=int(e("SEED", "1337")))
    p.add_argument("--mesh-shape", default=e("MESH_SHAPE", ""),
                   help='e.g. "dp=2,fsdp=2" | "dp=2,sp=4" | "" → all chips on dp')
    p.add_argument("--dcn-mesh-shape", default=e("DCN_MESH_SHAPE", ""),
                   help='multi-slice: axes spanning DCN (e.g. "dp=2"); '
                        "--mesh-shape then gives the intra-slice axes")
    p.add_argument("--output-dir", default=e("OUTPUT_DIR", "./bert-finetune"))
    p.add_argument("--checkpoint-every-steps", type=int,
                   default=int(e("CHECKPOINT_EVERY_STEPS", "0")))
    p.add_argument("--async-checkpoint", action="store_true",
                   default=_env_bool("ASYNC_CHECKPOINT", False),
                   help="write checkpoints in the background (orbax async)")
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


def shard_schema(seq_len: int) -> dict:
    """The ETL-bridge contract for sequence-classification shards."""
    return {
        "input_ids": ("int", (seq_len,)),
        "attention_mask": ("int", (seq_len,)),
        "label": ("int", ()),
    }


def main(argv=None) -> dict:
    args = parse_args(argv)
    if not args.data_pattern:
        raise SystemExit("--data-pattern is required (glob of TFRecord shards)")
    initialize_distributed(
        num_processes=args.num_processes,
        process_id=args.process_id,
        coordinator_addr=args.coordinator_addr,
        coordinator_port=args.coordinator_port,
    )
    banner(logger, f"BERT fine-tune: {args.data_pattern}")

    cfg = BertConfig(
        vocab_size=args.vocab_size,
        hidden_size=args.hidden_size,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        intermediate_size=args.intermediate_size,
        max_position_embeddings=max(512, args.seq_len),
        dtype=jnp.bfloat16 if args.compute_dtype == "bfloat16" else jnp.float32,
        remat=args.remat,
        sp_impl=args.sp_impl,
        num_experts=args.num_experts,
        moe_every=args.moe_every,
    )
    mesh = mesh_from_spec(parse_mesh_shape(args.mesh_shape),
                          parse_mesh_shape(args.dcn_mesh_shape))
    model = BertForPretraining(cfg, mesh=mesh, num_labels=args.num_labels)
    task = TASKS["bert_mlm" if args.objective == "mlm" else "bert_classification"]()
    tx = make_optimizer(
        args.learning_rate, schedule=args.lr_schedule,
        total_steps=args.epochs * args.steps_per_epoch,
        warmup_steps=args.warmup_steps, optimizer=args.optimizer,
        weight_decay=args.weight_decay, grad_clip_norm=args.grad_clip_norm)
    trainer = Trainer(model, task, mesh, tx=tx, ema_decay=args.ema_decay)

    local_bs = local_batch_size(args.batch_size)

    def batches():
        schema = shard_schema(args.seq_len)
        if args.objective == "mlm":
            schema.pop("label")  # token-stream pretraining data is unlabeled
        raw_iter = read_tfrecord_batches(
            args.data_pattern, schema, local_bs, seed=args.seed
        )
        if args.objective == "mlm":
            from pyspark_tf_gke_tpu.data.mlm import mlm_batches

            yield from mlm_batches(raw_iter, args.vocab_size, seed=args.seed,
                                   mask_prob=args.mlm_prob)
            return
        for raw in raw_iter:
            yield {
                "input_ids": raw["input_ids"],
                "attention_mask": raw["attention_mask"],
                "labels": raw["label"].reshape(-1),
            }

    # A throwaway iterator provides the init-tracing batch (the trainer
    # tiles it up to one row per global data shard).
    state = trainer.init_state(make_rng(args.seed), next(batches()))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(state.params))
    logger.info("Model: %d params (%.1fM), mesh=%s", n_params, n_params / 1e6,
                dict(mesh.shape))

    def attempt_run(attempt: int) -> dict:
        nonlocal state
        ckpt, state = make_checkpoint(
            args.output_dir, args.checkpoint_every_steps, state,
            args.resume or attempt > 0,
            async_save=args.async_checkpoint,
        )
        try:
            # Fresh stream per attempt: the previous attempt's prefetcher
            # may have advanced a shared iterator past unseen batches.
            state, history = trainer.fit(
                state, batches(), args.epochs, args.steps_per_epoch,
                checkpoint_manager=ckpt,
                heartbeat=make_heartbeat(args.output_dir, args.heartbeat_every_steps,
                                         args.heartbeat_file),
            )
            finalize_run(ckpt, state, history, args.output_dir,
                         model_name="bert-finetune")
        finally:
            # Join in-flight async saves even on failure: the next attempt
            # builds a fresh manager on this directory, and two writers race.
            ckpt.close()
        return history

    return run_with_recovery(attempt_run, max_restarts=args.max_restarts)


if __name__ == "__main__":
    from pyspark_tf_gke_tpu.obs.compiles import install_compile_listener
    from pyspark_tf_gke_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    install_compile_listener()
    main(sys.argv[1:])
