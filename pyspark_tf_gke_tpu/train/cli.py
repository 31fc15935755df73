"""End-to-end training entry point — the analog of the reference's
``run_deep_training`` / ``run_image_training`` + ``__main__`` dispatch
(``train_tf_ps.py:517-899``), minus the interactive ``input()`` gate
(a coordinator-mode artifact; SPMD jobs must start unattended).

CSV mode: MLP classifier on the health-CSV schema.
Image mode: CNN (x,y) regressor on a flat dir + clean_labels.jsonl.
Both: deterministic 80/20 split, label_map.json / history.json artifacts,
orbax checkpoint at the end (periodic with --checkpoint-every-steps),
optional resume.

Run it identically on 1 chip or a pod slice — parallelism comes from
--mesh-shape and (multi-host) the jax.distributed bootstrap flags.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

import jax
import numpy as np

from pyspark_tf_gke_tpu.data.csv_loader import load_csv
from pyspark_tf_gke_tpu.data.images import make_image_arrays
from pyspark_tf_gke_tpu.data.pipeline import (
    BatchIterator,
    host_shard,
    train_validation_split,
)
from pyspark_tf_gke_tpu.models import build_model
from pyspark_tf_gke_tpu.parallel.distributed import initialize_distributed
from pyspark_tf_gke_tpu.parallel.mesh import mesh_from_spec
from pyspark_tf_gke_tpu.train.checkpoint import save_label_map
from pyspark_tf_gke_tpu.train.harness import (
    finalize_run,
    local_batch_size,
    make_checkpoint,
    make_heartbeat,
    make_optimizer,
)
from pyspark_tf_gke_tpu.train.resilience import FaultInjector, run_with_recovery
from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
from pyspark_tf_gke_tpu.utils.config import Config, parse_args
from pyspark_tf_gke_tpu.utils.logging import banner, get_logger
from pyspark_tf_gke_tpu.utils.seeding import make_rng

logger = get_logger("train.cli")


def _dtype(name: str):
    import jax.numpy as jnp

    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "": None}.get(name, None)


def _heartbeat(cfg: Config):
    return make_heartbeat(cfg.output_dir, cfg.heartbeat_every_steps, cfg.heartbeat_file)


def run_csv_training(cfg: Config, fault_injector: Optional[FaultInjector] = None) -> dict:
    banner(logger, f"CSV training: {cfg.data_path}")
    X, y, vocab = load_csv(cfg.data_path)
    num_classes = int(np.max(y)) + 1
    save_label_map(cfg.output_dir, vocab)

    train_idx, val_idx = train_validation_split(len(X), cfg.validation_split, cfg.seed)
    Xt, yt = host_shard(X[train_idx], y[train_idx])
    Xv, yv = X[val_idx], y[val_idx]

    if cfg.model not in ("", "mlp"):
        raise ValueError(
            f"CSV mode trains the MLP classifier; got --model {cfg.model}. "
            "BERT fine-tunes through train.bert_finetune, a causal LM "
            "trains through train.lm_pretrain."
        )

    local_bs = local_batch_size(cfg.batch_size)
    train_iter = BatchIterator({"x": Xt, "y": yt}, local_bs, seed=cfg.seed)
    steps = cfg.steps_per_epoch or train_iter.steps_per_epoch
    # With accumulation an optimizer step consumes accum microbatches; keep
    # one epoch = one dataset pass.
    steps = -(-steps // cfg.grad_accum_steps)

    mesh = mesh_from_spec(cfg.mesh_axes(), cfg.dcn_mesh_axes())
    model = build_model("mlp", num_classes=num_classes)
    tx = make_optimizer(cfg.learning_rate, cfg.lr_schedule,
                        total_steps=cfg.epochs * steps, warmup_steps=cfg.warmup_steps,
                        optimizer=cfg.optimizer, weight_decay=cfg.weight_decay,
                        momentum=cfg.momentum, grad_clip_norm=cfg.grad_clip_norm)
    trainer = Trainer(model, TASKS["classification"](), mesh, tx=tx,
                      fsdp_min_size=cfg.fsdp_min_size)
    # Unsliced host-shard arrays as the init sample: shape-only tracing, and
    # the trainer trims to exactly one row per data shard itself.
    state = trainer.init_state(make_rng(cfg.seed), {"x": Xt, "y": yt})

    ckpt, state = make_checkpoint(
        cfg.output_dir, cfg.checkpoint_every_steps, state, cfg.resume,
        async_save=cfg.async_checkpoint,
    )
    restored_step = int(jax.device_get(state.step))
    if restored_step:
        # continue the exact deterministic batch order from where the
        # restored optimizer step left off (each step consumed
        # grad_accum microbatches)
        train_iter.fast_forward(restored_step * cfg.grad_accum_steps)

    def val_batches():
        if len(Xv) < local_bs:
            return
        it = BatchIterator({"x": Xv, "y": yv}, local_bs, shuffle=False,
                           drop_remainder=True)
        for _ in range(it.steps_per_epoch):
            yield next(it)

    try:
        state, history = trainer.fit(
            state, train_iter, cfg.epochs, steps, val_batches=val_batches,
            checkpoint_manager=ckpt, log_every=cfg.log_every_steps,
            heartbeat=_heartbeat(cfg), fault_injector=fault_injector,
            grad_accum=cfg.grad_accum_steps,
        )
        finalize_run(ckpt, state, history, cfg.output_dir, model_name="mlp")
    finally:
        # Join in-flight async saves even on failure: the restart wrapper
        # builds a fresh manager on this directory, and two writers race.
        ckpt.close()
    return history


def run_image_training(cfg: Config, fault_injector: Optional[FaultInjector] = None) -> dict:
    banner(logger, f"Image training: {cfg.data_path}")
    from pyspark_tf_gke_tpu.data.images import list_labeled_images

    filepaths, _ = list_labeled_images(cfg.data_path)
    train_idx, val_idx = train_validation_split(
        len(filepaths), cfg.validation_split, cfg.seed
    )
    images_t, targets_t = make_image_arrays(
        cfg.data_path, (cfg.img_height, cfg.img_width), train_idx
    )
    images_v, targets_v = make_image_arrays(
        cfg.data_path, (cfg.img_height, cfg.img_width), val_idx
    )
    images_t, targets_t = host_shard(images_t, targets_t)

    local_bs = local_batch_size(cfg.batch_size)
    train_iter = BatchIterator(
        {"image": images_t, "target": targets_t}, local_bs, seed=cfg.seed
    )
    steps = cfg.steps_per_epoch or train_iter.steps_per_epoch
    steps = -(-steps // cfg.grad_accum_steps)

    if cfg.model not in ("", "cnn"):
        raise ValueError(
            f"Image mode trains the CNN regressor; got --model {cfg.model}. "
            "BERT fine-tunes through train.bert_finetune, a causal LM "
            "trains through train.lm_pretrain."
        )
    mesh = mesh_from_spec(cfg.mesh_axes(), cfg.dcn_mesh_axes())
    model = build_model("cnn", flat=cfg.flat_layer, dtype=_dtype(cfg.compute_dtype))
    tx = make_optimizer(cfg.learning_rate, cfg.lr_schedule,
                        total_steps=cfg.epochs * steps, warmup_steps=cfg.warmup_steps,
                        optimizer=cfg.optimizer, weight_decay=cfg.weight_decay,
                        momentum=cfg.momentum, grad_clip_norm=cfg.grad_clip_norm)
    trainer = Trainer(model, TASKS["regression"](), mesh, tx=tx,
                      fsdp_min_size=cfg.fsdp_min_size)
    state = trainer.init_state(
        make_rng(cfg.seed), {"image": images_t, "target": targets_t}
    )

    ckpt, state = make_checkpoint(
        cfg.output_dir, cfg.checkpoint_every_steps, state, cfg.resume,
        async_save=cfg.async_checkpoint,
    )
    restored_step = int(jax.device_get(state.step))
    if restored_step:
        train_iter.fast_forward(restored_step * cfg.grad_accum_steps)

    def val_batches():
        if len(images_v) < local_bs:
            return
        it = BatchIterator({"image": images_v, "target": targets_v}, local_bs,
                           shuffle=False)
        for _ in range(it.steps_per_epoch):
            yield next(it)

    try:
        state, history = trainer.fit(
            state, train_iter, cfg.epochs, steps, val_batches=val_batches,
            checkpoint_manager=ckpt, log_every=cfg.log_every_steps,
            heartbeat=_heartbeat(cfg), fault_injector=fault_injector,
            grad_accum=cfg.grad_accum_steps,
        )
        finalize_run(ckpt, state, history, cfg.output_dir,
                     model_name="cnn-b1" if cfg.flat_layer else "cnn-a1")
    finally:
        ckpt.close()
    return history


def main(argv: Optional[list] = None) -> dict:
    cfg = parse_args(argv)
    initialize_distributed(
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
        coordinator_addr=cfg.coordinator_addr,
        coordinator_port=cfg.coordinator_port,
    )
    if cfg.profile_dir:
        jax.profiler.start_trace(cfg.profile_dir)
    try:
        # One injector across attempts: each injected step fires once, so
        # the post-resume replay of the same global step proceeds.
        fault_injector = FaultInjector.from_spec(cfg.fail_at_steps)
        is_image_mode = cfg.data_is_images or os.path.isdir(cfg.data_path)

        def attempt_run(attempt: int) -> dict:
            run_cfg = cfg.replace(resume=cfg.resume or attempt > 0)
            if attempt > 0:
                logger.warning("Restart %d: resuming from latest checkpoint", attempt)
            if is_image_mode:
                return run_image_training(run_cfg, fault_injector)
            return run_csv_training(run_cfg, fault_injector)

        return run_with_recovery(attempt_run, max_restarts=cfg.max_restarts)
    finally:
        if cfg.profile_dir:
            jax.profiler.stop_trace()


if __name__ == "__main__":
    from pyspark_tf_gke_tpu.obs.compiles import install_compile_listener
    from pyspark_tf_gke_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    install_compile_listener()
    main(sys.argv[1:])
