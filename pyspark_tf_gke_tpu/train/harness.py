"""Shared run scaffolding for the training entry points (cli.py,
bert_finetune.py): the pieces every entry repeats — host-local batch
sizing, checkpoint setup/restore/finalize, run-notes artifacts, and the
heartbeat plumbing from train/resilience.py."""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import numpy as np

from pyspark_tf_gke_tpu.train.checkpoint import CheckpointManager, save_history
from pyspark_tf_gke_tpu.train.resilience import Heartbeat
from pyspark_tf_gke_tpu.utils.fs import fs_write_text, is_remote


# THE optimizer list: every CLI's --optimizer choices come from here so
# a new family lands in all entry points at once (cli, lm_pretrain,
# bert_finetune each used to copy-paste it and drift).
OPTIMIZERS = ("adam", "adamw", "sgd", "momentum", "lamb", "adafactor")


def make_optimizer(
    learning_rate: float,
    schedule: str = "constant",
    total_steps: int = 0,
    warmup_steps: int = 0,
    optimizer: str = "adam",
    weight_decay: float = 0.0,
    momentum: float = 0.9,
    grad_clip_norm: float = 0.0,
):
    """Optimizer factory: adam | adamw | sgd | momentum | lamb |
    adafactor with an
    optax LR schedule (constant | cosine | warmup_cosine) and optional
    global-norm gradient clipping. (The reference uses bare constant-LR
    Adam, train_tf_ps.py:339,606; adamw+warmup_cosine is the standard
    recipe for the BERT config, lamb for large-batch pretraining.)"""
    import optax

    if schedule not in ("constant", "cosine", "warmup_cosine"):
        raise ValueError(
            f"unknown lr schedule {schedule!r}; use constant | cosine | warmup_cosine"
        )
    if weight_decay and optimizer not in ("adamw", "lamb", "adafactor"):
        raise ValueError(
            f"weight_decay={weight_decay} is ignored by optimizer "
            f"{optimizer!r} — use adamw, lamb or adafactor (or set "
            "weight_decay=0)"
        )
    if warmup_steps and schedule != "warmup_cosine":
        raise ValueError(
            f"warmup_steps={warmup_steps} is ignored by schedule "
            f"{schedule!r} — use warmup_cosine (or set warmup_steps=0)"
        )
    if schedule != "constant" and total_steps <= 0:
        raise ValueError(
            f"lr schedule {schedule!r} needs total_steps > 0 (a decay over 0 "
            "steps would pin the learning rate at ~0 for the whole run)"
        )
    if schedule == "constant":
        lr = learning_rate
    elif schedule == "cosine":
        lr = optax.cosine_decay_schedule(learning_rate, total_steps)
    elif schedule == "warmup_cosine":
        lr = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, max(warmup_steps, 1),
            max(total_steps, warmup_steps + 1),
        )

    def decay_mask(params):
        # Standard BERT/LAMB recipe: decay matrices/embeddings only —
        # never biases or LayerNorm scales (all 1-D leaves).
        import jax as _jax

        return _jax.tree.map(lambda p: _jax.numpy.ndim(p) >= 2, params)

    if optimizer == "adam":
        tx = optax.adam(lr)
    elif optimizer == "adamw":
        tx = optax.adamw(lr, weight_decay=weight_decay, mask=decay_mask)
    elif optimizer == "sgd":
        tx = optax.sgd(lr)
    elif optimizer == "momentum":
        tx = optax.sgd(lr, momentum=momentum, nesterov=True)
    elif optimizer == "lamb":
        tx = optax.lamb(lr, weight_decay=weight_decay, mask=decay_mask)
    elif optimizer == "adafactor":
        # the TPU-idiomatic memory-efficient choice (t5x's default):
        # factored second moments store O(rows+cols) per matrix instead
        # of Adam's O(rows*cols) — at h768 BERT scale the optimizer
        # state drops ~2x, and with it that share of the per-step HBM
        # stream of parameters and optimizer state.
        tx = optax.adafactor(lr, weight_decay_rate=weight_decay or None,
                             weight_decay_mask=(decay_mask if weight_decay
                                                else None))
    else:
        raise ValueError(
            f"unknown optimizer {optimizer!r}; use " + " | ".join(OPTIMIZERS)
        )
    if grad_clip_norm > 0:
        tx = optax.chain(optax.clip_by_global_norm(grad_clip_norm), tx)
    return tx


def local_batch_size(global_batch: int) -> int:
    """Per-host batch from the GLOBAL batch size (reference semantics:
    batch flags are global; each host feeds its slice)."""
    n_proc = jax.process_count()
    if global_batch % n_proc:
        raise ValueError(
            f"global batch {global_batch} not divisible by {n_proc} hosts"
        )
    return global_batch // n_proc


def make_checkpoint(
    output_dir: str,
    every_steps: int,
    state,
    resume: bool,
    async_save: bool = False,
):
    """Build the CheckpointManager under ``output_dir`` and restore the
    latest step when resuming. Returns (manager, possibly-restored state)."""
    ckpt = CheckpointManager(
        os.path.join(output_dir, "checkpoints"), every_steps=every_steps,
        async_save=async_save,
    )
    if resume and ckpt.latest_step() is not None:
        state = ckpt.restore(state)
    return ckpt, state


def finalize_run(ckpt: CheckpointManager, state, history: Dict, output_dir: str,
                 model_name: str = "model") -> None:
    """Terminal save: checkpoint + history.json (the reference's
    model.save + history dump, train_tf_ps.py:674-679) + run notes."""
    ckpt.save(state, history)
    ckpt.wait()  # terminal save must be durable before the process exits
    save_history(output_dir, history)
    save_run_notes(output_dir, model_name, state, history)


def save_run_notes(output_dir: str, model_name: str, state, history: Dict) -> str:
    """``<model_name>.txt`` run notes — the analog of the reference's
    ``tf-model/150-320-by-256-B1-model.txt`` artifacts (param count/size,
    hardware, epochs, final metrics)."""
    path = os.path.join(output_dir, f"{model_name}.txt")
    if jax.process_index() != 0:
        return path
    leaves = jax.tree.leaves(state.params)
    n_params = sum(int(np.prod(l.shape)) for l in leaves)
    n_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
    devices = jax.devices()
    lines = [
        f"model: {model_name}",
        f"total params: {n_params:,}",
        f"size: {n_bytes / (1 << 20):.2f} MB",
        f"devices: {len(devices)}x {devices[0].platform}"
        + (f" ({devices[0].device_kind})" if hasattr(devices[0], "device_kind") else ""),
        f"processes: {jax.process_count()}",
        f"final step: {int(jax.device_get(state.step))}",
        f"epochs recorded: {len(history.get('loss', []))}",
    ]
    for key, vals in sorted(history.items()):
        if vals:
            lines.append(f"final {key}: {vals[-1]:.6g}")
    fs_write_text(path, "\n".join(lines) + "\n")
    return path


def make_heartbeat(
    output_dir: str, every_steps: int, path: str = ""
) -> Optional[Heartbeat]:
    if not every_steps:
        return None
    if not path:
        # heartbeats must be node-local (age probes need local mtime;
        # a per-step gs:// write would be absurd) — when the artifact
        # dir is remote, default to /tmp like the k8s manifests do.
        # Per-process in BOTH defaults: with a shared file a hung
        # process hides behind any live peer's beats (local
        # multi-process runs are exactly the fake-slice test shape).
        path = ("/tmp/tpu-heartbeat-{process_index}.json"
                if is_remote(output_dir)
                else os.path.join(output_dir,
                                  "heartbeat-{process_index}.json"))
    return Heartbeat(path, every_steps)
