"""The trainer: sharded jit train step + epoch loop.

Replaces the reference's ParameterServerStrategy machinery
(``train_tf_ps.py:440-511``) and its coordinator-scheduled step loop
(``train_tf_ps.py:611-647``) with the SPMD design (SURVEY §7): one jitted
``train_step`` — forward, loss, grad, Adam update — compiled once over a
device mesh. Gradient combination across chips is *implicit*: the batch is
sharded over the data axes, so XLA inserts the allreduce over ICI.
Parameter sharding (the ``MinSizePartitioner`` analog) is a
``NamedSharding`` on the state pytree, applied identically to params and
optimizer moments.

Training here is **synchronous** data-parallel by design — the reference's
asynchronous PS updates are an artifact of its gRPC push/pull transport;
on a TPU mesh synchronous allreduce is both faster and better-behaved
(loss parity at worker-count>1 is therefore final-metric parity, per
BASELINE.md).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pyspark_tf_gke_tpu.obs.compiles import install_compile_listener, on_compile
from pyspark_tf_gke_tpu.obs.events import get_event_log
from pyspark_tf_gke_tpu.obs.metrics import get_registry, platform_families
from pyspark_tf_gke_tpu.obs.trace import annotate, get_tracer, span
from pyspark_tf_gke_tpu.ops.pallas.scope import part_scope
from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding
from pyspark_tf_gke_tpu.parallel.sharding import (
    DEFAULT_MIN_SIZE,
    LOGICAL_RULES,
    fsdp_spec,
)
from pyspark_tf_gke_tpu.train.losses import (
    accuracy_metric,
    mae_metric,
    mse_loss,
    softmax_cross_entropy,
)
from pyspark_tf_gke_tpu.train.state import TrainState
from pyspark_tf_gke_tpu.utils.compile_cache import key_on_names
from pyspark_tf_gke_tpu.utils.logging import get_logger

logger = get_logger("train.trainer")

# Weight on the MoE load-balance auxiliary loss (Switch Transformer's 1e-2).
MOE_AUX_WEIGHT = 0.01


@dataclasses.dataclass(frozen=True)
class TrainerTask:
    """How a model family plugs into the generic step: how to call it and
    how to score it. The ``(preds, batch) -> (loss, metrics)`` pairings
    mirror the reference's compile() choices (train_tf_ps.py:336-377)."""

    name: str
    forward: Callable[..., Any]  # (model, variables, batch, train, mutable) -> (preds, new_model_state|None)
    loss_and_metrics: Callable[[Any, Dict[str, jnp.ndarray]], Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]
    has_batch_stats: bool = False


def _forward_simple(model, variables, batch, train, mutable):
    return model(variables, batch), None


def classification_task() -> TrainerTask:
    def forward(model, variables, batch, train, mutable):
        return model.apply(variables, batch["x"]), None

    def lam(preds, batch):
        loss = softmax_cross_entropy(preds, batch["y"])
        return loss, {"loss": loss, "accuracy": accuracy_metric(preds, batch["y"])}

    return TrainerTask("classification", forward, lam)


def regression_task() -> TrainerTask:
    def forward(model, variables, batch, train, mutable):
        return model.apply(variables, batch["image"]), None

    def lam(preds, batch):
        loss = mse_loss(preds, batch["target"])
        return loss, {
            "loss": loss,
            "mse": loss,
            "mae": mae_metric(preds, batch["target"]),
        }

    return TrainerTask("regression", forward, lam)


def _image_cls_lam(preds, batch):
    loss = softmax_cross_entropy(preds, batch["label"])
    return loss, {"loss": loss, "accuracy": accuracy_metric(preds, batch["label"])}


def resnet_task() -> TrainerTask:
    def forward(model, variables, batch, train, mutable):
        if train:
            preds, new_state = model.apply(
                variables, batch["image"], train=True, mutable=["batch_stats"]
            )
            # Stat-free norm variants (gn/none diagnostics) yield no
            # mutable collection; mirror init_state's None so the scan
            # carry keeps one pytree structure either way.
            return preds, new_state.get("batch_stats")
        return model.apply(variables, batch["image"], train=False), None

    return TrainerTask("resnet", forward, _image_cls_lam, has_batch_stats=True)


def vit_task() -> TrainerTask:
    """Image classification for stateless transformer classifiers
    (models/vit.py — no batch-norm statistics to thread; dict preds
    carry the MoE aux loss when experts are enabled)."""

    def forward(model, variables, batch, train, mutable):
        return model.apply(variables, batch["image"]), None

    def lam(preds, batch):
        loss, metrics = _image_cls_lam(preds["logits"], batch)
        return _add_moe_aux(loss, metrics, preds)

    return TrainerTask("vit", forward, lam)


def _bert_forward(model, variables, batch, train, mutable):
    """Shared forward for every BERT objective (classification, MLM).
    ``train`` routes the embedding lookup: one-hot matmul when a
    gradient will flow, plain gather for eval (models/embedding.py)."""
    return model.apply(
        variables, batch["input_ids"],
        attention_mask=batch.get("attention_mask"), train=train
    ), None


def _add_moe_aux(loss, metrics, preds):
    """MoE load-balance loss (models/moe.py); 0 for dense configs."""
    aux = preds.get("aux_loss")
    if aux is not None:
        loss = loss + MOE_AUX_WEIGHT * aux
        metrics["moe_aux_loss"] = aux
    return loss, metrics


def bert_classification_task() -> TrainerTask:
    def lam(preds, batch):
        logits = preds["cls_logits"]
        loss = softmax_cross_entropy(logits, batch["labels"])
        metrics = {"loss": loss, "accuracy": accuracy_metric(logits, batch["labels"])}
        return _add_moe_aux(loss, metrics, preds)

    return TrainerTask("bert_classification", _bert_forward, lam)


def bert_mlm_task() -> TrainerTask:
    """Masked-language-model pretraining: cross-entropy over the masked
    positions only (labels == IGNORE_INDEX elsewhere — data/mlm.py)."""
    from pyspark_tf_gke_tpu.data.mlm import IGNORE_INDEX

    def lam(preds, batch):
        logits = preds["mlm_logits"].astype(jnp.float32)  # [B, S, V]
        labels = batch["mlm_labels"]
        mask = (labels != IGNORE_INDEX)
        safe = jnp.where(mask, labels, 0)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
        denom = jnp.maximum(mask.sum(), 1)
        loss = jnp.where(mask, per_tok, 0.0).sum() / denom
        acc = (jnp.where(mask, jnp.argmax(logits, -1) == safe, False).sum()
               / denom)
        metrics = {"loss": loss, "mlm_accuracy": acc,
                   "masked_frac": mask.mean()}
        return _add_moe_aux(loss, metrics, preds)

    return TrainerTask("bert_mlm", _bert_forward, lam)


def causal_lm_task(vocab_chunks: Optional[int] = None) -> TrainerTask:
    """Next-token prediction: shift-by-one cross entropy over every
    position that has a successor (optionally masked by attention_mask).

    ``vocab_chunks=N`` switches to the chunked large-vocab loss
    (``ops/chunked_ce.py``): the model returns final hidden states and
    the LM-head weight is applied chunk-by-chunk inside the loss, so the
    fp32 ``[B, S, V]`` logits — the memory hog of LM training — never
    materialize. Numerics match the dense path to fp32 tolerance."""

    def _apply(model, variables, batch, train, **kw):
        """``(model's output, its step counters)``: a model that sows
        counters (``HybridLM``'s expert layers) has them collected and handed
        on as metrics; any other is applied as ever."""
        kw.update(segment_ids=batch.get("segment_ids"), train=train)
        if not getattr(model, "sows_counters", False):
            return model.apply(variables, batch["input_ids"], **kw), {}
        out, sown = model.apply(variables, batch["input_ids"],
                                mutable=["counters"], **kw)
        return out, model.step_counters(sown.get("counters", {}))

    def _reduce(per_tok, pred_ids, targets, mask, counters):
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            denom = jnp.maximum(m.sum(), 1.0)
            loss = (per_tok * m).sum() / denom
            acc = ((pred_ids == targets) * m).sum() / denom
        else:
            loss = per_tok.mean()
            acc = (pred_ids == targets).astype(jnp.float32).mean()
        return loss, {"loss": loss, "next_token_accuracy": acc, **counters}

    if vocab_chunks:
        from pyspark_tf_gke_tpu.ops.chunked_ce import chunked_cross_entropy

        def forward(model, variables, batch, train, mutable):
            hidden, counters = _apply(model, variables, batch, train,
                                      return_hidden=True)
            head = variables["params"]["lm_head"]
            return {"hidden": hidden, "kernel": head["kernel"],
                    "bias": head.get("bias"), "counters": counters}, None

        def lam(preds, batch):
            ids = batch["input_ids"]
            targets = ids[:, 1:]
            h = preds["hidden"][:, :-1]
            b, s1, e = h.shape
            per_tok, amax = chunked_cross_entropy(
                h.reshape(b * s1, e), preds["kernel"], preds["bias"],
                targets.reshape(-1), num_chunks=vocab_chunks)
            return _reduce(per_tok.reshape(b, s1),
                           amax.reshape(b, s1), targets,
                           batch.get("attention_mask"), preds["counters"])

        return TrainerTask("causal_lm", forward, lam)

    def forward(model, variables, batch, train, mutable):
        return _apply(model, variables, batch, train), None

    def lam(preds, batch):
        logits, counters = preds
        ids = batch["input_ids"]
        targets = ids[:, 1:]
        lg = logits[:, :-1].astype(jnp.float32)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(lg, targets)
        return _reduce(per_tok, jnp.argmax(lg, -1), targets,
                       batch.get("attention_mask"), counters)

    return TrainerTask("causal_lm", forward, lam)


TASKS = {
    "classification": classification_task,
    "regression": regression_task,
    "resnet": resnet_task,
    "vit": vit_task,
    "bert_classification": bert_classification_task,
    "bert_mlm": bert_mlm_task,
    "causal_lm": causal_lm_task,
}


class _CountingIterator:
    """Pass-through iterator that tallies consumed global rows (for
    examples/sec accounting across plain and grad-accum steps)."""

    def __init__(self, it):
        self._it = it
        self.rows = 0

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self._it)
        self.rows += next(iter(batch.values())).shape[0]
        return batch


class _LoopPhase:
    """One host phase of the step loop (``train.input_wait``, ...): an
    annotation in the profiler's trace and, from the same two clock
    reads, the seconds the epoch's span and the registry are given.
    Never a ring span: a ``fit`` of 100,000 steps must not grow a trace."""

    __slots__ = ("name", "total", "_t0", "_annotation")

    def __init__(self, name: str):
        self.name = name
        self.total = 0.0

    def __enter__(self):
        self._annotation = annotate(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.total += time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)


# the jitted functions one optimizer step runs (plain and grad-accum): a
# compile of one of these after a fit's first step is a recompile
STEP_FUNCTIONS = ("train_step", "grad_step", "apply_mean")


class Trainer:
    """Builds sharded state, compiles the step, runs the epoch loop."""

    def __init__(
        self,
        model: nn.Module,
        task: TrainerTask,
        mesh: Mesh,
        learning_rate: float = 1e-3,
        tx: Optional[optax.GradientTransformation] = None,
        fsdp_min_size: int = DEFAULT_MIN_SIZE,
        logical_rules=LOGICAL_RULES,
        ema_decay: float = 0.0,  # >0 maintains an EMA of params (eval/serving)
        mu_dtype: Optional[Any] = None,  # Adam first-moment dtype; bf16
        # halves that slice of the per-step param/optimizer HBM traffic,
        # which weighs most where parameters are many and the batch small
        # (the flagship CNN: 43M params, batch 32). Default f32 keeps
        # reference-parity optimizer numerics; ignored when tx is given.
        metrics_registry=None,  # obs.MetricsRegistry (default: shared)
        event_log=None,  # obs.EventLog (default: shared trail)
        tracer=None,  # obs.TraceRecorder (default: the process's)
    ):
        self.model = model
        self.task = task
        self.mesh = mesh
        self.tx = tx if tx is not None else optax.adam(
            learning_rate, mu_dtype=mu_dtype)
        self.fsdp_min_size = fsdp_min_size
        self.logical_rules = logical_rules
        self.ema_decay = ema_decay
        self._train_step = None
        self._raw_train_step = None
        self._eval_step = None
        self._debug_step = None
        self._grad_step = None
        self._accum_add = None
        self._apply_step = None
        self._scan_steps: Dict[int, Any] = {}
        self.state_shardings = None
        # observability plane (obs/): history stays the artifact format;
        # these are the live/scrapable view of the same loop
        self.metrics_registry = (metrics_registry if metrics_registry
                                 is not None else get_registry())
        self._obs = platform_families(self.metrics_registry)
        self._event_log = event_log if event_log is not None else get_event_log()
        # train.* spans (docs/OBSERVABILITY.md "Training spans"); JAX's
        # trace / lower / compile hang under them (obs/compiles.py)
        self._tracer = tracer if tracer is not None else get_tracer()
        install_compile_listener()
        self._input_wait = _LoopPhase("train.input_wait")
        self._dispatch = _LoopPhase("train.step_dispatch")
        self._first_sync = _LoopPhase("train.first_step_sync")
        self._sync = _LoopPhase("train.epoch_sync")

    # ---- state construction -------------------------------------------------

    def _sample_inputs(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Minimal batch slice for shape-only init: one row per data-parallel
        shard (shard_map paths, e.g. ring attention, need the global batch
        divisible by dp*fsdp even at init). Batches with fewer rows than
        shards — legitimate on multi-host, where the local batch can be
        smaller than the global shard count — are tiled up; this is shape
        tracing only, values are irrelevant."""
        n = self.mesh.shape.get("dp", 1) * self.mesh.shape.get("fsdp", 1)
        rows = len(next(iter(batch.values())))
        if rows < n:
            reps = -(-n // rows)  # ceil
            batch = {k: np.concatenate([np.asarray(v)] * reps) for k, v in batch.items()}
        return {k: v[:n] for k, v in batch.items()}

    def _create_fn(self, sample_batch):
        model, task, tx = self.model, self.task, self.tx

        def create(rng):
            if task.name == "resnet":
                variables = model.init(rng, sample_batch["image"], train=False)
            elif task.name == "vit":
                variables = model.init(rng, sample_batch["image"])
            elif task.name.startswith("bert"):
                variables = model.init(
                    rng,
                    sample_batch["input_ids"],
                    attention_mask=sample_batch.get("attention_mask"),
                )
            elif task.name == "causal_lm":
                variables = model.init(rng, sample_batch["input_ids"])
            elif task.name == "regression":
                variables = model.init(rng, sample_batch["image"])
            else:
                variables = model.init(rng, sample_batch["x"])
            params = variables["params"]
            batch_stats = variables.get("batch_stats")
            return TrainState.create(params, tx, batch_stats,
                                     ema_decay=self.ema_decay)

        return create

    def init_state(self, rng: jax.Array, sample_batch: Dict[str, np.ndarray]) -> TrainState:
        """Init params directly into their target shardings (jit with
        out_shardings) so large models never materialize unsharded."""
        with span("train.init_state", tracer=self._tracer):
            return self._init_state(rng, sample_batch)

    def _init_state(self, rng, sample_batch):
        sample = self._sample_inputs(sample_batch)
        create = self._create_fn(sample)
        abstract = jax.eval_shape(create, rng)

        boxed = any(
            isinstance(l, nn.Partitioned)
            for l in jax.tree.leaves(
                abstract, is_leaf=lambda x: isinstance(x, nn.Partitioned)
            )
        )
        if boxed:
            specs = nn.get_partition_spec(abstract)
            shardings = nn.logical_to_mesh_sharding(specs, self.mesh, self.logical_rules)

            # Unbox WITHOUT the in-jit constraint (see the helper's
            # docstring — raw-Partitioned LOGICAL names crash strict
            # NamedSharding validation); the jit's ``out_shardings``
            # below is the placement authority either way.
            from pyspark_tf_gke_tpu.parallel.compat import (
                unbox_without_constraint,
            )

            create_unboxed = lambda r: unbox_without_constraint(create(r))
        else:
            shardings = jax.tree.map(
                lambda l: NamedSharding(
                    self.mesh, fsdp_spec(l.shape, self.mesh, self.fsdp_min_size)
                ),
                abstract,
            )
            create_unboxed = create

        self.state_shardings = shardings
        with self.mesh:
            state = jax.jit(create_unboxed, out_shardings=shardings)(rng)
        return state

    # ---- compiled steps -----------------------------------------------------

    def _build_steps(self):
        model, task = self.model, self.task
        key_on_names()     # the steps' part scopes are read off their executables

        def train_step(state: TrainState, batch):
            def loss_fn(params):
                variables = {"params": params}
                if state.batch_stats is not None:
                    variables["batch_stats"] = state.batch_stats
                preds, new_batch_stats = task.forward(model, variables, batch, True, True)
                with part_scope("head_loss"):
                    loss, metrics = task.loss_and_metrics(preds, batch)
                return loss, (metrics, new_batch_stats)

            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (_, (metrics, new_batch_stats)), grads = grad_fn(state.params)
            with part_scope("optimizer"):
                if task.has_batch_stats and new_batch_stats is not None:
                    state = state.apply_gradients(grads, batch_stats=new_batch_stats)
                else:
                    state = state.apply_gradients(grads)
            return state, metrics

        def eval_step(state: TrainState, batch):
            variables = {"params": state.params}
            if state.batch_stats is not None:
                variables["batch_stats"] = state.batch_stats
            preds, _ = task.forward(model, variables, batch, False, False)
            _, metrics = task.loss_and_metrics(preds, batch)
            return metrics

        self._raw_train_step = train_step
        self._train_step = jax.jit(
            train_step,
            donate_argnums=0,
            out_shardings=(self.state_shardings, None),
        )
        self._eval_step = jax.jit(eval_step)

    def step(self, state: TrainState, batch: Dict[str, jax.Array]):
        if self._train_step is None:
            self._build_steps()
        with self.mesh:
            return self._train_step(state, batch)

    def _build_accum_steps(self):
        """Two-phase step for gradient accumulation: grads-only compute per
        microbatch, one optimizer apply per A microbatches. Emulates an
        A-times-larger global batch with the same device memory."""
        model, task = self.model, self.task

        def grad_step(state: TrainState, batch):
            def loss_fn(params):
                variables = {"params": params}
                if state.batch_stats is not None:
                    variables["batch_stats"] = state.batch_stats
                preds, new_bs = task.forward(model, variables, batch, True, True)
                with part_scope("head_loss"):
                    loss, metrics = task.loss_and_metrics(preds, batch)
                return loss, (metrics, new_bs)

            (_, (metrics, new_bs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
            return grads, metrics, new_bs

        def apply_step(state: TrainState, grads, new_batch_stats):
            if task.has_batch_stats and new_batch_stats is not None:
                return state.apply_gradients(grads, batch_stats=new_batch_stats)
            return state.apply_gradients(grads)

        def apply_mean(state: TrainState, grads_sum, bs_sum, accum):
            with part_scope("optimizer"):
                grads = jax.tree.map(lambda g: g / accum, grads_sum)
                bs = (
                    None if bs_sum is None
                    else jax.tree.map(lambda b: b / accum, bs_sum)
                )
                return apply_step(state, grads, bs)

        param_shardings = (
            self.state_shardings.params if self.state_shardings is not None else None
        )
        self._grad_step = jax.jit(grad_step, out_shardings=(param_shardings, None, None))
        # One fused add per accumulation round, donating the accumulator —
        # no per-leaf host dispatches and no extra live gradient buffer.
        self._accum_add = jax.jit(
            lambda acc, new: jax.tree.map(jnp.add, acc, new), donate_argnums=0
        )
        # Donate only the state: its buffers back every output 1:1.
        # Donating grads too made XLA warn "donated buffers were not
        # usable" — there is no output left for them to back.
        self._apply_step = jax.jit(
            apply_mean, donate_argnums=0, out_shardings=self.state_shardings
        )

    def accum_step(self, state: TrainState, batches, accum: int):
        """One optimizer step from ``accum`` consecutive global batches
        pulled off ``batches`` (an iterator of device-resident batch
        dicts). Gradients AND batch-norm statistics are averaged over the
        microbatches. Returns (state, averaged metrics)."""
        if self._grad_step is None:
            self._build_accum_steps()
        with self.mesh:
            acc = None  # (grads_sum, metrics_sum, bs_sum)
            for _ in range(accum):
                with self._input_wait:
                    batch = next(batches)
                with self._dispatch:
                    grads, metrics, new_bs = self._grad_step(state, batch)
                    new = (grads, metrics) if new_bs is None else (grads, metrics, new_bs)
                    acc = new if acc is None else self._accum_add(acc, new)
            grads_sum, metrics_sum = acc[0], acc[1]
            bs_sum = acc[2] if len(acc) == 3 else None
            with self._dispatch:
                state = self._apply_step(state, grads_sum, bs_sum, accum)
        return state, {k: v / accum for k, v in metrics_sum.items()}

    def debug_step(self, state: TrainState, batch: Dict[str, jax.Array]):
        """Undonated train step for utils.debug determinism checks — the
        input state stays valid, so the same (state, batch) can be
        replayed and fingerprinted."""
        if self._train_step is None:
            self._build_steps()
        if self._debug_step is None:
            self._debug_step = jax.jit(
                self._raw_train_step, out_shardings=(self.state_shardings, None)
            )
        with self.mesh:
            return self._debug_step(state, batch)

    def multi_step(self, state: TrainState, batch: Dict[str, jax.Array], k: int):
        """Run ``k`` train steps on the same batch inside ONE dispatch via an
        on-device ``lax.scan``. Amortizes per-dispatch host latency — for
        step-time measurement and for small models where dispatch
        dominates. Returns
        (state, stacked metrics with leading dim k)."""
        if self._train_step is None:
            self._build_steps()
        fn = self._scan_steps.get(k)
        if fn is None:
            raw = self._raw_train_step

            def scan_fn(state, batch):
                def body(s, _):
                    s2, m = raw(s, batch)
                    return s2, m
                return jax.lax.scan(body, state, None, length=k)

            fn = jax.jit(scan_fn, donate_argnums=0,
                         out_shardings=(self.state_shardings, None))
            self._scan_steps[k] = fn
        with self.mesh:
            return fn(state, batch)

    def evaluate(self, state: TrainState, batches,
                 use_ema: bool = False) -> Dict[str, float]:
        """Metrics accumulate as device scalars — one host sync at the
        end, not one per batch (a per-batch ``float(v)`` readback
        serializes dispatch against the device queue). ``use_ema``
        evaluates the EMA weights (same jit trace — only the leaves
        swap)."""
        if use_ema:
            if state.ema_params is None:
                raise ValueError("use_ema=True but the trainer was built "
                                 "with ema_decay=0")
            state = state.replace(params=state.ema_params)
        if self._eval_step is None:
            self._build_steps()
        sums: Optional[Dict[str, jax.Array]] = None
        count = 0
        with self.mesh:
            for batch in batches:
                metrics = self._eval_step(state, batch)
                sums = (
                    metrics if sums is None
                    else jax.tree.map(jnp.add, sums, metrics)
                )
                count += 1
        if sums is None:
            return {}
        host = jax.device_get(sums)
        return {k: float(v) / count for k, v in host.items()}

    # ---- epoch loop ---------------------------------------------------------

    def fit(
        self,
        state: TrainState,
        batches,  # iterator of host-local numpy batch dicts
        epochs: int,
        steps_per_epoch: int,
        val_batches: Optional[Callable[[], Any]] = None,  # () -> iterable of batch dicts
        checkpoint_manager=None,
        log_every: int = 0,
        heartbeat=None,  # train.resilience.Heartbeat
        fault_injector=None,  # train.resilience.FaultInjector (chaos tests)
        prefetch: int = 2,  # device-resident batches staged ahead (0 = inline)
        grad_accum: int = 1,  # microbatches accumulated per optimizer step
        val_use_ema: bool = False,  # validate the EMA weights (the ones exported)
    ) -> Tuple[TrainState, Dict[str, list]]:
        """Run the training loop; returns final state and a Keras-style
        history dict (the reference's ``history.history`` analog,
        ``train_tf_ps.py:674-679``), extended with the north-star timing
        metrics (step_time_ms, examples_per_sec)."""
        from pyspark_tf_gke_tpu.data.pipeline import prefetch_to_device

        # one trace per call: train.fit > train.epoch > train.validate,
        # train.checkpoint, jax.*; what lies outside the epochs is what
        # a fit call costs besides its steps
        with span("train.fit", tracer=self._tracer,
                  attrs={"task": self.task.name, "epochs": epochs,
                         "steps_per_epoch": steps_per_epoch}):
            data_sharding = batch_sharding(self.mesh)
            history: Dict[str, list] = {}
            # Host-side mirror of state.step: one sync here, then pure
            # increments — no per-step device readback for liveness.
            global_step = int(jax.device_get(state.step))
            prefetched = prefetch_to_device(batches, data_sharding, size=prefetch)
            device_batches = _CountingIterator(prefetched)
            try:
                return self._fit_epochs(
                    state, device_batches, epochs, steps_per_epoch, val_batches,
                    checkpoint_manager, log_every, heartbeat, fault_injector,
                    history, global_step, grad_accum, val_use_ema,
                )
            finally:
                # Stop the prefetch worker: it must not keep draining the
                # caller's iterator after fit returns or raises (restart
                # wrappers reuse that iterator).
                prefetched.close()

    def _fit_epochs(
        self, state, device_batches, epochs, steps_per_epoch, val_batches,
        checkpoint_manager, log_every, heartbeat, fault_injector,
        history, global_step, grad_accum, val_use_ema=False,
    ):
        from pyspark_tf_gke_tpu.data.pipeline import put_global_batch

        self._event_log.emit(
            "train_fit_start", task=self.task.name, epochs=epochs,
            steps_per_epoch=steps_per_epoch, start_step=global_step,
            grad_accum=grad_accum)
        start_step = global_step
        phases = (self._input_wait, self._dispatch, self._first_sync, self._sync)

        def recompiled(fun: str, seconds: float) -> None:
            # "which step recompiled": the step's own function compiled
            # again after this fit had already run a step
            if fun in STEP_FUNCTIONS and global_step > start_step:
                self._event_log.emit(
                    "train_recompile", fun=fun, global_step=global_step,
                    seconds=round(seconds, 3))

        for epoch in range(epochs):
            with span("train.epoch", attrs={"epoch": epoch + 1}) as epoch_span, \
                    on_compile(recompiled):
                for phase in phases:
                    phase.total = 0.0
                # Metrics accumulate as device scalars — no host sync inside the
                # step loop, so dispatch overlaps with next-batch preparation.
                sums: Dict[str, jax.Array] = {}
                t_first_step = 0.0
                epoch_start = time.perf_counter()
                examples = 0
                for step_i in range(steps_per_epoch):
                    rows_before = device_batches.rows
                    waited = self._input_wait.total
                    t0 = time.perf_counter()
                    if grad_accum > 1:
                        state, metrics = self.accum_step(state, device_batches, grad_accum)
                    else:
                        with self._input_wait:
                            batch = next(device_batches)
                        with self._dispatch:
                            state, metrics = self.step(state, batch)
                    if step_i == 0:
                        # first step includes compilation; keep it out of step-time stats
                        with self._first_sync:
                            jax.block_until_ready(metrics)
                        t_first_step = time.perf_counter() - t0
                    # global rows consumed this optimizer step
                    step_rows = device_batches.rows - rows_before
                    examples += step_rows
                    global_step += 1
                    # obs plane: counters record everything; the histogram
                    # records steady steps only — each epoch's step 0 is
                    # excluded (epoch 0's includes compile; later epochs'
                    # absorb the drained dispatch queue at the
                    # block_until_ready above), mirroring the history's
                    # steady_steps accounting. Steady observations are the
                    # host dispatch interval: with the step loop kept
                    # async by design, this equals device step time once
                    # the in-flight queue saturates, and under-reads it
                    # before then — the history's synced epoch-level
                    # step_time_ms stays the calibration reference.
                    self._obs["train_steps_total"].inc()
                    self._obs["train_examples_total"].inc(step_rows)
                    self._obs["train_input_wait_ms"].observe(
                        (self._input_wait.total - waited) * 1000.0)
                    if step_i != 0:
                        self._obs["train_step_time_ms"].observe(
                            (time.perf_counter() - t0) * 1000.0)
                    if heartbeat is not None:
                        heartbeat.beat(global_step)
                    if fault_injector is not None:
                        fault_injector.maybe_fail(global_step)
                    # each sum is one more program in the device's queue:
                    # where the runtime bounds what is in flight, this is
                    # where a loop that runs ahead of the device is held
                    with annotate("train.metrics_accumulate"):
                        for k, v in metrics.items():
                            sums[k] = sums[k] + v if k in sums else v
                    if log_every and (step_i + 1) % log_every == 0:
                        logger.info(
                            "epoch %d step %d/%d loss=%.4f",
                            epoch + 1, step_i + 1, steps_per_epoch,
                            float(sums.get("loss", 0.0)) / (step_i + 1),
                        )
                with self._sync:
                    sums_host = {k: float(jax.device_get(v)) for k, v in sums.items()}
                    jax.block_until_ready(state.step)
                epoch_time = time.perf_counter() - epoch_start
                if epoch_span is not None:
                    epoch_span.set("steps", steps_per_epoch)
                    epoch_span.set("rows", examples)
                    epoch_span.set("input_wait_ms", self._input_wait.total * 1e3)
                    epoch_span.set("dispatch_ms", self._dispatch.total * 1e3)
                    epoch_span.set(
                        "sync_ms", (self._first_sync.total + self._sync.total) * 1e3)
                    # the expert layers' counters, summed over the epoch's steps
                    for k, v in sums_host.items():
                        if k.startswith("moe_held_"):
                            epoch_span.set(k, v)

                for k, v in sums_host.items():
                    history.setdefault(k, []).append(v / steps_per_epoch)
                steady_steps = max(steps_per_epoch - 1, 1)
                steady_time = max(epoch_time - t_first_step, 1e-9)
                steady_examples = examples * steady_steps / steps_per_epoch
                step_ms = steady_time / steady_steps * 1000.0
                history.setdefault("step_time_ms", []).append(step_ms)
                history.setdefault("examples_per_sec", []).append(steady_examples / steady_time)

                msg = " - ".join(
                    f"{k}: {history[k][-1]:.4f}" for k in sums
                )
                logger.info("Epoch %d/%d - %s - %.1f ms/step", epoch + 1, epochs, msg, step_ms)
                self._obs["train_epochs_total"].inc()
                if "loss" in history:
                    self._obs["train_last_loss"].set(history["loss"][-1])
                for key in ("moe_held_assignments", "moe_held_load_max",
                            "moe_held_rows_walked"):
                    if key in history:
                        self._obs[f"train_{key}"].set(history[key][-1])
                self._event_log.emit(
                    "train_epoch_end", epoch=epoch + 1, global_step=global_step,
                    step_time_ms=round(step_ms, 3),
                    loss=history.get("loss", [None])[-1])

                if val_batches is not None:
                    with span("train.validate"):
                        val_sharding = batch_sharding(self.mesh)
                        val_iter = (
                            put_global_batch(b, val_sharding) for b in val_batches()
                        )
                        val_metrics = self.evaluate(state, val_iter,
                                                    use_ema=val_use_ema)
                    for k, v in val_metrics.items():
                        history.setdefault(f"val_{k}", []).append(v)
                    logger.info(
                        "Epoch %d validation - %s", epoch + 1,
                        " - ".join(f"{k}: {v:.4f}" for k, v in val_metrics.items()),
                    )

                if checkpoint_manager is not None:
                    with span("train.checkpoint"):
                        checkpoint_manager.maybe_save(state, history)

        return state, history
