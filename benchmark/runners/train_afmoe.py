"""Runner of kind ``train_afmoe``: ``runners/train_nemotron_h.py``'s run for the
hybrid decoder (``models/hybrid_lm.py``) built from an ``afmoe`` file, as
``train/lm_pretrain.py --arch afmoe --model-config`` builds it. The same
trainer, feed, proof steps, window, probe and comparison (``Feed``, ``Probe``,
``gaps``, ``compared`` are ``runners/train.py``'s) and the same counters handed
to the readers; what differs is who makes the weights
(``lib/weights_afmoe.py``), which reference follows the first steps
(``reference/afmoe.py``), that the cell's optimizer may name one of
``make_optimizer``'s schedules (``lm_pretrain --lr-schedule`` /
``--warmup-steps``: the rate's warm-up, PERF.md section 4), and
that a checkout whose program lacks the family fails at once, before anything
is built."""

import gc
import time

import numpy as np

from lib import trace as tracelib
from lib import weights as W
from lib import weights_afmoe as A
from runners.train import NOT_COMPARED, PROOF_STEPS, Feed, Probe, compared, gaps

COUNTERS = ("moe_held_assignments", "moe_held_load_max")


def build(cfg, cell, mix, seed, devices, jax, lap):
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.models.hybrid_lm import HybridLM, config_from_file
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.harness import make_optimizer
    from pyspark_tf_gke_tpu.train.trainer import Trainer, causal_lm_task

    tr = cell["train"]
    mesh = make_mesh(tr["mesh"], devices=devices)
    model = HybridLM(config_from_file(cfg, dtype=jnp.bfloat16, remat=bool(tr["remat"])),
                     mesh=mesh)
    task = causal_lm_task(vocab_chunks=tr["vocab_chunks"] or None)
    opt = tr["optimizer"]
    tx = make_optimizer(opt["learning_rate"], optimizer=opt["name"],
                        schedule=opt.get("schedule", "constant"),
                        warmup_steps=int(opt.get("warmup_steps", 0)),
                        total_steps=int(opt.get("total_steps", 0)))
    trainer = Trainer(model, task, mesh, tx=tx)
    rows = int(tr["rows_per_chip"]) * len(devices)
    sample = {"input_ids": np.zeros((rows, int(mix["seq_len"])), np.int32)}
    lap("import_program")
    state = trainer.init_state(jax.random.PRNGKey(0), sample)
    jax.block_until_ready(state.params)
    lap("init_state")
    shapes = A.leaf_shapes(cfg)
    shard = W.flatten(trainer.state_shardings.params)
    missing = set(shapes) ^ set(shard)
    if missing:
        raise KeyError(f"program and lib/weights_afmoe.py disagree on leaves: {sorted(missing)}")
    params = jax.jit(lambda key: W.nest(W.make_leaves(key, shapes)),
                     out_shardings=W.nest({n: shard[n] for n in shapes}))(W.seed_key(seed))
    state = state.replace(params=params)
    jax.block_until_ready(state.params)
    lap("weights")
    return trainer, state, rows, shapes


def compare_with_reference(cfg, cell, seed, batches, prog) -> dict:
    from reference import afmoe

    ref = afmoe.train_steps(
        cfg, seed, batches, cell["train"]["optimizer"], steps=PROOF_STEPS,
        rows_block=int(cell["check"]["reference_rows_block"]))
    return gaps(prog, ref)


def run(ctx: dict) -> dict:
    import jax

    # a checkout whose program lacks the family fails here, at once
    # (``models/hybrid_lm.py`` is older than the kinds this cell builds)
    from pyspark_tf_gke_tpu.models.hybrid_lm import GatedAttention  # noqa: F401

    spec, seed, seconds = ctx["spec"], ctx["seed"], ctx["seconds"]
    cfg, cell, mix = spec["config"], spec["cell"], spec["traffic"]
    now = time.perf_counter
    prefetch = int(mix["prefetch"])
    parts, last = {}, [ctx["t_start"]]

    def lap(name):
        t = now()
        parts[name] = {"s": t - last[0], **ctx["compiles"].mark()}
        last[0] = t

    lap("start_and_devices")
    trainer, state, rows, shapes = build(cfg, cell, mix, seed, ctx["devices"], jax, lap)
    feed = Feed(mix, seed, cfg["vocab_size"], rows)
    tokens_per_step = rows * int(mix["seq_len"])
    consumed = 0

    def fit(state, epochs, steps, hook=None):
        nonlocal consumed
        state, hist = trainer.fit(state, feed, epochs=epochs, steps_per_epoch=steps,
                                  checkpoint_manager=hook, prefetch=prefetch)
        consumed += epochs * steps
        feed.rewind(consumed)
        return state, hist

    probe = Probe(jax, seed, shapes, float(cell["train"]["optimizer"]["b1"]))
    state, hist = fit(state, PROOF_STEPS, 1, hook=probe)
    prog = {"loss": [float(x) for x in hist["loss"]], "grad_norm": probe.grad_norm,
            "delta_norm": probe.delta_norm}
    proof_batches = [feed.batch(k)["input_ids"] for k in range(PROOF_STEPS)]
    lap("proof_steps")

    warm_steps = int(cell["warmup_steps"])
    state, hist = fit(state, 1, warm_steps)
    jax.block_until_ready(state.params)
    step_s = max(hist["step_time_ms"][-1] / 1e3, 1e-6)
    n_steps = max(2, int(seconds / step_s))
    lap("warm")

    compiles0 = ctx["compiles"].count
    traced = None
    if ctx["trace"]:
        n_tr = max(2, int(min(float(cell["trace_seconds"]), seconds) / step_s))
        t_tr_start = now()
        tracelib.start(ctx["trace_dir"])
        t_tr0 = now()
        state, _ = fit(state, 1, n_tr)
        jax.block_until_ready(state.params)
        t_tr1 = now()
        jax.profiler.stop_trace()
        traced = {"window_s": t_tr1 - t_tr0, "steps": n_tr}
        n_steps = max(2, n_steps - n_tr)
    t_open = now()
    setup_s = t_open - ctx["t_start"] - ((t_open - t_tr_start) if traced else 0.0)
    state, hist = fit(state, 1, n_steps)
    jax.block_until_ready(state.params)
    t_close = now()
    compiles = ctx["compiles"].count - compiles0
    window_s = t_close - t_open

    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in ctx["devices"])
    final_loss = float(hist["loss"][-1])
    # the window's mean of each step counter (the trainer's history)
    counters = {k: float(hist[k][-1]) for k in COUNTERS if k in hist}
    del state, trainer, probe
    gc.collect()

    t_ref0 = now()
    read = compare_with_reference(cfg, cell, seed, proof_batches, prog)
    readings = compared(read)
    t_ref1 = now()
    readings["compiles_in_window"] = float(compiles)
    readings["loss_not_finite"] = 0.0 if np.isfinite(final_loss) else 1.0

    end_to_end = {"setup_s": setup_s,
                  "train_tok_s": n_steps * tokens_per_step / window_s}
    layers, breakdown, device_extra = None, None, None
    if ctx["trace"]:
        tr = tracelib.load(ctx["trace_dir"])
        device_extra = {"busy_s": tracelib.busy_seconds(tr),
                        "window_s": traced["window_s"]}
        breakdown = {"device_ops": tracelib.top_ops(tr),
                     "idle_gaps": tracelib.idle_gaps(tr)}
        layers = {
            "cfg": cfg, "cell": cell, "traffic": mix, "peaks": ctx["peaks"],
            "chips": ctx["chips"], "window_s": window_s,
            "trace": tr, "trace_window_s": traced["window_s"],
            "trace_steps": traced["steps"], "steps": n_steps,
            "rows": rows, "tokens_per_step": tokens_per_step,
            "counters": counters,
        }
    return {
        "end_to_end": end_to_end, "layers": layers, "readings": readings,
        "attempted": n_steps, "failed": 0 if np.isfinite(final_loss) else n_steps,
        "memory_peak_bytes": memory_peak, "device_extra": device_extra,
        "breakdown": breakdown,
        "info": {"window_s": window_s, "steps": n_steps, "rows": rows,
                 "warm_step_ms": step_s * 1e3, "final_loss": final_loss,
                 "setup_parts": parts, "counters": counters,
                 "reference_s": t_ref1 - t_ref0,
                 **{k: read[k] for k in NOT_COMPARED},
                 "first_losses": prog["loss"]},
    }
