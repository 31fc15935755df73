"""Runner of kind ``train``: ``Trainer.fit`` as ``train/lm_pretrain.py`` runs
it (``causal_lm_task``, ``make_optimizer`` defaults, a mesh over the cell's
chips), fed by a seeded host iterator through ``prefetch_to_device``.

Set-up builds ONE trainer with its compiled step and its state, drives it
from the seed through its first steps by the window's own call (``fit``) and
feed, reads what the comparison needs from that state through ``fit``'s own
``checkpoint_manager`` hook, and hands the same trainer and state to the
window. The reference follows those first steps after the window has closed
and the program's state is freed.
"""

import gc
import time

import numpy as np

from lib import checks, traffic, weights as W
from lib import trace as tracelib

PROOF_STEPS = 3


Feed = traffic.TrainFeed


class Probe:
    """Stands where ``fit`` takes a checkpoint manager: after each epoch it
    is handed the state, and keeps per-leaf norms (a few hundred floats)."""

    def __init__(self, jax, seed, shapes, b1):
        import jax.numpy as jnp

        def norms(tree):
            return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                    for n, v in W.flatten(tree).items()}

        def delta(params, key):
            flat = W.flatten(params)
            return {n: jnp.sqrt(jnp.sum(jnp.square(
                v - W.make_leaf(key, n, W.name_tag(n), shapes[n]))))
                for n, v in flat.items()}

        self._jax, self._key = jax, W.seed_key(seed)
        self._norms, self._delta, self._b1 = jax.jit(norms), jax.jit(delta), b1
        self.grad_norm, self.delta_norm, self.epoch = None, None, 0

    def maybe_save(self, state, history):
        self.epoch += 1
        if self.epoch == 1:
            # Adam's first moment after one step is (1 - b1) * g1: the first
            # gradient as the optimizer got it
            mu = next(s.mu for s in self._jax.tree.leaves(
                state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(s, "mu"))
            got = self._jax.device_get(self._norms(mu))
            self.grad_norm = {n: float(v) / (1.0 - self._b1) for n, v in got.items()}
        if self.epoch == PROOF_STEPS:
            got = self._jax.device_get(self._delta(state.params, self._key))
            self.delta_norm = {n: float(v) for n, v in got.items()}


def build(cfg, cell, mix, seed, devices, jax, lap):
    import jax.numpy as jnp

    from pyspark_tf_gke_tpu.models.causal_lm import CausalLM, CausalLMConfig
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh
    from pyspark_tf_gke_tpu.train.harness import make_optimizer
    from pyspark_tf_gke_tpu.train.trainer import Trainer, causal_lm_task

    tr = cell["train"]
    mesh = make_mesh(tr["mesh"], devices=devices)
    mcfg = CausalLMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
        max_seq_len=cfg["n_positions"],
        layer_norm_eps=cfg["layer_norm_epsilon"], dtype=jnp.bfloat16,
        remat=bool(tr["remat"]))
    model = CausalLM(mcfg, mesh=mesh)
    task = causal_lm_task(vocab_chunks=tr["vocab_chunks"] or None)
    opt = tr["optimizer"]
    tx = make_optimizer(opt["learning_rate"], optimizer=opt["name"])
    trainer = Trainer(model, task, mesh, tx=tx)
    rows = int(tr["rows_per_chip"]) * len(devices)
    sample = {"input_ids": np.zeros((rows, int(mix["seq_len"])), np.int32)}
    lap("import_program")
    state = trainer.init_state(jax.random.PRNGKey(0), sample)
    jax.block_until_ready(state.params)
    lap("init_state")
    shapes = W.gpt2_leaf_shapes(cfg)
    shard = W.flatten(trainer.state_shardings.params)
    params = jax.jit(lambda key: W.nest(W.make_leaves(key, shapes)),
                     out_shardings=W.nest({n: shard[n] for n in shapes}))(W.seed_key(seed))
    state = state.replace(params=params)
    jax.block_until_ready(state.params)
    lap("weights")
    return trainer, state, rows, shapes


def compare_with_reference(cfg, cell, seed, batches, prog) -> dict:
    """The numbers compared, each a gap between the program's reading and
    the reference's (lib/checks.py says how a leaf gap is taken)."""
    from reference import gpt2

    ref = gpt2.train_steps(
        cfg, seed, batches, cell["train"]["optimizer"], steps=PROOF_STEPS,
        rows_block=int(cell["check"]["reference_rows_block"]))
    return gaps(prog, ref)


NOT_COMPARED = ("loss1_gap", "loss2_gap")


def compared(readings: dict) -> dict:
    """The first two steps' loss gaps are worked out and shown but not
    compared: neither the control nor a fault reads apart from sound runs on
    them on every seed, so a limit could only fail sound runs (PERF.md §2)."""
    return {k: v for k, v in readings.items() if k not in NOT_COMPARED}


def gaps(prog: dict, ref: dict) -> dict:
    out = {f"loss{i + 1}_gap": abs(prog["loss"][i] - ref["loss"][i]) / abs(ref["loss"][i])
           for i in range(PROOF_STEPS)}
    out["grad1_gap"] = checks.worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    out["delta3_gap"] = checks.worst_leaf_gap(
        prog["delta_norm"], ref["delta_norm"],
        leaves=checks.moving_leaves(ref["grad_norm"]))
    return out


def run(ctx: dict) -> dict:
    import jax

    spec, seed, seconds = ctx["spec"], ctx["seed"], ctx["seconds"]
    cfg, cell, mix = spec["config"], spec["cell"], spec["traffic"]
    now = time.perf_counter
    prefetch = int(mix["prefetch"])
    parts, last = {}, [ctx["t_start"]]

    def lap(name):
        """One part of set-up: its seconds, and what JAX traced, lowered and
        compiled or loaded in it."""
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

    # the first steps, one epoch each so that history keeps each step's loss
    probe = Probe(jax, seed, shapes, float(cell["train"]["optimizer"]["b1"]))
    state, hist = fit(state, PROOF_STEPS, 1, hook=probe)
    prog = {"loss": [float(x) for x in hist["loss"]],
            "grad_norm": probe.grad_norm, "delta_norm": probe.delta_norm}
    proof_batches = [feed.batch(k)["input_ids"] for k in range(PROOF_STEPS)]
    lap("proof_steps")

    # warm the loop's own small programs and time a step, to size the window
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
        t_tr0 = now()          # the profiler is up: the traced window opens
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
    del state, trainer, probe
    gc.collect()

    t_ref0 = now()
    all_gaps = compare_with_reference(cfg, cell, seed, proof_batches, prog)
    readings = compared(all_gaps)
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
        }
    return {
        "end_to_end": end_to_end, "layers": layers, "readings": readings,
        "attempted": n_steps, "failed": 0 if np.isfinite(final_loss) else n_steps,
        "memory_peak_bytes": memory_peak, "device_extra": device_extra,
        "breakdown": breakdown,
        "info": {"window_s": window_s, "steps": n_steps, "rows": rows,
                 "warm_step_ms": step_s * 1e3, "final_loss": final_loss,
                 "setup_parts": parts,
                 "reference_s": t_ref1 - t_ref0,
                 **{k: all_gaps[k] for k in NOT_COMPARED},
                 "first_losses": prog["loss"]},
    }
