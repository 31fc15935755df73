import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.data.pipeline import BatchIterator
from pyspark_tf_gke_tpu.data.synthetic import (
    synthetic_classification_arrays,
    synthetic_tokens,
)
from pyspark_tf_gke_tpu.models import BertConfig, BertForPretraining, CNNRegressor, MLPClassifier, ResNet50
from pyspark_tf_gke_tpu.train.checkpoint import CheckpointManager
from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
from pyspark_tf_gke_tpu.utils.seeding import make_rng


def _fit(trainer, arrays, batch_size, epochs=2, steps=8, seed=0):
    it = BatchIterator(arrays, batch_size, seed=seed)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    state, history = trainer.fit(state, it, epochs=epochs, steps_per_epoch=steps)
    return state, history


def test_mlp_loss_decreases(mesh_dp):
    X, y = synthetic_classification_arrays(n=512, num_classes=5)
    model = MLPClassifier(num_classes=5)
    trainer = Trainer(model, TASKS["classification"](), mesh_dp, learning_rate=1e-2)
    _, history = _fit(trainer, {"x": X, "y": y}, batch_size=64, epochs=3, steps=8)
    assert history["loss"][-1] < history["loss"][0]
    assert history["accuracy"][-1] > 0.3
    assert "step_time_ms" in history and "examples_per_sec" in history


def test_cnn_regression_trains(mesh_dp):
    rng = np.random.default_rng(0)
    images = rng.uniform(0, 1, (64, 32, 40, 3)).astype(np.float32)
    targets = rng.uniform(0, 30, (64, 2)).astype(np.float32)
    model = CNNRegressor(flat=False)
    trainer = Trainer(model, TASKS["regression"](), mesh_dp, learning_rate=1e-3)
    _, history = _fit(trainer, {"image": images, "target": targets}, batch_size=16,
                      epochs=2, steps=4)
    assert history["loss"][-1] < history["loss"][0]
    assert "mae" in history and "mse" in history


def test_fsdp_sharded_training(mesh_dp_fsdp):
    """Params large enough to shard over fsdp; loss must still decrease and
    state shardings must actually split the big kernel."""
    X, y = synthetic_classification_arrays(n=256, input_dim=8, num_classes=4)
    model = MLPClassifier(num_classes=4, hidden=(256, 512))
    trainer = Trainer(model, TASKS["classification"](), mesh_dp_fsdp,
                      learning_rate=1e-2, fsdp_min_size=1024)
    it = BatchIterator({"x": X, "y": y}, 32, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    big_kernel = state.params["Dense_1"]["kernel"]  # 256x512
    spec = big_kernel.sharding.spec
    assert "fsdp" in str(spec)
    state, history = trainer.fit(state, it, epochs=2, steps_per_epoch=8)
    assert history["loss"][-1] < history["loss"][0]
    # adam moments share the param sharding
    mu = state.opt_state[0].mu["Dense_1"]["kernel"]
    assert mu.sharding == big_kernel.sharding


def test_resnet_batchstats_update(mesh_dp):
    rng = np.random.default_rng(0)
    images = rng.uniform(0, 1, (16, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 4, 16).astype(np.int32)
    model = ResNet50(num_classes=4, dtype=None)
    trainer = Trainer(model, TASKS["resnet"](), mesh_dp, learning_rate=1e-3)
    it = BatchIterator({"image": images, "label": labels}, 8, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    bs_before = jax.device_get(jax.tree.leaves(state.batch_stats)[0]).copy()
    state, _ = trainer.fit(state, it, epochs=1, steps_per_epoch=2)
    bs_after = jax.device_get(jax.tree.leaves(state.batch_stats)[0])
    assert not np.allclose(bs_before, bs_after)


def test_bert_tp_training(mesh_tp):
    """BERT with logical tp/fsdp sharding on a dp=2,fsdp=2,tp=2 mesh."""
    cfg = BertConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                     intermediate_size=128, max_position_embeddings=64,
                     dtype=jnp.float32)
    model = BertForPretraining(cfg, mesh=mesh_tp)
    batch = synthetic_tokens(batch=16, seq_len=32, vocab_size=256)
    trainer = Trainer(model, TASKS["bert_classification"](), mesh_tp,
                      learning_rate=1e-3)
    it = BatchIterator(batch, 8, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    # mlp_in kernel is annotated (embed, mlp) → tp shards the wide dim
    k = state.params["encoder"]["layer_0"]["mlp_in"]["kernel"]
    assert "tp" in str(k.sharding.spec)
    state, history = trainer.fit(state, it, epochs=2, steps_per_epoch=4)
    assert np.isfinite(history["loss"]).all()
    assert history["loss"][-1] < history["loss"][0]


def test_checkpoint_roundtrip(tmp_path, mesh_dp):
    X, y = synthetic_classification_arrays(n=128, num_classes=3)
    model = MLPClassifier(num_classes=3)
    trainer = Trainer(model, TASKS["classification"](), mesh_dp, learning_rate=1e-2)
    it = BatchIterator({"x": X, "y": y}, 32, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    state, _ = trainer.fit(state, it, epochs=1, steps_per_epoch=3)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(state, {"loss": [1.0]})
    assert mgr.latest_step() == 3

    state2 = trainer.init_state(make_rng(0), next(iter(it)))
    restored = mgr.restore(state2)
    assert int(restored.step) == 3
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
        np.testing.assert_allclose(jax.device_get(a), jax.device_get(b))
    assert os.path.exists(tmp_path / "ckpt" / "history.json")
    mgr.close()


def test_maybe_save_fires_on_elapsed_steps(tmp_path, mesh_dp):
    """Epoch-end steps rarely hit an exact modulus; maybe_save must fire
    whenever >= every_steps elapsed since the last save."""
    X, y = synthetic_classification_arrays(n=96, num_classes=3)
    model = MLPClassifier(num_classes=3)
    trainer = Trainer(model, TASKS["classification"](), mesh_dp, learning_rate=1e-2)
    it = BatchIterator({"x": X, "y": y}, 32, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    mgr = CheckpointManager(str(tmp_path / "c"), every_steps=5)
    # 3 steps/epoch, every_steps=5 → saves expected at steps 6 and 12
    state, _ = trainer.fit(state, it, epochs=4, steps_per_epoch=3,
                           checkpoint_manager=mgr)
    assert mgr.latest_step() == 12
    mgr.close()


# ---- gradient accumulation + LR schedules -----------------------------------

def test_grad_accum_matches_large_batch(mesh_dp):
    """A=2 accumulation over two half-batches must equal one full-batch
    step (same data, mean loss), bit-exact on CPU f32."""
    import jax.numpy as jnp
    from pyspark_tf_gke_tpu.data.pipeline import put_global_batch
    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    rng = np.random.default_rng(3)
    X = rng.normal(size=(32, 3)).astype(np.float32)
    y = rng.integers(0, 4, 32).astype(np.int32)
    sharding = batch_sharding(mesh_dp)

    def fresh(trainer_cls=Trainer):
        t = trainer_cls(MLPClassifier(num_classes=4), TASKS["classification"](),
                        mesh_dp, learning_rate=1e-2)
        s = t.init_state(make_rng(0), {"x": X, "y": y})
        return t, s

    # full batch, one step
    t1, s1 = fresh()
    s1, m1 = t1.step(s1, put_global_batch({"x": X, "y": y}, sharding))

    # two half batches, accumulated
    t2, s2 = fresh()
    halves = iter([
        put_global_batch({"x": X[:16], "y": y[:16]}, sharding),
        put_global_batch({"x": X[16:], "y": y[16:]}, sharding),
    ])
    s2, m2 = t2.accum_step(s2, halves, accum=2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


def test_fit_with_grad_accum(mesh_dp):
    from pyspark_tf_gke_tpu.data.pipeline import BatchIterator
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    rng = np.random.default_rng(0)
    data = {"x": rng.normal(size=(64, 3)).astype(np.float32),
            "y": rng.integers(0, 4, 64).astype(np.int32)}
    trainer = Trainer(MLPClassifier(num_classes=4), TASKS["classification"](),
                      mesh_dp, learning_rate=1e-2)
    state = trainer.init_state(make_rng(0), data)
    it = BatchIterator(data, 16, seed=7)
    state, history = trainer.fit(state, it, epochs=2, steps_per_epoch=2,
                                 grad_accum=2)
    assert len(history["loss"]) == 2
    assert all(np.isfinite(v) for v in history["loss"])
    # 2 optimizer steps/epoch x 2 epochs, each consuming 2 microbatches
    assert int(jax.device_get(state.step)) == 4


def test_make_optimizer_schedules():
    from pyspark_tf_gke_tpu.train.harness import make_optimizer

    for sched in ("constant", "cosine", "warmup_cosine"):
        warmup = 10 if sched == "warmup_cosine" else 0
        tx = make_optimizer(1e-3, sched, total_steps=100, warmup_steps=warmup)
        assert tx is not None
    with pytest.raises(ValueError, match="unknown lr schedule"):
        make_optimizer(1e-3, "linear")


def test_async_checkpoint_with_donated_training(tmp_path, mesh_dp):
    """Async save must snapshot the state before returning: the trainer
    keeps stepping (donating/overwriting the very buffers being saved)
    while the write completes in the background, and the restored
    checkpoint must equal the state AT save time, not after."""
    X, y = synthetic_classification_arrays(n=128, num_classes=3)
    model = MLPClassifier(num_classes=3)
    trainer = Trainer(model, TASKS["classification"](), mesh_dp, learning_rate=1e-2)
    it = BatchIterator({"x": X, "y": y}, 32, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    state, _ = trainer.fit(state, it, epochs=1, steps_per_epoch=2)

    saved_params = jax.device_get(state.params)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=True)
    mgr.save(state, {"loss": [1.0]})
    # keep training immediately — donates the in-flight state's buffers
    state, _ = trainer.fit(state, it, epochs=1, steps_per_epoch=3)
    mgr.wait()
    assert mgr.latest_step() == 2

    template = trainer.init_state(make_rng(1), next(iter(it)))
    restored = mgr.restore(template)
    assert int(restored.step) == 2
    for a, b in zip(jax.tree.leaves(saved_params), jax.tree.leaves(restored.params)):
        np.testing.assert_allclose(np.asarray(a), jax.device_get(b))
    mgr.close()


def test_make_optimizer_families(mesh_dp):
    """Every optimizer family must build and train the MLP a step."""
    from pyspark_tf_gke_tpu.data.pipeline import put_global_batch
    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding
    from pyspark_tf_gke_tpu.train.harness import make_optimizer

    X, y = synthetic_classification_arrays(n=64, num_classes=3)
    batch = {"x": X[:32], "y": y[:32]}
    gb = put_global_batch(batch, batch_sharding(mesh_dp))
    for name in ("adam", "adamw", "sgd", "momentum", "lamb"):
        wd = 0.01 if name in ("adamw", "lamb") else 0.0
        tx = make_optimizer(1e-2, optimizer=name, weight_decay=wd,
                            grad_clip_norm=1.0)
        model = MLPClassifier(num_classes=3)
        trainer = Trainer(model, TASKS["classification"](), mesh_dp, tx=tx)
        state = trainer.init_state(make_rng(0), batch)
        state, metrics = trainer.step(state, gb)
        assert np.isfinite(float(jax.device_get(metrics["loss"]))), name

    with pytest.raises(ValueError):
        make_optimizer(1e-2, optimizer="adagrad")


def test_ema_params_track_and_evaluate(mesh_dp):
    """ema_decay>0: EMA leaves lag params (decay-weighted), survive an
    orbax checkpoint roundtrip, and evaluate(use_ema=True) runs on the
    averaged weights. (Resuming a pre-EMA checkpoint into an EMA-enabled
    trainer is a structure change — start a fresh run for that.)"""
    X, y = synthetic_classification_arrays(n=256, num_classes=5)
    model = MLPClassifier(num_classes=5)
    trainer = Trainer(model, TASKS["classification"](), mesh_dp,
                      learning_rate=1e-2, ema_decay=0.9)
    it = BatchIterator({"x": X, "y": y}, 64, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    assert state.ema_params is not None
    p0 = jax.device_get(jax.tree.leaves(state.params)[0])

    for batch in [next(iter(it)) for _ in range(4)]:
        from pyspark_tf_gke_tpu.data.pipeline import put_global_batch
        from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding

        gb = put_global_batch(batch, batch_sharding(mesh_dp))
        state, _ = trainer.step(state, gb)

    p = jax.device_get(jax.tree.leaves(state.params)[0])
    e = jax.device_get(jax.tree.leaves(state.ema_params)[0])
    # EMA moved off init but lags the raw params
    assert not np.allclose(e, p0)
    assert not np.allclose(e, p)
    assert np.linalg.norm(e - p0) < np.linalg.norm(p - p0)

    gb = put_global_batch(next(iter(it)), batch_sharding(mesh_dp))
    m_raw = trainer.evaluate(state, [gb])
    m_ema = trainer.evaluate(state, [gb], use_ema=True)
    assert np.isfinite(m_raw["loss"]) and np.isfinite(m_ema["loss"])
    assert m_raw["loss"] != m_ema["loss"]

    # EMA leaves ride the checkpoint pytree
    import tempfile

    with tempfile.TemporaryDirectory() as ckdir:
        mgr = CheckpointManager(ckdir)
        mgr.save(state, force=True)
        restored = mgr.restore(state)
        mgr.close()
    np.testing.assert_array_equal(
        jax.device_get(jax.tree.leaves(restored.ema_params)[0]), e)


def test_evaluate_use_ema_without_ema_raises(mesh_dp):
    X, y = synthetic_classification_arrays(n=64, num_classes=3)
    model = MLPClassifier(num_classes=3)
    trainer = Trainer(model, TASKS["classification"](), mesh_dp)
    it = BatchIterator({"x": X, "y": y}, 32, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    with pytest.raises(ValueError, match="ema_decay=0"):
        trainer.evaluate(state, [], use_ema=True)


def test_ema_decay_validated(mesh_dp):
    from pyspark_tf_gke_tpu.train.state import TrainState
    import optax

    with pytest.raises(ValueError, match="ema_decay"):
        TrainState.create({"w": jnp.ones((2,))}, optax.sgd(0.1), ema_decay=1.0)


def test_make_optimizer_rejects_ignored_knobs():
    from pyspark_tf_gke_tpu.train.harness import make_optimizer

    with pytest.raises(ValueError, match="weight_decay"):
        make_optimizer(1e-3, optimizer="adam", weight_decay=0.01)
    with pytest.raises(ValueError, match="warmup_steps"):
        make_optimizer(1e-3, schedule="cosine", total_steps=10, warmup_steps=5)
    # valid combos still build
    make_optimizer(1e-3, optimizer="adamw", weight_decay=0.01,
                   schedule="warmup_cosine", total_steps=10, warmup_steps=2)


def test_average_checkpoints_tool(tmp_path, mesh_dp):
    """tools/average_checkpoints: mean of the last K checkpoints' params,
    restorable into a TrainState by the normal manager."""
    from tools.average_checkpoints import average_checkpoints

    X, y = synthetic_classification_arrays(n=96, num_classes=3)
    model = MLPClassifier(num_classes=3)
    trainer = Trainer(model, TASKS["classification"](), mesh_dp,
                      learning_rate=1e-2)
    it = BatchIterator({"x": X, "y": y}, 32, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))

    ckdir = str(tmp_path / "ck")
    mgr = CheckpointManager(ckdir, max_to_keep=10)
    snapshots = []
    for _ in range(3):
        state, _ = trainer.fit(state, it, epochs=1, steps_per_epoch=2)
        mgr.save(state, force=True)
        snapshots.append(jax.device_get(jax.tree.leaves(state.params)[0]))
    mgr.close()

    outdir = str(tmp_path / "avg")
    step = average_checkpoints(ckdir, outdir, last=3)
    assert step == int(jax.device_get(state.step))

    restored = CheckpointManager(outdir).restore(state)
    leaf = jax.device_get(jax.tree.leaves(restored.params)[0])
    np.testing.assert_allclose(leaf, np.mean(snapshots, axis=0), rtol=1e-6)
    # step/opt_state come from the newest checkpoint
    assert int(jax.device_get(restored.step)) == step

    with pytest.raises(ValueError, match="at least 2"):
        onedir = str(tmp_path / "one")
        m2 = CheckpointManager(onedir)
        m2.save(state, force=True)
        m2.close()
        average_checkpoints(onedir, str(tmp_path / "avg2"), last=5)

    with pytest.raises(ValueError, match="last"):
        average_checkpoints(ckdir, str(tmp_path / "avg3"), last=0)


def test_adam_mu_dtype_bf16(mesh_dp):
    """mu_dtype=bf16: the Adam first-moment leaves store in bfloat16
    (halving that slice of the per-step optimizer HBM traffic), training
    stays finite, and the default remains f32 for reference parity."""
    x = np.random.default_rng(0).normal(size=(16, 4)).astype(np.float32)
    batch = {"x": x, "y": np.zeros((16,), np.int32)}

    def moment_dtypes(trainer):
        state = trainer.init_state(make_rng(0), batch)
        mus = [l.dtype for l in jax.tree.leaves(state.opt_state)
               if hasattr(l, "dtype")]
        state, metrics = trainer.step(
            state, {k: jnp.asarray(v) for k, v in batch.items()})
        assert np.isfinite(float(jax.device_get(metrics["loss"])))
        return mus, state

    model = MLPClassifier(num_classes=3)
    bf16 = Trainer(model, TASKS["classification"](), mesh_dp,
                   mu_dtype=jnp.bfloat16)
    mus, _ = moment_dtypes(bf16)
    assert jnp.bfloat16 in mus and jnp.float32 in mus  # mu bf16, nu f32

    default = Trainer(model, TASKS["classification"](), mesh_dp)
    mus, _ = moment_dtypes(default)
    assert jnp.bfloat16 not in mus  # parity default untouched


def test_adafactor_trains(mesh_dp):
    """adafactor (t5x's TPU default) must train through the standard
    Trainer path AND actually factor the second moments: optax only
    factors dims >= 128, so the probe model carries a 128x192 matrix
    and the opt_state must hold O(rows+cols) v_row/v_col stats for it
    (not a full O(rows*cols) tensor)."""
    from pyspark_tf_gke_tpu.train.harness import make_optimizer

    X, y = synthetic_classification_arrays(n=96, num_classes=3)
    model = MLPClassifier(num_classes=3, hidden=(128, 192))
    trainer = Trainer(model, TASKS["classification"](), mesh_dp,
                      tx=make_optimizer(1e-2, optimizer="adafactor"))
    it = BatchIterator({"x": X, "y": y}, 32, seed=0)
    batch = next(iter(it))
    state = trainer.init_state(make_rng(0), batch)
    losses = []
    for _ in range(8):
        state, metrics = trainer.step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert losses[-1] < losses[0]

    # factored evidence: some second-moment leaves are 1-D rows/cols of
    # the 128x192 kernel, and NO leaf stores its full 128x192 moment
    shapes = [np.asarray(x).shape
              for x in jax.tree.leaves(jax.device_get(state.opt_state))]
    assert (128,) in shapes and (192,) in shapes, shapes
    assert (128, 192) not in shapes, "second moment was NOT factored"

    def nbytes(tree):
        return sum(np.asarray(x).nbytes
                   for x in jax.tree.leaves(jax.device_get(tree)))

    adam_state = Trainer(model, TASKS["classification"](), mesh_dp,
                         learning_rate=1e-2).init_state(make_rng(0), batch)
    assert nbytes(state.opt_state) < nbytes(adam_state.opt_state)


def test_adafactor_weight_decay_builds():
    from pyspark_tf_gke_tpu.train.harness import make_optimizer

    make_optimizer(1e-3, optimizer="adafactor", weight_decay=0.01)
