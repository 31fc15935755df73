import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.models import (
    BertConfig,
    BertForPretraining,
    CNNRegressor,
    MLPClassifier,
    ResNet50,
    build_model,
)


def _param_count(tree):
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


def test_mlp_shapes():
    model = MLPClassifier(num_classes=17)
    out, params = _init_and_apply(model, jnp.ones((4, 3)))
    assert out.shape == (4, 17)
    # Dense 3→16→32→64→17 with biases
    expected = (3 * 16 + 16) + (16 * 32 + 32) + (32 * 64 + 64) + (64 * 17 + 17)
    assert _param_count(params) == expected


def _init_and_apply(model, x, **kw):
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), x, **kw))
    variables = model.init(jax.random.key(0), x, **kw)
    out = model.apply(variables, x, **kw)
    return out, variables["params"]


def test_cnn_b1_param_count_parity():
    """The reference's B1 model has exactly 43,368,850 params at 256x320
    (tf-model/150-320-by-256-B1-model.txt:31-33) — including Keras's
    per-element PReLU alphas. Verified by eval_shape (no giant init)."""
    model = CNNRegressor(num_outputs=2, flat=True)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 256, 320, 3)))
    )
    assert _param_count(abstract["params"]) == 43_368_850


def test_cnn_forward_small():
    model = CNNRegressor(num_outputs=2, flat=False)
    out, _ = _init_and_apply(model, jnp.ones((2, 64, 80, 3)))
    assert out.shape == (2, 2)
    assert out.dtype == jnp.float32


def test_cnn_bf16_compute():
    model = CNNRegressor(num_outputs=2, flat=False, dtype=jnp.bfloat16)
    out, _ = _init_and_apply(model, jnp.ones((2, 32, 32, 3)))
    assert out.shape == (2, 2) and out.dtype == jnp.float32


def test_cnn_shared_prelu_smaller():
    full = jax.eval_shape(
        lambda: CNNRegressor(flat=False).init(jax.random.key(0), jnp.ones((1, 64, 64, 3)))
    )
    shared = jax.eval_shape(
        lambda: CNNRegressor(flat=False, prelu_shared_axes=(1, 2)).init(
            jax.random.key(0), jnp.ones((1, 64, 64, 3))
        )
    )
    assert _param_count(shared["params"]) < _param_count(full["params"])


def test_resnet50_forward():
    model = ResNet50(num_classes=10, dtype=None)
    x = jnp.ones((2, 64, 64, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    assert "batch_stats" in variables
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)


def test_resnet50_param_count():
    """ResNet-50 with a 10-way head ≈ 23.5M params (standard)."""
    model = ResNet50(num_classes=10, dtype=None)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.ones((1, 64, 64, 3)), train=False)
    )
    n = _param_count(abstract["params"])
    assert 23_000_000 < n < 24_000_000


def test_bert_tiny_forward():
    cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
                     intermediate_size=64, max_position_embeddings=64)
    model = BertForPretraining(cfg)
    ids = jnp.ones((2, 16), dtype=jnp.int32)
    variables = model.init(jax.random.key(0), ids)
    out = model.apply(variables, ids)
    assert out["mlm_logits"].shape == (2, 16, 128)
    assert out["cls_logits"].shape == (2, 2)


def test_bert_base_param_count():
    """BERT-base ≈ 110M params (109,482,240 encoder+embeddings in the
    canonical implementation; ours adds the MLM transform + heads)."""
    cfg = BertConfig()
    model = BertForPretraining(cfg)
    ids = jnp.ones((1, 8), dtype=jnp.int32)
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), ids))
    n = _param_count(abstract["params"])
    assert 105_000_000 < n < 140_000_000


def test_build_model_factory():
    assert isinstance(build_model("mlp", num_classes=5), MLPClassifier)
    assert isinstance(build_model("cnn", flat=True), CNNRegressor)
    with pytest.raises(ValueError):
        build_model("nope")


def test_space_to_depth_layout():
    from pyspark_tf_gke_tpu.models.resnet import space_to_depth

    # Each output pixel must stack its 2x2 input patch along channels in
    # (row-major patch, then original channel) order.
    x = jnp.arange(2 * 4 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 4, 3)
    y = space_to_depth(x, 2)
    assert y.shape == (2, 2, 2, 12)
    expected = jnp.concatenate(
        [x[:, 0:1, 0:1, :], x[:, 0:1, 1:2, :],
         x[:, 1:2, 0:1, :], x[:, 1:2, 1:2, :]], axis=-1)
    assert jnp.array_equal(y[:, 0:1, 0:1, :], expected)
    import pytest

    with pytest.raises(ValueError, match="divisible"):
        space_to_depth(jnp.ones((1, 5, 4, 3)), 2)


def test_resnet50_s2d_stem_shapes_match_plain():
    # The s2d variant must be output-shape-identical to the plain stem
    # (an A/B of the two compares like against like), differing only in the
    # stem parameterization: 4x4x12 kernel instead of 7x7x3.
    plain = ResNet50(num_classes=10, dtype=None)
    s2d = ResNet50(num_classes=10, dtype=None, s2d_stem=True)
    x = jnp.ones((2, 64, 64, 3))
    vp = jax.eval_shape(lambda: plain.init(jax.random.key(0), x, train=False))
    vs = jax.eval_shape(lambda: s2d.init(jax.random.key(0), x, train=False))
    op = jax.eval_shape(
        lambda: plain.apply(
            plain.init(jax.random.key(0), x, train=False), x, train=False))
    os_ = jax.eval_shape(
        lambda: s2d.apply(
            s2d.init(jax.random.key(0), x, train=False), x, train=False))
    assert op.shape == os_.shape == (2, 10)
    kp = vp["params"]["conv_init"]["kernel"]
    ks = vs["params"]["conv_init_s2d"]["kernel"]
    assert kp.shape == (7, 7, 3, 64)
    assert ks.shape == (4, 4, 12, 64)
    # Everything downstream of the stem is structurally identical.
    downstream_p = {k for k in vp["params"] if not k.startswith("conv_init")}
    downstream_s = {k for k in vs["params"] if not k.startswith("conv_init")}
    assert downstream_p == downstream_s


def test_resnet50_s2d_trains():
    import numpy as np
    import optax

    model = ResNet50(num_classes=4, num_filters=8, stage_sizes=(1, 1),
                     dtype=None, s2d_stem=True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0, 1, (8, 32, 32, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 4, (8,)).astype(np.int32))
    variables = model.init(jax.random.key(0), x, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, updates["batch_stats"]

        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        upd, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, upd), bs, opt_state, loss

    first = None
    for _ in range(10):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state)
        first = first if first is not None else float(loss)
    assert float(loss) < first  # the reparameterized stem learns


def test_resnet_norm_variants_forward_and_trainer_step():
    # The MFU-diagnostic norm lever (models/resnet.py norm_variant):
    # every variant must produce finite logits of the right shape, and
    # the stat-free variants (gn/none) must run through the Trainer's
    # resnet task, whose batch_stats threading assumes BN by default.
    import numpy as np

    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding, make_mesh
    from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    rng = np.random.default_rng(0)
    batch = {
        "image": rng.uniform(0, 1, (4, 32, 32, 3)).astype(np.float32),
        "label": rng.integers(0, 4, (4,)).astype(np.int32),
    }
    mesh = make_mesh({"dp": 2}, jax.devices()[:2])
    for variant in ("bn_f32", "gn", "none"):
        # num_filters=32: GroupNorm-32 needs channels divisible by 32
        model = ResNet50(num_classes=4, num_filters=32, stage_sizes=(1, 1),
                         dtype=None, norm_variant=variant)
        trainer = Trainer(model, TASKS["resnet"](), mesh,
                          learning_rate=1e-2)
        state = trainer.init_state(make_rng(0), batch)
        gb = {k: jax.device_put(v, batch_sharding(mesh))
              for k, v in batch.items()}
        state, metrics = trainer.step(state, gb)
        assert np.isfinite(float(jax.device_get(metrics["loss"]))), variant

    with pytest.raises(ValueError):
        ResNet50(num_classes=4, norm_variant="bogus").init(
            jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=True)
