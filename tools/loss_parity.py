"""Loss-parity oracle: the reference TF CNN-B1 vs this repo's JAX CNN-B1
trained on the SAME seeded synthetic dataset, same batch order, same
optimizer settings — the trajectory-level regression check SURVEY §4
names as the build's metric ("loss parity", per the reference's recorded
150-epoch history ``tf-model/150-320-by-256-B1-model.json``; since that
run's private laser-spot data isn't shipped, this oracle reproduces the
task synthetically and compares the two *implementations* head-to-head).

Both sides train the identical architecture (``build_cnn_model``,
``/root/reference/workloads/raw-tf/train_tf_ps.py:346-378``) with Adam
lr=1e-3 / eps=1e-7 (Keras defaults, the single-process compile path,
``train_tf_ps.py:372-377``), MSE loss, identical data and batch order,
no shuffling. Weight inits are framework-seeded (not bit-identical), so
parity is **final-metric parity within tolerance**, not per-step
equality — the same definition BASELINE.md applies to worker-count>1.

Writes ``tools/parity_report.json`` and exits non-zero on violation.
``tests/test_loss_parity.py`` runs a reduced config (slow-marked).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (pyspark_tf_gke_tpu)

KERAS_ADAM_EPS = 1e-7  # Keras Adam default; optax's is 1e-8


def make_spot_arrays(n: int, height: int, width: int, seed: int = 1337):
    """In-memory laser-spot regression set (the data/synthetic.py task
    without the PNG round-trip): dark frame, bright gaussian blob, target
    = blob center in raw pixel coords — the reference trains on raw
    (x_px, y_px) (``train_tf_ps.py:202-299``)."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:height, 0:width].astype(np.float32)
    images = np.empty((n, height, width, 3), np.float32)
    targets = np.empty((n, 2), np.float32)
    for i in range(n):
        cx = float(rng.uniform(4, width - 4))
        cy = float(rng.uniform(4, height - 4))
        blob = np.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2) / (2 * 3.0 ** 2)))
        img = (blob[..., None] * np.array([255.0, 40.0, 40.0]) +
               rng.normal(8, 4, (height, width, 3))).clip(0, 255)
        images[i] = img / 255.0  # the reference pipeline's rescale
        targets[i] = (cx, cy)
    return images, targets


FRAMING = (
    "The reference's recorded 150-epoch history "
    "(tf-model/150-320-by-256-B1-model.json) was trained on a private "
    "laser-spot image set that is NOT checked into the reference repo, "
    "so trajectory parity against that exact run is impossible. This "
    "report is therefore an IMPLEMENTATION-vs-IMPLEMENTATION oracle: "
    "the reference's own TF/Keras model code and this repo's JAX model "
    "train on the SAME seeded synthetic dataset, same batch order, same "
    "optimizer; parity = the JAX side reaches a final metric no worse "
    "than the TF side's best epoch. Both reference trainers are "
    "covered: the flagship CNN-B1 image regressor "
    "(train_tf_ps.py:346-378) and the MLP/CSV classifier "
    "(train_tf_ps.py:328-343)."
)


def make_health_arrays(n: int, num_classes: int = 6, seed: int = 1337):
    """In-memory analog of the CSV task (load_csv semantics,
    ``train_tf_ps.py:75-149``): 3 float features (value, lower_ci,
    upper_ci) whose joint distribution clusters by label — a learnable
    stand-in for the health_disparities subpopulation classes."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, n).astype(np.int32)
    centers = rng.uniform(-3, 3, (num_classes, 3)).astype(np.float32)
    feats = centers[labels] + rng.normal(0, 0.6, (n, 3)).astype(np.float32)
    # lower_ci/upper_ci bracket value the way the real rows do
    feats[:, 1] = feats[:, 0] - np.abs(feats[:, 1]) * 0.1
    feats[:, 2] = feats[:, 0] + np.abs(feats[:, 2]) * 0.1
    return feats.astype(np.float32), labels


def build_reference_cnn(input_shape=(256, 320, 3), flat=True):
    """The reference's build_cnn_model architecture (train_tf_ps.py:346-378),
    reconstructed from its published Keras summary (43,368,850 parameters at
    the default shape)."""
    import tensorflow as tf

    layers = [tf.keras.layers.Input(shape=input_shape)]
    for i, feats in enumerate((8, 16, 32, 64, 64)):
        layers.append(tf.keras.layers.Conv2D(feats, 5, padding="same"))
        layers.append(tf.keras.layers.PReLU())
        if i < 4:
            layers.append(tf.keras.layers.MaxPooling2D())
    layers.append(tf.keras.layers.Flatten() if flat else tf.keras.layers.GlobalAveragePooling2D())
    layers.append(tf.keras.layers.Dense(2048 if flat else 128, activation="relu"))
    layers.append(tf.keras.layers.Dense(2, activation="linear"))
    return tf.keras.Sequential(layers)


def run_tf(images, targets, batch_size: int, epochs: int, lr: float = 1e-3):
    """The reference implementation: Keras Sequential B1, model.fit with
    shuffle=False so the batch order matches the JAX run exactly."""
    import tensorflow as tf

    tf.keras.utils.set_random_seed(1337)
    model = build_reference_cnn(input_shape=images.shape[1:], flat=True)
    model.compile(
        optimizer=tf.keras.optimizers.Adam(lr, epsilon=KERAS_ADAM_EPS),
        loss=tf.keras.losses.MeanSquaredError(),
        metrics=[tf.keras.metrics.MeanAbsoluteError(name="mae")],
    )
    hist = model.fit(images, targets, batch_size=batch_size, epochs=epochs,
                     shuffle=False, verbose=0)
    return {k: [float(v) for v in vs] for k, vs in hist.history.items()}


def run_jax(images, targets, batch_size: int, epochs: int, lr: float = 1e-3):
    """This repo's implementation: CNNRegressor(flat=True) + Trainer,
    float32 compute for apples-to-apples numerics, same batch order."""
    import jax
    import optax

    # TF trains in true f32; JAX on TPU lowers f32 convs to bf16 passes
    # by default, which drags the convergence comparison.
    jax.config.update("jax_default_matmul_precision", "highest")

    from pyspark_tf_gke_tpu.data.pipeline import put_global_batch
    from pyspark_tf_gke_tpu.models import CNNRegressor
    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding, make_mesh
    from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    model = CNNRegressor(num_outputs=2, flat=True, dtype=None)  # f32
    trainer = Trainer(model, TASKS["regression"](), mesh,
                      tx=optax.adam(lr, eps=KERAS_ADAM_EPS))
    state = trainer.init_state(
        make_rng(1337), {"image": images[:1], "target": targets[:1]}
    )
    sharding = batch_sharding(mesh)
    steps = len(images) // batch_size
    history = {"loss": [], "mae": []}
    for _ in range(epochs):
        sums = {"loss": 0.0, "mae": 0.0}
        for i in range(steps):
            sl = slice(i * batch_size, (i + 1) * batch_size)
            gb = put_global_batch(
                {"image": images[sl], "target": targets[sl]}, sharding
            )
            state, metrics = trainer.step(state, gb)
            m = jax.device_get(metrics)
            sums["loss"] += float(m["loss"])
            sums["mae"] += float(m["mae"])
        for k in history:
            history[k].append(sums[k] / steps)
    return history


def run_tf_mlp(feats, labels, batch_size: int, epochs: int, lr: float = 1e-3):
    """The reference's OTHER trainer: build_deep_model
    (``train_tf_ps.py:328-343``) — Dense 16/32/64 relu + softmax head,
    Adam lr=1e-3, sparse categorical CE."""
    import tensorflow as tf

    num_classes = int(labels.max()) + 1
    tf.keras.utils.set_random_seed(1337)
    model = tf.keras.Sequential([
        tf.keras.layers.Input(shape=(feats.shape[1],)),
        tf.keras.layers.Dense(16, activation="relu"),
        tf.keras.layers.Dense(32, activation="relu"),
        tf.keras.layers.Dense(64, activation="relu"),
        tf.keras.layers.Dense(num_classes, activation="softmax"),
    ])
    model.compile(
        optimizer=tf.keras.optimizers.Adam(lr, epsilon=KERAS_ADAM_EPS),
        loss=tf.keras.losses.SparseCategoricalCrossentropy(),
        metrics=["accuracy"],
    )
    hist = model.fit(feats, labels, batch_size=batch_size, epochs=epochs,
                     shuffle=False, verbose=0)
    return {k: [float(v) for v in vs] for k, vs in hist.history.items()}


def run_jax_mlp(feats, labels, batch_size: int, epochs: int, lr: float = 1e-3):
    """This repo's MLPClassifier (models/mlp.py — the param-count parity
    twin) + Trainer, same batch order."""
    import jax
    import optax

    from pyspark_tf_gke_tpu.data.pipeline import put_global_batch
    from pyspark_tf_gke_tpu.models import MLPClassifier
    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding, make_mesh
    from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    model = MLPClassifier(num_classes=int(labels.max()) + 1)
    trainer = Trainer(model, TASKS["classification"](), mesh,
                      tx=optax.adam(lr, eps=KERAS_ADAM_EPS))
    state = trainer.init_state(make_rng(1337), {"x": feats[:1], "y": labels[:1]})
    sharding = batch_sharding(mesh)
    steps = len(feats) // batch_size
    history = {"loss": [], "accuracy": []}
    for _ in range(epochs):
        sums = {"loss": 0.0, "accuracy": 0.0}
        for i in range(steps):
            sl = slice(i * batch_size, (i + 1) * batch_size)
            gb = put_global_batch({"x": feats[sl], "y": labels[sl]}, sharding)
            state, metrics = trainer.step(state, gb)
            m = jax.device_get(metrics)
            sums["loss"] += float(m["loss"])
            sums["accuracy"] += float(m["accuracy"])
        for k in history:
            history[k].append(sums[k] / steps)
    return history


def compare_cls(tf_hist, jax_hist, loss_ratio_tol: float, acc_abs_tol: float):
    """Classification parity-or-better: final CE loss no worse than the
    TF run's best epoch (× tol) and final accuracy within ``acc_abs_tol``
    of the TF run's best."""
    checks = {}
    tl, jl = min(tf_hist["loss"]), jax_hist["loss"][-1]
    ta, ja = max(tf_hist["accuracy"]), jax_hist["accuracy"][-1]
    checks["final_loss_not_worse_than_tf_best"] = {
        "tf_best": tl, "tf_final": tf_hist["loss"][-1], "jax_final": jl,
        "tol": loss_ratio_tol, "ok": jl <= tl * loss_ratio_tol,
    }
    checks["final_accuracy_not_worse_than_tf_best"] = {
        "tf_best": ta, "tf_final": tf_hist["accuracy"][-1], "jax_final": ja,
        "tol": acc_abs_tol, "ok": ja >= ta - acc_abs_tol,
    }
    for name, hist in (("tf", tf_hist), ("jax", jax_hist)):
        checks[f"{name}_descended"] = {
            "first": hist["loss"][0], "last": hist["loss"][-1],
            "ok": hist["loss"][-1] < hist["loss"][0],
        }
    return checks, all(c["ok"] for c in checks.values())


def compare(tf_hist, jax_hist, loss_ratio_tol: float, mae_rel_tol: float):
    """Parity-or-better checks: the JAX trajectory must reach a final
    loss/MAE no worse than the reference's (within tolerance) — beating
    it is a pass, not a violation (the 30-epoch full-size run converges
    ~29x lower than TF; the build goal is 'matches or beats')."""
    checks = {}
    # Gate against the reference's BEST epoch, not its last: Keras runs
    # can diverge at the tail (the checked-in 30-epoch TF trajectory
    # ends at 128 after bottoming at ~22), and "not worse than a
    # diverged tail" would pass regressions the reference beats at
    # every converged epoch.
    tl, jl = min(tf_hist["loss"]), jax_hist["loss"][-1]
    tm, jm = min(tf_hist["mae"]), jax_hist["mae"][-1]
    checks["final_loss_not_worse_than_tf_best"] = {
        "tf_best": tl, "tf_final": tf_hist["loss"][-1], "jax_final": jl,
        "tol": loss_ratio_tol,
        "ok": jl <= tl * loss_ratio_tol,
    }
    checks["final_mae_not_worse_than_tf_best"] = {
        "tf_best": tm, "tf_final": tf_hist["mae"][-1], "jax_final": jm,
        "tol": mae_rel_tol,
        "ok": jm <= tm * (1.0 + mae_rel_tol),
    }
    for name, hist in (("tf", tf_hist), ("jax", jax_hist)):
        checks[f"{name}_descended"] = {
            "first": hist["loss"][0], "last": hist["loss"][-1],
            "ok": hist["loss"][-1] < hist["loss"][0],
        }
    return checks, all(c["ok"] for c in checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--images", type=int, default=128)
    ap.add_argument("--height", type=int, default=256)
    ap.add_argument("--width", type=int, default=320)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--loss-ratio-tol", type=float, default=1.6,
                    help="one-sided multiplier on the TF run's best-epoch "
                         "loss: jax_final must be <= tf_best * tol "
                         "(inits are framework-seeded, not identical)")
    ap.add_argument("--mae-rel-tol", type=float, default=0.35)
    ap.add_argument("--mlp-rows", type=int, default=4096)
    ap.add_argument("--mlp-epochs", type=int, default=20)
    ap.add_argument("--acc-abs-tol", type=float, default=0.05)
    ap.add_argument("--skip-cnn", action="store_true",
                    help="reuse the existing report's cnn_b1 section "
                         "(recorded run) and refresh only the MLP half — "
                         "the CNN pair is expensive off-TPU")
    ap.add_argument("--report", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "parity_report.json"))
    args = ap.parse_args(argv)

    cnn_section = None
    if args.skip_cnn:
        with open(args.report) as fh:
            prev = json.load(fh)
        cnn_section = prev.get("cnn_b1") or {
            # migrate a pre-restructure report (flat layout)
            "reference_workload": "train_tf_ps.py:346-378 (flagship)",
            "config": prev["config"],
            "optimizer": prev["optimizer"],
            "tf_history": prev["tf_history"],
            "jax_history": prev["jax_history"],
            "checks": prev["checks"],
            "parity": prev["parity"],
        }
        tf_hist, jax_hist = cnn_section["tf_history"], cnn_section["jax_history"]
        checks, ok = cnn_section["checks"], cnn_section["parity"]
        print("cnn: reusing recorded histories from the existing report",
              file=sys.stderr)
    else:
        images, targets = make_spot_arrays(args.images, args.height, args.width)
        print(f"cnn dataset: {args.images} images {args.height}x{args.width}, "
              f"batch {args.batch_size}, {args.epochs} epochs", file=sys.stderr)

        tf_hist = run_tf(images, targets, args.batch_size, args.epochs)
        print(f"tf   loss: {tf_hist['loss'][0]:.1f} -> "
              f"{tf_hist['loss'][-1]:.2f}", file=sys.stderr)
        jax_hist = run_jax(images, targets, args.batch_size, args.epochs)
        print(f"jax  loss: {jax_hist['loss'][0]:.1f} -> "
              f"{jax_hist['loss'][-1]:.2f}", file=sys.stderr)
        checks, ok = compare(tf_hist, jax_hist, args.loss_ratio_tol,
                             args.mae_rel_tol)

    feats, labels = make_health_arrays(args.mlp_rows)
    print(f"mlp dataset: {args.mlp_rows} rows, batch {args.batch_size}, "
          f"{args.mlp_epochs} epochs", file=sys.stderr)
    tf_mlp = run_tf_mlp(feats, labels, args.batch_size, args.mlp_epochs)
    jax_mlp = run_jax_mlp(feats, labels, args.batch_size, args.mlp_epochs)
    print(f"tf   mlp acc: {tf_mlp['accuracy'][-1]:.3f}  "
          f"jax mlp acc: {jax_mlp['accuracy'][-1]:.3f}", file=sys.stderr)
    mlp_checks, mlp_ok = compare_cls(tf_mlp, jax_mlp, args.loss_ratio_tol,
                                     args.acc_abs_tol)

    report = {
        "framing": FRAMING,
        "reference_dataset_available": False,
        "cnn_b1": cnn_section or {
            "reference_workload": "train_tf_ps.py:346-378 (flagship)",
            "config": {k: getattr(args, k) for k in
                       ("images", "height", "width", "batch_size", "epochs")},
            "optimizer": {"name": "adam", "lr": 1e-3, "eps": KERAS_ADAM_EPS},
            "tf_history": tf_hist,
            "jax_history": jax_hist,
            "checks": checks,
            "parity": ok,
        },
        "mlp_csv": {
            "reference_workload": "train_tf_ps.py:328-343 (CSV classifier)",
            "config": {"rows": args.mlp_rows, "batch_size": args.batch_size,
                       "epochs": args.mlp_epochs},
            "optimizer": {"name": "adam", "lr": 1e-3, "eps": KERAS_ADAM_EPS},
            "tf_history": tf_mlp,
            "jax_history": jax_mlp,
            "checks": mlp_checks,
            "parity": mlp_ok,
        },
        "parity": ok and mlp_ok,
    }
    with open(args.report, "w") as fh:
        json.dump(report, fh, indent=2)
    print(json.dumps({"parity": ok and mlp_ok, "report": args.report,
                      "cnn_final_loss": {"tf": tf_hist["loss"][-1],
                                         "jax": jax_hist["loss"][-1]},
                      "mlp_final_acc": {"tf": tf_mlp["accuracy"][-1],
                                        "jax": jax_mlp["accuracy"][-1]}}))
    return 0 if (ok and mlp_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
