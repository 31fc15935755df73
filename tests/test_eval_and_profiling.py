import os

import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.data.pipeline import BatchIterator
from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_image_dataset, synthetic_tokens
from pyspark_tf_gke_tpu.evaluate.image_checker import ManualImageChecker
from pyspark_tf_gke_tpu.models import BertConfig, BertForPretraining, CNNRegressor
from pyspark_tf_gke_tpu.train.checkpoint import CheckpointManager
from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
from pyspark_tf_gke_tpu.utils.seeding import make_rng


def test_image_checker_end_to_end(tmp_path, mesh_dp):
    data_dir = make_synthetic_image_dataset(str(tmp_path / "imgs"), num_images=8,
                                            height=32, width=40)
    images = np.random.default_rng(0).uniform(0, 1, (8, 32, 40, 3)).astype(np.float32)
    targets = np.random.default_rng(1).uniform(0, 30, (8, 2)).astype(np.float32)
    model = CNNRegressor(flat=False)
    trainer = Trainer(model, TASKS["regression"](), mesh_dp, learning_rate=1e-3)
    it = BatchIterator({"image": images, "target": targets}, 8, seed=0)
    state = trainer.init_state(make_rng(0), next(iter(it)))
    state, _ = trainer.fit(state, it, epochs=1, steps_per_epoch=1)
    ckpt_dir = str(tmp_path / "ckpt")
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(state)
    mgr.close()

    checker = ManualImageChecker(ckpt_dir, image_size=(32, 40), flat=False,
                                 output_dir=str(tmp_path / "plots"))
    result = checker.main(data_dir)
    assert result["n_images"] == 8
    assert result["mean_px_error"] >= 0
    plots = os.listdir(tmp_path / "plots")
    assert len(plots) == 8 and all(p.endswith("_eval.png") for p in plots)


def test_bert_flash_flag_interpret(mesh_dp):
    """use_flash wires the Pallas kernel into BERT (interpret mode on CPU)."""
    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
                     intermediate_size=64, max_position_embeddings=32,
                     dtype=jnp.float32, use_flash=True)
    model = BertForPretraining(cfg)
    batch = synthetic_tokens(batch=2, seq_len=32, vocab_size=64)
    variables = model.init(make_rng(0), batch["input_ids"])
    out = model.apply(variables, batch["input_ids"],
                      attention_mask=batch["attention_mask"])
    cfg2 = BertConfig(**{**cfg.__dict__, "use_flash": False})
    model2 = BertForPretraining(cfg2)
    out2 = model2.apply(variables, batch["input_ids"],
                        attention_mask=batch["attention_mask"])
    np.testing.assert_allclose(np.asarray(out["cls_logits"]),
                               np.asarray(out2["cls_logits"]), atol=2e-4)
