"""End-to-end BASELINE config 5: BERT fine-tune fed by TFRecord shards
written with the ETL-bridge schema contract, read via the native IO
plane (no tensorflow required)."""

import os

import numpy as np
import pytest

from pyspark_tf_gke_tpu.data.native_tfrecord import write_tfrecord_shards
from pyspark_tf_gke_tpu.train import bert_finetune

SEQ = 16


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    """parse_args defaults come from env vars; isolate from ambient ones."""
    for var in ("DATA_PATTERN", "NUM_PROCESSES", "MESH_SHAPE", "OUTPUT_DIR",
                "EPOCHS", "BATCH_SIZE", "MAX_RESTARTS", "COORDINATOR_ADDR"):
        monkeypatch.delenv(var, raising=False)


def _write_shards(tmp_path, n=192, vocab=96):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (n, SEQ)).astype(np.int64)
    # a label the model can learn (the first token's parity): random labels
    # read by the reader's threads in another order give no falling loss
    arrays = {
        "input_ids": ids,
        "attention_mask": np.ones((n, SEQ), dtype=np.int64),
        "label": ids[:, 0] % 2,
    }
    prefix = str(tmp_path / "shards" / "train")
    write_tfrecord_shards(arrays, prefix, num_shards=4)
    return f"{prefix}-*.tfrecord"


def _args(pattern, out, extra=()):
    return [
        "--data-pattern", pattern, "--output-dir", out,
        "--seq-len", str(SEQ), "--vocab-size", "96",
        "--hidden-size", "32", "--num-layers", "2", "--num-heads", "4",
        "--intermediate-size", "64", "--compute-dtype", "float32",
        "--epochs", "2", "--steps-per-epoch", "6", "--batch-size", "16",
        "--learning-rate", "1e-2", *extra,
    ]


def test_bert_finetune_from_shards(tmp_path, devices):
    pattern = _write_shards(tmp_path)
    out = str(tmp_path / "run")
    history = bert_finetune.main(_args(pattern, out, ["--mesh-shape", "dp=8"]))
    assert len(history["loss"]) == 2
    assert all(np.isfinite(v) for v in history["loss"])
    assert history["loss"][-1] < history["loss"][0]
    assert os.path.exists(os.path.join(out, "history.json"))
    assert os.path.isdir(os.path.join(out, "checkpoints"))


def test_bert_finetune_sp_ulysses(tmp_path, devices):
    """Same entry on a dp x sp mesh with the Ulysses implementation."""
    pattern = _write_shards(tmp_path)
    out = str(tmp_path / "run-sp")
    history = bert_finetune.main(_args(
        pattern, out,
        ["--mesh-shape", "dp=2,sp=4", "--sp-impl", "ulysses"],
    ))
    assert all(np.isfinite(v) for v in history["loss"])


def test_bert_finetune_requires_pattern():
    with pytest.raises(SystemExit):
        bert_finetune.main(["--output-dir", "/tmp/x"])
