"""gs://-path support on the TPU-host data plane (VERDICT missing #4),
unit-tested via fsspec's memory:// filesystem — same code path as gs://
(is_remote → fsspec), no network.
"""

import os
import numpy as np
import pytest

fsspec = pytest.importorskip("fsspec")

from pyspark_tf_gke_tpu.utils.fs import fs_glob, fs_open, is_remote, spool_local


def _put(url: str, data: bytes):
    with fsspec.open(url, "wb") as fh:
        fh.write(data)


def test_is_remote_routing():
    assert is_remote("gs://bucket/x.csv")
    assert is_remote("memory://bucket/x.csv")
    assert not is_remote("/tmp/x.csv")
    assert not is_remote("relative/x.csv")
    assert not is_remote("https://host/x.csv")  # urlopen path, not fsspec


def test_csv_loader_remote(tmp_path):
    from pyspark_tf_gke_tpu.data.csv_loader import load_csv
    from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_csv

    local = str(tmp_path / "health.csv")
    make_synthetic_csv(local, rows=80)
    _put("memory://bucket/health.csv", open(local, "rb").read())

    x_l, y_l, vocab_l = load_csv(local)
    x_r, y_r, vocab_r = load_csv("memory://bucket/health.csv")
    np.testing.assert_array_equal(x_l, x_r)
    np.testing.assert_array_equal(y_l, y_r)
    assert vocab_l == vocab_r


def test_fs_glob_and_spool(tmp_path):
    for i in range(3):
        _put(f"memory://bucket/shards/part-{i:05d}.tfrecord", bytes([i]) * 10)
    got = fs_glob("memory://bucket/shards/part-*.tfrecord")
    assert [g.rsplit("/", 1)[1] for g in got] == [
        f"part-{i:05d}.tfrecord" for i in range(3)
    ]
    assert all(g.startswith("memory://") for g in got)

    spool = str(tmp_path / "spool")
    local = spool_local(got[1], spool_dir=spool)
    assert open(local, "rb").read() == b"\x01" * 10
    # second call reuses the spooled copy (content-addressed)
    assert spool_local(got[1], spool_dir=spool) == local
    # memory:// gives no etag and no mtime: an object overwritten at equal
    # size must not be served from the older copy
    _put(got[1], b"\x07" * 10)
    assert open(spool_local(got[1], spool_dir=spool), "rb").read() == b"\x07" * 10
    # local paths pass through
    assert spool_local("/tmp/x") == "/tmp/x"


def test_native_tfrecord_reader_remote(tmp_path):
    """Full shard pipeline over a remote filesystem: write locally,
    upload, read back through the spool via the native reader."""
    from pyspark_tf_gke_tpu.data import native_tfrecord as ntr
    from pyspark_tf_gke_tpu.data.tfrecord import schema_for

    rng = np.random.default_rng(0)
    arrays = {
        "input_ids": rng.integers(0, 100, (64, 16)).astype(np.int64),
        "label": rng.integers(0, 2, (64,)).astype(np.int64),
    }
    schema = schema_for(arrays)

    def read_all(pattern):
        rows = []
        # one reader thread: with more, rows of different shards interleave
        # as the threads are scheduled (``native.ExamplePool``), and a
        # row-by-row comparison of two reads fails on a busy machine
        for b in ntr.read_tfrecord_batches(
            pattern, schema, 8, shuffle=False, repeat=False,
            process_index=0, process_count=1, nthreads=1,
        ):
            rows.append(b["input_ids"])
        return np.concatenate(rows)

    # an earlier run's shards under the same names, equally long, read
    # through the default spool (which outlives a run) before this run's
    older = {k: v[::-1].copy() for k, v in arrays.items()}
    for run, data in (("older", older), ("this", arrays)):
        paths = ntr.write_tfrecord_shards(data, str(tmp_path / run / "p"), num_shards=4)
        for p in paths:
            _put(f"memory://bucket/tfr/{p.rsplit('/', 1)[1]}", open(p, "rb").read())
        remote_rows = read_all("memory://bucket/tfr/p-*.tfrecord")
    local_rows = read_all(str(tmp_path / "this" / "p-*.tfrecord"))
    np.testing.assert_array_equal(local_rows, remote_rows)


def test_tfdata_tfrecord_reader_remote(tmp_path):
    """The tf.data reader over a non-gs remote scheme stages through the
    spool (gs:// itself would go to TF's native GCS filesystem)."""
    pytest.importorskip("tensorflow")
    from pyspark_tf_gke_tpu.data import tfrecord as tfr

    rng = np.random.default_rng(1)
    arrays = {"x": rng.normal(size=(32, 4)).astype(np.float32),
              "label": rng.integers(0, 3, (32,)).astype(np.int64)}
    schema = tfr.schema_for(arrays)
    paths = tfr.write_tfrecord_shards(arrays, str(tmp_path / "q"), num_shards=2)
    for p in paths:
        _put(f"memory://bucket/tfd/{p.rsplit('/', 1)[1]}", open(p, "rb").read())

    it = tfr.read_tfrecord_batches(
        "memory://bucket/tfd/q-*.tfrecord", schema, 8, shuffle=False,
        repeat=False, process_index=0, process_count=1,
    )
    n = sum(len(b["label"]) for b in it)
    assert n == 32


# ---- GCS-semantics enforcement (VERDICT r2 #8) ------------------------------
#
# memory:// is more permissive than gs:// (it allows append and write-
# seek, which object stores don't). GSemFS subclasses it to ENFORCE the
# GCS contract — no append mode, no seeking on a write stream, whole-
# object writes only — so any reader/writer in the data plane that
# quietly relied on posix-isms fails HERE instead of in production.


class _NoSeekWriter:
    """Write-stream facade enforcing object-store semantics."""

    def __init__(self, inner):
        self._inner = inner

    def write(self, data):
        return self._inner.write(data)

    def seek(self, *a, **k):
        raise OSError("GCS object writes are append-only streams; "
                      "seek on a write stream is not supported")

    def truncate(self, *a, **k):
        raise OSError("GCS objects cannot be truncated in place")

    def close(self):
        return self._inner.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _register_gsem():
    from fsspec.implementations.memory import MemoryFileSystem

    class GSemFS(MemoryFileSystem):
        protocol = "gsem"

        def _open(self, path, mode="rb", **kwargs):
            if "a" in mode:
                raise OSError("GCS does not support append mode")
            f = super()._open(path, mode, **kwargs)
            if "w" in mode:
                return _NoSeekWriter(f)
            return f

    try:
        fsspec.register_implementation("gsem", GSemFS)
    except ValueError:
        pass  # already registered in this process
    return GSemFS


@pytest.fixture(scope="module")
def gsem():
    _register_gsem()
    yield "gsem://bucket"


def test_gsem_enforces_gcs_semantics(gsem):
    with pytest.raises(OSError, match="append"):
        fsspec.open(f"{gsem}/x.bin", "ab").open()
    with fsspec.open(f"{gsem}/x.bin", "wb") as fh:
        fh.write(b"abc")
        with pytest.raises(OSError, match="seek"):
            fh.seek(0)


def test_csv_loader_under_gcs_semantics(gsem, tmp_path):
    from pyspark_tf_gke_tpu.data.csv_loader import load_csv
    from pyspark_tf_gke_tpu.data.synthetic import make_synthetic_csv

    local = str(tmp_path / "health.csv")
    make_synthetic_csv(local, rows=60)
    _put(f"{gsem}/health.csv", open(local, "rb").read())
    x_l, y_l, vocab_l = load_csv(local)
    x_r, y_r, vocab_r = load_csv(f"{gsem}/health.csv")
    np.testing.assert_array_equal(x_l, x_r)
    assert vocab_l == vocab_r


def test_native_tfrecord_spool_under_gcs_semantics(gsem, tmp_path):
    from pyspark_tf_gke_tpu.data import native_tfrecord as ntr
    from pyspark_tf_gke_tpu.data.tfrecord import schema_for

    rng = np.random.default_rng(0)
    arrays = {"input_ids": rng.integers(0, 50, (24, 8)).astype(np.int64)}
    schema = schema_for(arrays)
    for p in ntr.write_tfrecord_shards(arrays, str(tmp_path / "s"),
                                       num_shards=2):
        _put(f"{gsem}/tfr/{p.rsplit('/', 1)[1]}", open(p, "rb").read())
    rows = sum(
        len(b["input_ids"]) for b in ntr.read_tfrecord_batches(
            f"{gsem}/tfr/s-*.tfrecord", schema, 8, shuffle=False,
            repeat=False, process_index=0, process_count=1))
    assert rows == 24


def test_artifact_writers_under_gcs_semantics(gsem):
    """history.json / label_map.json / run-notes writers must do whole-
    object writes (no local-dir makedirs, no append) on remote output
    dirs — the k8s manifests set OUTPUT_DIR=gs://."""
    from pyspark_tf_gke_tpu.train.checkpoint import save_history, save_label_map

    out = f"{gsem}/runs/job1"
    save_history(out, {"loss": [3.0, 2.0]})
    save_label_map(out, ["a", "b"])
    import json

    with fsspec.open(f"{out}/history.json") as fh:
        assert json.load(fh)["loss"] == [3.0, 2.0]
    with fsspec.open(f"{out}/label_map.json") as fh:
        assert json.load(fh) == {"0": "a", "1": "b"}


def test_checkpoint_dir_remote_path_not_mangled(monkeypatch):
    """gs:// checkpoint dirs must reach orbax verbatim — abspath would
    silently turn them into a local ./gs:/ tree."""
    import pyspark_tf_gke_tpu.train.checkpoint as ck

    captured = {}

    class FakeMgr:
        def __init__(self, directory, options=None):
            captured["dir"] = directory

        def close(self):
            pass

        def wait_until_finished(self):
            pass

        def latest_step(self):
            return None

    monkeypatch.setattr(ck.ocp, "CheckpointManager", FakeMgr)
    mgr = ck.CheckpointManager("gs://bucket/runs/ck")
    assert mgr.directory == "gs://bucket/runs/ck"
    assert captured["dir"] == "gs://bucket/runs/ck"
    assert not os.path.exists("gs:")  # no local mangled tree
    mgr.close()


def test_heartbeat_rejects_remote_path():
    from pyspark_tf_gke_tpu.train.harness import make_heartbeat
    from pyspark_tf_gke_tpu.train.resilience import Heartbeat

    with pytest.raises(ValueError, match="node-local"):
        Heartbeat("gs://bucket/hb.json")
    hb = make_heartbeat("gs://bucket/out", every_steps=5)
    assert hb.path.startswith("/tmp")


def test_fs_copy_tree_pulls_bundle_layout(tmp_path):
    """Remote bundle pull (train/serve.py startup): the whole tree lands
    under local_dir with relative paths preserved."""
    from pyspark_tf_gke_tpu.utils.fs import fs_copy_tree

    _put("memory://bucket/bundle/config.json", b'{"a": 1}')
    _put("memory://bucket/bundle/params/data/chunk0", b"\x00" * 16)
    local = str(tmp_path / "pulled")
    out = fs_copy_tree("memory://bucket/bundle", local)
    assert out == local
    assert open(f"{local}/config.json", "rb").read() == b'{"a": 1}'
    assert open(f"{local}/params/data/chunk0", "rb").read() == b"\x00" * 16
    with pytest.raises(ValueError, match="remote"):
        fs_copy_tree("/local/path", local)
