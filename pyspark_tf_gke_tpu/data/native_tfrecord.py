"""TFRecord pipeline on the first-party native IO plane.

Same contract as ``pyspark_tf_gke_tpu.data.tfrecord`` (the tf.data-backed
path) but with zero tensorflow dependency: framing + Example codec + the
threaded prefetch reader come from the C++ library
(``native/src/tfrecord_io.cc``), with the pure-Python codec
(``data/codec.py``) as last-resort fallback. This is the path the
training image uses — tensorflow stays a Spark-side-only dependency.

Semantics mirrored from the reference's input pipeline
(``/root/reference/workloads/raw-tf/train_tf_ps.py:301-322``):
file-level host sharding (the ``dataset.shard`` analog), a 3000-row
shuffle buffer, repeat, drop-remainder batching.
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, Optional, Sequence, Tuple

import numpy as np

from pyspark_tf_gke_tpu.data.codec import Schema
from pyspark_tf_gke_tpu.utils.logging import get_logger
from pyspark_tf_gke_tpu.utils.seeding import DEFAULT_SEED, np_rng

logger = get_logger("data.native_tfrecord")


def native_available() -> bool:
    from pyspark_tf_gke_tpu import native

    return native.available()


# ---------------------------------------------------------------------------
# writing
# ---------------------------------------------------------------------------


def _write_one_shard(arrays: Dict[str, np.ndarray], schema: Schema,
                     path: str, shard: int, num_shards: int,
                     use_native: bool) -> None:
    """Write shard ``shard`` (rows ``shard::num_shards``) to ``path`` —
    the per-shard body both the serial and threaded writers run, so
    their outputs are byte-identical."""
    n = len(next(iter(arrays.values())))
    if use_native:
        from pyspark_tf_gke_tpu import native as io

        with io.RecordWriter(path) as w:
            for i in range(shard, n, num_shards):
                row = {k: arrays[k][i] for k in schema}
                w.write(io.encode_example(schema, row))
    else:
        from pyspark_tf_gke_tpu.data.codec import encode_example, encode_record

        with open(path, "wb") as f:
            for i in range(shard, n, num_shards):
                row = {k: arrays[k][i] for k in schema}
                f.write(encode_record(encode_example(schema, row)))


def write_tfrecord_shards(
    arrays: Dict[str, np.ndarray],
    path_prefix: str,
    num_shards: int = 4,
    schema: Optional[Schema] = None,
    num_workers: Optional[int] = None,
) -> Sequence[str]:
    """Write row-aligned arrays as TFRecord shards via the native codec
    (python-codec fallback). Same naming/striping as the tf.data writer:
    ``{prefix}-{i:05d}-of-{n:05d}.tfrecord``, row i -> shard i % n.

    Shards are independent row stripes, so they write CONCURRENTLY: one
    worker thread per shard up to ``num_workers`` (default
    ``min(num_shards, cpu_count)``; 1 = the serial path). Output bytes
    are identical either way — the parallel writer is a pure throughput
    change (the native writer's encode/IO path releases the GIL so
    threads genuinely overlap). A worker exception
    cancels the write and re-raises at the caller with the shard's
    partial file removed — matching the ``data/pipeline.py`` prefetch
    relay contract: no silent half-written shard can reach a manifest.
    """
    from pyspark_tf_gke_tpu.data.tfrecord import schema_for

    if schema is None:
        schema = schema_for(arrays)
    n = len(next(iter(arrays.values())))
    for k, v in arrays.items():
        if len(v) != n:
            raise ValueError(f"array {k!r} length {len(v)} != {n}")
    os.makedirs(os.path.dirname(os.path.abspath(path_prefix)), exist_ok=True)

    use_native = native_available()
    if not use_native:
        logger.warning("native IO unavailable; using pure-Python codec")

    paths = [f"{path_prefix}-{shard:05d}-of-{num_shards:05d}.tfrecord"
             for shard in range(num_shards)]
    if num_workers is None:
        num_workers = min(num_shards, os.cpu_count() or 1)
    num_workers = max(1, min(int(num_workers), num_shards))

    if num_workers == 1:
        for shard, path in enumerate(paths):
            try:
                _write_one_shard(arrays, schema, path, shard, num_shards,
                                 use_native)
            except BaseException:
                try:  # same no-torn-shard contract as the threaded path
                    os.remove(path)
                except OSError:
                    pass
                raise
        return paths

    import queue
    import threading

    todo: "queue.Queue" = queue.Queue()
    for shard in range(num_shards):
        todo.put(shard)
    errors: list = []
    err_lock = threading.Lock()

    def worker() -> None:
        while True:
            try:
                shard = todo.get_nowait()
            except queue.Empty:
                return
            with err_lock:
                if errors:  # a sibling failed: stop dequeuing work
                    return
            try:
                _write_one_shard(arrays, schema, paths[shard], shard,
                                 num_shards, use_native)
            except BaseException as exc:  # noqa: BLE001 — relayed below
                try:  # never leave a torn shard behind
                    os.remove(paths[shard])
                except OSError:
                    pass
                with err_lock:
                    errors.append(exc)
                return

    threads = [threading.Thread(target=worker, name=f"shard-writer-{i}",
                                daemon=True)
               for i in range(num_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return paths


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------


def _iter_rows(
    files: Sequence[str], schema: Schema, nthreads: int, read_batch: int
) -> Iterator[Dict[str, np.ndarray]]:
    """Stream decoded row-blocks from the shard set."""
    if native_available():
        from pyspark_tf_gke_tpu.native import ExamplePool

        with ExamplePool(files, schema, nthreads=nthreads) as pool:
            while True:
                block = pool.next_rows(read_batch)
                if block is None:
                    return
                yield block
    else:
        from pyspark_tf_gke_tpu.data.codec import iter_records, parse_example

        rows = []
        for path in files:
            for rec in iter_records(path):
                rows.append(parse_example(schema, rec))
                if len(rows) == read_batch:
                    yield {
                        k: np.stack([r[k] for r in rows]) for k in schema
                    }
                    rows = []
        if rows:
            yield {k: np.stack([r[k] for r in rows]) for k in schema}


class ShuffleBuffer:
    """Fixed-capacity reservoir shuffle, the tf.data ``shuffle(buffer)``
    analog (reference uses buffer 3000, train_tf_ps.py:599)."""

    def __init__(self, capacity: int, seed: int = DEFAULT_SEED):
        self.capacity = capacity
        self._rng = np_rng(seed)
        self._rows: list = []

    def push_pop(self, row) -> Optional[object]:
        if len(self._rows) < self.capacity:
            self._rows.append(row)
            return None
        j = int(self._rng.integers(len(self._rows)))
        out = self._rows[j]
        self._rows[j] = row
        return out

    def drain(self) -> Iterator[object]:
        order = self._rng.permutation(len(self._rows))
        for j in order:
            yield self._rows[j]
        self._rows = []


def read_tfrecord_batches(
    pattern: str,
    schema: Schema,
    batch_size: int,
    shuffle: bool = True,
    seed: int = DEFAULT_SEED,
    repeat: bool = True,
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
    nthreads: int = 4,
    shuffle_buffer: int = 3000,
    int_dtype=np.int32,
) -> Iterator[Dict[str, np.ndarray]]:
    """Stream host-sharded numpy batches from TFRecord shards, natively.

    Drop-in replacement for ``data.tfrecord.read_tfrecord_batches`` —
    same file-level host sharding (sorted files striped over processes)
    and the same cast of int features to int32 that the tf.data parse fn
    applies.
    """
    import jax

    if process_index is None:
        process_index = jax.process_index()
    if process_count is None:
        process_count = jax.process_count()

    from pyspark_tf_gke_tpu.utils.fs import fs_glob, spool_local

    files = fs_glob(pattern)
    if not files:
        raise FileNotFoundError(f"no TFRecord shards match {pattern!r}")
    local_files = files[process_index::process_count]
    if not local_files:
        raise ValueError(
            f"{len(files)} shards < {process_count} processes; write more shards"
        )
    # The C++ reader (native/src/tfrecord_io.cc) is fopen-based —
    # gs://-and-friends stage through the local spool once, then every
    # epoch reads locally. Sharding happens BEFORE spooling: each host
    # downloads only its own shards.
    local_files = [spool_local(f) for f in local_files]

    def cast(batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        out = {}
        for k, (kind, _) in schema.items():
            v = batch[k]
            out[k] = v.astype(int_dtype) if kind == "int" else v
        return out

    pending: Dict[str, list] = {k: [] for k in schema}
    pending_rows = 0

    def emit_ready() -> Iterator[Dict[str, np.ndarray]]:
        nonlocal pending, pending_rows
        while pending_rows >= batch_size:
            batch = {}
            for k in schema:
                stacked = (
                    pending[k][0]
                    if len(pending[k]) == 1
                    else np.concatenate(pending[k])
                )
                batch[k] = stacked[:batch_size]
                pending[k] = [stacked[batch_size:]]
            pending_rows -= batch_size
            yield cast(batch)

    while True:  # epoch loop (single pass if not repeat)
        if shuffle:
            buf = ShuffleBuffer(shuffle_buffer, seed=seed)
            seed += 1  # reshuffle differently each epoch, deterministically

            def rows():
                for block in _iter_rows(local_files, schema, nthreads, batch_size):
                    n = len(next(iter(block.values())))
                    for i in range(n):
                        out = buf.push_pop({k: block[k][i] for k in schema})
                        if out is not None:
                            yield out
                yield from buf.drain()

            row_iter = rows()
            stash: list = []
            for row in row_iter:
                stash.append(row)
                if len(stash) == batch_size:
                    yield cast({k: np.stack([r[k] for r in stash]) for k in schema})
                    stash = []
            # drop remainder (parity with drop_remainder=True)
        else:
            for block in _iter_rows(local_files, schema, nthreads, batch_size):
                for k in schema:
                    pending[k].append(block[k])
                pending_rows += len(next(iter(block.values())))
                yield from emit_ready()
            pending = {k: [] for k in schema}
            pending_rows = 0
        if not repeat:
            return


# ---------------------------------------------------------------------------
# manifest tailing (the continuous pipeline's trainer-side data source)
# ---------------------------------------------------------------------------


class ManifestTailSource:
    """Infinite batch iterator tailing a growing shard-set manifest.

    The continuous pipeline's trainer-side hand-off: the ETL side
    appends completed shard generations to a
    :class:`~pyspark_tf_gke_tpu.pipeline.manifest.ShardSetManifest`;
    this source re-reads the manifest at every **epoch boundary**, so
    shards landed mid-epoch join the NEXT epoch's pass (an epoch is one
    deterministic shuffled pass over the shard set present when it
    started — the ``dataset.shard``+``repeat`` analog, made growable).

    Determinism + resume: epoch ``e`` shuffles with ``seed + e`` through
    a :class:`~pyspark_tf_gke_tpu.data.pipeline.BatchIterator`, and
    ``consumed_batches`` counts every draw. Re-creating the source with
    a persisted ``consumed_batches`` replays epoch lengths against the
    CURRENT manifest and ``fast_forward``s into the interrupted epoch —
    a coordinator restart resumes the exact batch stream mid-epoch
    whenever the manifest hasn't grown since the crash (and a
    consistent, freshly-shuffled stream when it has).

    Host-sharding mirrors :func:`read_tfrecord_batches`: sorted shards
    striped over processes, each host reading only its own files.
    """

    def __init__(self, manifest_path: str, schema: Schema,
                 batch_size: int, *, shuffle: bool = True,
                 seed: int = DEFAULT_SEED, consumed_batches: int = 0,
                 wait_timeout_s: float = 60.0, poll_s: float = 0.1,
                 nthreads: int = 1, int_dtype=np.int32,
                 process_index: int = 0, process_count: int = 1):
        # nthreads defaults to 1: exact-resume REQUIRES a deterministic
        # row order, and the native ExamplePool interleaves shard
        # blocks nondeterministically with >1 reader thread — the
        # seeded BatchIterator shuffle then permutes DIFFERENT
        # underlying rows run to run, silently breaking the
        # replay-identical contract (and its test) ~1 run in 8.
        # Epoch loads are once-per-epoch; determinism outranks read
        # parallelism here. Callers that don't resume may raise it.
        from pyspark_tf_gke_tpu.pipeline.manifest import ShardSetManifest

        self.manifest = ShardSetManifest(manifest_path)
        self.schema = schema
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.seed = int(seed)
        self.wait_timeout_s = float(wait_timeout_s)
        self.poll_s = float(poll_s)
        self.nthreads = int(nthreads)
        self.int_dtype = int_dtype
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.consumed_batches = 0
        self.epoch = 0
        self.data_generation = 0  # manifest generation the epoch saw
        self._it: Optional["BatchIterator"] = None
        self._remaining = 0
        self._fast_forward(int(consumed_batches))

    # -- internals ------------------------------------------------------

    def _load_rows(self) -> Dict[str, np.ndarray]:
        """All rows of this host's stripe of the CURRENT shard set,
        blocking (bounded) until the manifest holds at least one full
        batch for it."""
        import time as _time

        deadline = _time.monotonic() + self.wait_timeout_s
        while True:
            gen = self.manifest.generation()
            shards = self.manifest.shards()
            local = sorted(shards)[self.process_index::self.process_count]
            rows: Dict[str, list] = {k: [] for k in self.schema}
            count = 0
            for block in (_iter_rows(local, self.schema, self.nthreads,
                                     max(self.batch_size, 256))
                          if local else ()):
                for k in self.schema:
                    rows[k].append(block[k])
                count += len(next(iter(block.values())))
            if count >= self.batch_size:
                self.data_generation = gen
                out = {}
                for k, (kind, _) in self.schema.items():
                    stacked = (rows[k][0] if len(rows[k]) == 1
                               else np.concatenate(rows[k]))
                    out[k] = (stacked.astype(self.int_dtype)
                              if kind == "int" else stacked)
                return out
            if _time.monotonic() >= deadline:
                raise FileNotFoundError(
                    f"manifest {self.manifest.path} holds {count} row(s) "
                    f"for host {self.process_index}/{self.process_count} "
                    f"(< batch_size {self.batch_size}) after "
                    f"{self.wait_timeout_s}s")
            _time.sleep(self.poll_s)

    def _start_epoch(self) -> None:
        from pyspark_tf_gke_tpu.data.pipeline import BatchIterator

        arrays = self._load_rows()
        self._it = BatchIterator(arrays, self.batch_size,
                                 shuffle=self.shuffle,
                                 seed=self.seed + self.epoch)
        self._remaining = self._it.steps_per_epoch

    def _fast_forward(self, consumed: int) -> None:
        """Replay ``consumed`` draws' worth of epoch bookkeeping against
        the current manifest, landing mid-epoch via
        ``BatchIterator.fast_forward``."""
        if consumed < 0:
            raise ValueError(f"consumed_batches must be >= 0, "
                             f"got {consumed}")
        self._start_epoch()
        # the manifest is fixed for the duration of this replay, so
        # every replayed epoch has the SAME length — skip whole epochs
        # arithmetically (one shard-set reload at the final epoch for
        # its seed) instead of re-reading the data once per epoch
        spe = self._it.steps_per_epoch
        skip_epochs, left = divmod(consumed, spe)
        if skip_epochs:
            self.epoch += skip_epochs
            self._start_epoch()
        if left:
            self._it.fast_forward(left)
            self._remaining -= left
        self.consumed_batches = int(consumed)

    # -- iteration ------------------------------------------------------

    def __iter__(self) -> "ManifestTailSource":
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        if self._remaining <= 0:
            # epoch boundary: re-read the manifest — generations landed
            # mid-epoch join this new pass
            self.epoch += 1
            self._start_epoch()
        batch = next(self._it)
        self._remaining -= 1
        self.consumed_batches += 1
        return batch
