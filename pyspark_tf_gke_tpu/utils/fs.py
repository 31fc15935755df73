"""Filesystem access for the TPU-host data plane: local paths plus
fsspec URLs (``gs://`` in production; ``memory://`` in unit tests).

The reference reads ``gs://<project>-datasets/health.csv`` through the
Spark GCS connector and tf.data's native GCS filesystem
(``/root/reference/workloads/raw-spark/spark_checks/python_checks/spark_workload_to_cloud_k8s.py:40-48``);
this module is the equivalent for our host-side readers:

* ``fs_open``  — streaming reads for the CSV loader;
* ``fs_glob``  — shard-pattern expansion for the TFRecord readers;
* ``spool_local`` — stage a remote object into a local spool file for
  readers that need a real file descriptor (the C++ TFRecord reader,
  ``native/src/tfrecord_io.cc``, is fopen-based by design — sequential
  local reads; remote objects stream through the spool once).

HTTP(S) is deliberately not handled here — ``data.csv_loader.open_text``
keeps the reference's urlopen semantics for those.
"""

from __future__ import annotations

import glob as _glob
import hashlib
import os
import shutil
import tempfile
from typing import IO, List, Optional

_HTTP = ("http://", "https://")


def is_remote(path: str) -> bool:
    """True for fsspec-routed URLs (gs://, gcs://, memory://, s3://...);
    False for local paths and http(s), which have their own handling."""
    return "://" in path and not path.startswith(_HTTP)


def fs_open(path: str, mode: str = "rb") -> IO:
    """Open a local file or an fsspec URL."""
    if is_remote(path):
        import fsspec

        return fsspec.open(path, mode).open()
    return open(path, mode)


def fs_glob(pattern: str) -> List[str]:
    """Sorted glob for local patterns and fsspec URLs (scheme preserved)."""
    if is_remote(pattern):
        import fsspec

        fs, _, _ = fsspec.get_fs_token_paths(pattern)
        return sorted(fs.unstrip_protocol(p) for p in fs.glob(pattern))
    return sorted(_glob.glob(pattern))


def _default_spool_dir() -> str:
    """Per-user spool dir, created 0700 — a predictable world-shared
    /tmp path would let another local user pre-plant spool files."""
    d = os.path.join(tempfile.gettempdir(), f"fs_spool-{os.getuid()}")
    os.makedirs(d, mode=0o700, exist_ok=True)
    if os.stat(d).st_uid != os.getuid():  # pre-created by someone else
        d = tempfile.mkdtemp(prefix="fs_spool-")
    return d


def fs_makedirs(path: str) -> None:
    """mkdir -p for local paths; no-op for object stores (GCS has no
    directories — objects simply exist under a prefix)."""
    if not is_remote(path):
        os.makedirs(path, exist_ok=True)


def fs_write_text(path: str, text: str) -> str:
    """Write a small text artifact (history.json, run notes, label map)
    GCS-compatibly: one whole-object write per call — no append, no
    seek, which object stores don't support. Local writes go through a
    same-directory temp file + atomic rename so concurrent readers
    never observe a torn artifact."""
    if is_remote(path):
        import fsspec

        with fsspec.open(path, "w") as fh:
            fh.write(text)
        return path
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        fh.write(text)
    os.replace(tmp, path)
    return path


def fs_copy_tree(url: str, local_dir: str) -> str:
    """Recursively copy a remote directory tree (e.g. a ``gs://``
    serving bundle) into ``local_dir``. orbax restores from a directory
    tree, so serving pulls the whole bundle once rather than streaming
    per-file."""
    if not is_remote(url):
        raise ValueError(f"fs_copy_tree expects a remote URL, got {url!r}")
    import fsspec

    fs, _, (root,) = fsspec.get_fs_token_paths(url.rstrip("/"))
    os.makedirs(local_dir, exist_ok=True)
    # trailing separators make get() copy root's CONTENTS into local_dir
    # (async-batched on gcsfs) rather than nesting a basename dir
    fs.get(root.rstrip("/") + "/", local_dir.rstrip("/") + "/",
           recursive=True)
    return local_dir


def spool_local(path: str, spool_dir: Optional[str] = None) -> str:
    """Return a local path for ``path``, staging remote objects into a
    spool file (re-used across calls within the spool dir). The cache
    key includes the object's version metadata (etag or mtime from
    ``fs.info``), so an overwritten remote object re-downloads instead
    of serving a stale copy. A filesystem that gives neither (``memory://``)
    gives nothing that tells one object's contents from another's at the
    same path (a size does not: two runs' shards are equally long, and
    the default spool outlives a run), so its objects are copied anew on
    every call. Local paths pass through untouched."""
    if not is_remote(path):
        return path
    import fsspec

    fs, _, _ = fsspec.get_fs_token_paths(path)
    try:
        info = fs.info(path)
        version = info.get("etag") or info.get("mtime")
    except Exception:
        version = None
    spool_dir = spool_dir or _default_spool_dir()
    os.makedirs(spool_dir, exist_ok=True)
    digest = hashlib.sha1(f"{path}\0{version or ''}".encode()).hexdigest()[:16]
    local = os.path.join(spool_dir, f"{digest}-{os.path.basename(path)}")
    if version is None or not os.path.exists(local):
        tmp = f"{local}.tmp.{os.getpid()}"
        with fsspec.open(path, "rb") as src, open(tmp, "wb") as dst:
            shutil.copyfileobj(src, dst)
        os.replace(tmp, local)  # atomic: concurrent spoolers converge
    return local
