"""One-command data-plane bootstrap: the reference's load->ingest->
KMeans->bridge chain against this stack.

The reference's data story is a sequence of manual steps documented in
its READMEs: run ``load_csv.py`` against a port-forwarded MySQL
(``/root/reference/infra/local/mysql-database/load_csv.py:138-171``),
then submit ``k_means.py`` which ingests over JDBC and fits the
KMeans pipeline (``workloads/raw-spark/k_means.py:164-208``). This
module makes that whole chain ONE command against our stack:

    python -m pyspark_tf_gke_tpu.etl.bootstrap --out /tmp/etl_demo

which, in order:

1. generates the reference-schema dataset at reference scale
   (``data/synthetic.py::make_reference_csv`` — 18,154 rows, same
   header, hole rates, and comma-in-source quoting), or takes
   ``--csv`` to use a real file;
2. loads it into MySQL *when the glue can run* (mysql-connector
   importable and ``--mysql-host`` given — the sandbox has neither, so
   the step records WHY it was skipped instead of pretending);
3. ingests + fits KMeans. With a JVM + pyspark present this drives the
   Spark glue (session -> partitioned JDBC -> ``KMeansSparkWorkload``);
   otherwise the TPU-native twins run the same semantics directly from
   the CSV (``FeaturePipeline`` -> ``etl.kmeans.KMeans`` -> silhouette);
4. writes the feature matrix + cluster labels as TFRecord shards via
   the bridge (``etl/tfrecord_bridge.py``) and reads them back,
   verifying the row count round-trips.

Every step lands in the JSON summary printed as the last stdout line,
with ``"skipped"`` + reason for steps the environment cannot run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

import numpy as np


def _try_mysql_load(csv_path: str, host: Optional[str], summary: dict) -> None:
    if not host:
        summary["mysql_load"] = {
            "skipped": "no --mysql-host given (reference flow: "
                       "kubectl port-forward svc/mysql-external 3306)"}
        return
    try:
        import mysql.connector  # noqa: F401
    except ImportError:
        summary["mysql_load"] = {
            "skipped": "mysql-connector-python not installed"}
        return
    from pyspark_tf_gke_tpu.etl.load_csv_mysql import load_csv_to_mysql

    t0 = time.time()
    try:
        n = load_csv_to_mysql(csv_path, host=host)
    except Exception as exc:  # noqa: BLE001 — a dead port-forward must
        # not take down the MySQL-independent steps; the summary keeps
        # the failure loud instead
        summary["mysql_load"] = {"failed": f"{type(exc).__name__}: {exc}"}
        return
    summary["mysql_load"] = {"rows": n, "seconds": round(time.time() - t0, 1)}


def _spark_available() -> Optional[str]:
    try:
        import pyspark  # noqa: F401
    except ImportError:
        return "pyspark not installed"
    import shutil

    if not (os.environ.get("JAVA_HOME") or shutil.which("java")):
        return "no JVM (java not on PATH, JAVA_HOME unset)"
    return None


def _run_spark_chain(csv_path: str, mysql_host: Optional[str],
                     summary: dict) -> Optional[np.ndarray]:
    """The reference's actual executor path when the environment has a
    JVM: local[2] session (its own smoke pattern,
    ``spark_checks/python_checks/spark_installation_check.py:12-46``),
    CSV read (or JDBC when MySQL was loaded), KMeans pipeline."""
    why_not = _spark_available()
    if why_not:
        summary["spark_chain"] = {"skipped": why_not}
        return None
    from pyspark.sql import SparkSession

    from pyspark_tf_gke_tpu.etl.kmeans_spark import KMeansSparkWorkload

    t0 = time.time()
    spark = None
    try:
        builder = (SparkSession.builder.master("local[2]")
                   .appName("etl-bootstrap"))
        if mysql_host:
            # the JDBC read needs Connector/J on the executor classpath;
            # same coordinate the reference vendors as a jar
            # (infra/local/local_spark/jars/mysql-connector-j-8.4.0.jar)
            builder = builder.config(
                "spark.jars.packages", "com.mysql:mysql-connector-j:8.4.0")
        spark = builder.getOrCreate()
        if mysql_host:
            import logging

            from pyspark_tf_gke_tpu.etl.jdbc_ingest import (
                RetrieveDataFromMySQL)
            from pyspark_tf_gke_tpu.etl.spark_session import DB_CONFIG

            cfg = dict(DB_CONFIG, host=mysql_host)
            df = RetrieveDataFromMySQL(
                logging.getLogger("bootstrap"), cfg,
                spark).read_data_from_mysql()
        else:
            df = (spark.read.option("header", True)
                  .option("inferSchema", True).csv(csv_path))
        wl = KMeansSparkWorkload()
        wl.k_means(df)
        sil = wl.silhouette(df)
        summary["spark_chain"] = {
            "rows": df.count(), "silhouette": round(float(sil), 4),
            "seconds": round(time.time() - t0, 1)}
    except Exception as exc:  # noqa: BLE001 — a JDBC/Spark failure is
        # recorded, not fatal: the native twins below still run
        summary["spark_chain"] = {"failed": f"{type(exc).__name__}: {exc}"}
    finally:
        if spark is not None:
            spark.stop()
    return None


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True,
                    help="working directory for the generated artifacts")
    ap.add_argument("--csv", default=None,
                    help="existing reference-schema CSV (default: generate)")
    ap.add_argument("--rows", type=int, default=18154,
                    help="generator row count (reference scale)")
    ap.add_argument("--k", type=int, default=None,
                    help="clusters (default: etl.knobs.kmeans_k -> 25)")
    ap.add_argument("--max-iter", type=int, default=100,
                    help="Lloyd iterations (reference: 1000; 100 converges "
                    "on this data and keeps the demo minutes-scale on CPU)")
    ap.add_argument("--silhouette-sample", type=int, default=4096,
                    help="rows sampled for the O(N^2) silhouette")
    ap.add_argument("--mysql-host", default=None)
    ap.add_argument("--shards", type=int, default=16,
                    help="TFRecord shards (reference JDBC partitions: 16)")
    ap.add_argument("--platform", choices=("cpu", "default"), default="cpu",
                    help="jax platform for the native KMeans: 'cpu' "
                    "(default — an ETL demo must not take the chip a "
                    "trainer or server on this host owns) or 'default' "
                    "(whatever the env provides)")
    args = ap.parse_args(argv)

    if args.platform == "cpu":
        import jax

        # config.update wins over the JAX_PLATFORMS env var as long as
        # it runs before first backend use
        jax.config.update("jax_platforms", "cpu")

    os.makedirs(args.out, exist_ok=True)
    summary: dict = {"metric": "etl_bootstrap"}

    # 1. dataset
    t0 = time.time()
    if args.csv:
        csv_path = args.csv
        summary["dataset"] = {"path": csv_path, "generated": False}
    else:
        from pyspark_tf_gke_tpu.data.synthetic import make_reference_csv

        csv_path = make_reference_csv(
            os.path.join(args.out, "health.csv"), rows=args.rows)
        summary["dataset"] = {"path": csv_path, "generated": True,
                              "rows": args.rows,
                              "seconds": round(time.time() - t0, 1)}

    # 2. MySQL load (environment-gated, disclosed)
    _try_mysql_load(csv_path, args.mysql_host, summary)

    # 3a. Spark chain (environment-gated, disclosed)
    _run_spark_chain(csv_path, args.mysql_host, summary)

    # 3b. TPU-native twins — always run: the same pipeline semantics
    # (null filter, string index, one-hot x weight, mean imputation,
    # Lloyd's) without the JVM.
    from pyspark_tf_gke_tpu.etl.feature_pipeline import FeaturePipeline
    from pyspark_tf_gke_tpu.etl.kmeans import KMeans, silhouette_score
    from pyspark_tf_gke_tpu.etl.knobs import kmeans_k
    from pyspark_tf_gke_tpu.etl.workload import read_columns

    t0 = time.time()
    cols = read_columns(csv_path)
    pipe = FeaturePipeline()
    feats = pipe.fit_transform(cols)
    k = args.k or kmeans_k()
    km = KMeans(k=k, max_iter=args.max_iter, seed=1)
    km.fit(feats)
    labels = km.predict(feats)
    rng = np.random.default_rng(0)
    sample = rng.choice(len(feats), min(args.silhouette_sample, len(feats)),
                        replace=False)
    sil = silhouette_score(feats[sample], labels[sample])
    summary["native_chain"] = {
        "rows_in": int(len(cols["measure_name"])),
        "rows_kept": int(feats.shape[0]),
        "feature_width": int(feats.shape[1]),
        "k": k, "iters": int(km.n_iter),
        "silhouette": round(float(sil), 4),
        "silhouette_sample": int(len(sample)),
        "seconds": round(time.time() - t0, 1),
    }

    # 4. bridge: features+labels -> TFRecord shards -> read back
    from pyspark_tf_gke_tpu.etl.tfrecord_bridge import write_partition_rows

    t0 = time.time()
    prefix = os.path.join(args.out, "clusters")
    n = feats.shape[0]
    written = []
    for idx in range(args.shards):
        part = [
            {"features": feats[i].tolist(), "cluster": int(labels[i])}
            for i in range(idx, n, args.shards)
        ]
        written += list(write_partition_rows(
            idx, part, prefix, cols=["features", "cluster"],
            num_shards=args.shards))
    # read back with the first-party reader (no tf dependency).
    # process_index/count pinned so no jax backend init happens — a
    # host-side read must not claim the chip (one process per chip).
    from pyspark_tf_gke_tpu.data.native_tfrecord import read_tfrecord_batches

    # batch_size=1: the reader's drop-remainder contract (training
    # parity) must not eat the tail rows of the exact-count check
    seen = 0
    for batch in read_tfrecord_batches(
            f"{prefix}-*-of-{args.shards:05d}.tfrecord",
            {"features": ("float", (feats.shape[1],)),
             "cluster": ("int", ())},
            batch_size=1, shuffle=False, repeat=False,
            process_index=0, process_count=1):
        seen += len(batch["cluster"])
    summary["bridge"] = {
        "shards": len(written), "rows_written": n, "rows_read": seen,
        "roundtrip_ok": seen == n,
        "seconds": round(time.time() - t0, 1),
    }
    ok = summary["bridge"]["roundtrip_ok"] and np.isfinite(sil)
    summary["value"] = 1 if ok else 0
    summary["unit"] = "bootstrap_ok"
    print(json.dumps(summary))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(run())
