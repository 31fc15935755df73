"""Serving-bundle export/load: the framework's terminal model artifact.

The reference's terminal artifact is a saved Keras model plus sidecar
JSONs (``train_tf_ps.py:674-679``, ``tf-model/*``); the TPU-native
analog is a **serving bundle**: one directory holding

* ``config.json``   — the model architecture (CausalLMConfig fields,
  minus the dtype, which is serialized by name) + bundle metadata
  (quantized or not, tokenizer spec);
* ``params/``       — an orbax snapshot of the param tree, either dense
  or weight-only int8 (``ops/quant.py`` QTensor leaves — a pytree, so
  orbax handles it natively and the artifact shrinks ~4×).

``load_serving_bundle`` reconstructs the model and params ready for
``train/serving.py`` placement on any mesh. No framework-pickle, no
code in the artifact — config is data, weights are arrays.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from pyspark_tf_gke_tpu.models.causal_lm import CausalLM, CausalLMConfig
from pyspark_tf_gke_tpu.ops.quant import is_quantized, quantize_tree

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def _qtensor_paths(params) -> list:
    """Sorted keystr paths of every QTensor leaf."""
    from pyspark_tf_gke_tpu.ops.quant import QTensor

    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda l: isinstance(l, QTensor))
    return sorted(jax.tree_util.keystr(path) for path, leaf in flat
                  if isinstance(leaf, QTensor))


def _qtensor_scale_shapes(params) -> dict:
    """keystr path → scale shape for every QTensor leaf. Recorded in the
    bundle so the loader rebuilds the exact abstract (per-column kernels
    carry ``(cols,)`` scales, per-row embedding tables ``(rows, 1)``,
    caller-quantized trees whatever the caller chose) without guessing
    from the path."""
    from pyspark_tf_gke_tpu.ops.quant import QTensor

    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda l: isinstance(l, QTensor))
    return {jax.tree_util.keystr(path): list(leaf.scale.shape)
            for path, leaf in flat if isinstance(leaf, QTensor)}


def export_serving_bundle(
    cfg: CausalLMConfig,
    params: Any,
    out_dir: str,
    quantize: bool = True,
    tokenizer_spec: str = "byte",
    quantize_min_size: int = 4096,
    extra_meta: Optional[dict] = None,
) -> str:
    """Write a self-contained serving bundle. Returns ``out_dir``.

    ``extra_meta``: caller annotations merged into ``config.json``
    (reserved keys win) — the pipeline coordinator stamps
    ``pipeline_generation`` here so a replica serving the bundle
    advertises that generation on ``/loadz``."""
    os.makedirs(out_dir, exist_ok=True)
    if quantize and not is_quantized(params):
        params = jax.jit(
            lambda p: quantize_tree(p, min_size=quantize_min_size))(params)

    cfg_dict = dataclasses.asdict(cfg)
    cfg_dict["dtype"] = jnp.dtype(cfg.dtype).name
    meta = {
        **(extra_meta or {}),
        "format": "pyspark_tf_gke_tpu.serving_bundle.v1",
        "model": "causal_lm",
        "quantized": bool(is_quantized(params)),
        # The exact QTensor leaf paths, recorded so the loader rebuilds
        # the same pytree no matter how the tree was quantized (caller-
        # quantized trees included — a min_size alone couldn't say).
        "quantized_paths": _qtensor_paths(params),
        "quantized_scale_shapes": _qtensor_scale_shapes(params),
        "tokenizer": tokenizer_spec,
        "config": cfg_dict,
    }
    if jax.process_index() == 0:
        with open(os.path.join(out_dir, "config.json"), "w") as fh:
            json.dump(meta, fh, indent=2)

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(os.path.abspath(out_dir), "params"), params,
               force=True)
    ckptr.wait_until_finished()
    ckptr.close()
    return out_dir


def load_serving_bundle(bundle_dir: str) -> Tuple[CausalLM, Any, dict]:
    """Load ``(model, params, meta)`` from an exported bundle. The
    params come back with the exact pytree the bundle was saved with
    (QTensor leaves included) — pass them through
    ``train/serving.shard_params_for_serving`` to place on a mesh."""
    with open(os.path.join(bundle_dir, "config.json")) as fh:
        meta = json.load(fh)
    if meta.get("model") != "causal_lm":
        raise ValueError(f"unsupported bundle model {meta.get('model')!r}")

    cfg_dict = dict(meta["config"])
    cfg_dict["dtype"] = _DTYPES[cfg_dict["dtype"]]
    cfg = CausalLMConfig(**cfg_dict)
    model = CausalLM(cfg)

    # Abstract target with the same pytree (incl. QTensor nodes) so
    # orbax restores structure-exactly: re-init abstractly, then
    # quantize exactly the leaves the bundle recorded as QTensors.
    from flax import linen as nn

    from pyspark_tf_gke_tpu.ops.quant import quantize_tensor

    sample = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(
        lambda: nn.meta.unbox(model.init(jax.random.PRNGKey(0), sample)["params"]))
    qpaths = set(meta.get("quantized_paths", []))
    if qpaths:
        from pyspark_tf_gke_tpu.ops.quant import QTensor, is_embedding_path

        scale_shapes = meta.get("quantized_scale_shapes", {})

        def requantize_with(path, leaf, embed_axis0: bool):
            key = jax.tree_util.keystr(path)
            if key not in qpaths:
                return leaf
            if key in scale_shapes:
                # the bundle records each scale's exact shape — rebuild
                # the abstract from it so orbax validation matches
                # whatever granularity the export used
                return QTensor(
                    jax.ShapeDtypeStruct(leaf.shape, jnp.int8),
                    jax.ShapeDtypeStruct(
                        tuple(scale_shapes[key]), jnp.float32),
                    leaf.dtype)
            # Bundles from before scale shapes were recorded: most are
            # uniformly per-column, but a brief window quantized
            # embedding tables per-row — build_abstract covers both and
            # the loader below retries with the other interpretation.
            axis = 0 if (embed_axis0 and is_embedding_path(path)) else -1
            return jax.eval_shape(lambda l: quantize_tensor(l, axis=axis),
                                  leaf)

        def build_abstract(embed_axis0: bool):
            return jax.tree_util.tree_map_with_path(
                lambda p, l: requantize_with(p, l, embed_axis0), abstract)

        abstract_candidates = ([build_abstract(False)] if scale_shapes else
                               [build_abstract(False), build_abstract(True)])
    elif meta.get("quantized"):
        # Back-compat: bundles written before quantized_paths were
        # recorded carry only the export-side min_size threshold — and
        # predate per-row embedding scales, so every recorded scale is
        # the legacy per-column (cols,) shape.
        min_size = int(meta.get("quantize_min_size", 4096))

        def legacy_q(leaf):
            if (len(leaf.shape) == 2
                    and int(np.prod(leaf.shape)) >= min_size
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                return jax.eval_shape(quantize_tensor, leaf)
            return leaf

        abstract_candidates = [jax.tree.map(legacy_q, abstract)]
    else:
        abstract_candidates = [abstract]

    # Restore onto ONE named device, never into the layout recorded at
    # export: a bundle written by a 4-chip trainer records 4-device
    # shardings, and params restored into those make every serving jit
    # a multi-device program (which Mosaic kernels refuse outside a
    # shard_map) — and a bundle from another topology would not load at
    # all. Callers place the result (shard_params_for_serving).
    if jax.process_count() > 1:
        # Every process restores the FULL array onto its own CPU backend
        # device — host RAM, NOT an accelerator: a model that needs tp
        # to fit would OOM a single chip's HBM before
        # shard_params_for_serving ever placed its shards. (orbax also
        # refuses sharding-less abstract arrays here.)
        try:
            target = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # pragma: no cover - cpu backend always exists
            target = jax.local_devices()[0]
    else:
        target = jax.devices()[0]
    local = jax.sharding.SingleDeviceSharding(target)

    def pin(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=local)
        return leaf

    abstract_candidates = [jax.tree.map(pin, c)
                           for c in abstract_candidates]

    ckptr = ocp.StandardCheckpointer()
    try:
        params_path = os.path.join(os.path.abspath(bundle_dir), "params")
        first_exc = None
        for i, candidate in enumerate(abstract_candidates):
            try:
                params = ckptr.restore(params_path, candidate)
                break
            except Exception as exc:  # orbax shape-validation mismatch
                # The FIRST candidate is the expected layout; if every
                # candidate fails, its error is the real cause (a
                # missing/corrupt checkpoint would otherwise surface as
                # the ALTERNATE candidate's confusing shape mismatch).
                if first_exc is None:
                    first_exc = exc
                if i == len(abstract_candidates) - 1:
                    raise first_exc
    finally:
        ckptr.close()
    if jax.process_count() > 1:
        # hand callers host numpy: device_put from a committed
        # single-device array to a global multi-process sharding is the
        # one transfer shape jax does not support
        params = jax.device_get(params)
    return model, params, meta
