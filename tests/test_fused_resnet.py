"""Parity tests for the fused (Pallas) ResNet bottleneck path.

The fused block must be a *semantics-preserving* rewrite of the baseline
``BottleneckBlock`` + ``nn.BatchNorm`` stack: same math, different pass
structure. These tests map parameters between the two module trees and
require forward outputs, gradients, and running-statistic updates to
match in f32 (where the rewrite is exact up to reduction order).
Kernel-level numerics are covered in test_pallas_ops.py-style interpret
mode; nothing runs this path on hardware (ROADMAP.md, Design 3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.models.resnet import (
    BottleneckBlock, FusedBottleneckBlock, ResNet50)

import flax.linen as nn
import functools


def _baseline_block(features, strides, dtype):
    conv = functools.partial(nn.Conv, use_bias=False, dtype=dtype)
    norm = functools.partial(nn.BatchNorm, use_running_average=False,
                             momentum=0.9, epsilon=1e-5, dtype=dtype)
    return BottleneckBlock(features, conv=conv, norm=norm, strides=strides)


def _map_params(fused_vars, cin, features, needs_proj):
    """Fused param tree -> baseline BottleneckBlock param tree."""
    fp = fused_vars["params"]
    f = features
    params = {
        "Conv_0": {"kernel": fp["conv1_kernel"].reshape(1, 1, cin, f)},
        "BatchNorm_0": {"scale": fp["norm1_scale"],
                        "bias": fp["norm1_bias"]},
        "Conv_1": {"kernel": fp["conv2_kernel"]},
        "BatchNorm_1": {"scale": fp["norm2_scale"],
                        "bias": fp["norm2_bias"]},
        "Conv_2": {"kernel": fp["conv3_kernel"].reshape(1, 1, f, 4 * f)},
        "BatchNorm_2": {"scale": fp["norm3_scale"],
                        "bias": fp["norm3_bias"]},
    }
    stats = {
        "BatchNorm_0": {"mean": jnp.zeros((f,)), "var": jnp.ones((f,))},
        "BatchNorm_1": {"mean": jnp.zeros((f,)), "var": jnp.ones((f,))},
        "BatchNorm_2": {"mean": jnp.zeros((4 * f,)),
                        "var": jnp.ones((4 * f,))},
    }
    if needs_proj:
        params["conv_proj"] = {
            "kernel": fp["proj_kernel"].reshape(1, 1, cin, 4 * f)}
        params["norm_proj"] = {"scale": fp["norm_proj_scale"],
                               "bias": fp["norm_proj_bias"]}
        stats["norm_proj"] = {"mean": jnp.zeros((4 * f,)),
                              "var": jnp.ones((4 * f,))}
    return {"params": params, "batch_stats": stats}


@pytest.mark.parametrize("strides,cin,pallas3", [
    ((1, 1), 64, False), ((2, 2), 32, False), ((1, 1), 64, True),
    ((2, 2), 32, True),  # stride-2: pallas3 falls back to the XLA conv
])
def test_fused_block_matches_baseline_f32(strides, cin, pallas3):
    # f32 end-to-end so the only differences are reduction order —
    # forward, grads, and running-stat updates must all line up.
    f = 16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 8, 8, cin)), jnp.float32)

    fused = FusedBottleneckBlock(f, strides=strides, dtype=jnp.float32,
                                 pallas_conv3=pallas3)
    fvars = fused.init(jax.random.PRNGKey(0), x, train=True)
    base = _baseline_block(f, strides, jnp.float32)
    needs_proj = strides != (1, 1) or cin != 4 * f
    bvars = _map_params(fvars, cin, f, needs_proj)

    yf, fmut = fused.apply(fvars, x, train=True,
                           mutable=["batch_stats"])
    yb, bmut = base.apply(bvars, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yb),
                               rtol=1e-4, atol=1e-4)

    # running stats took the same update
    bstats = bmut["batch_stats"]
    fstats = fmut["batch_stats"]
    np.testing.assert_allclose(np.asarray(fstats["norm1_mean"]),
                               np.asarray(bstats["BatchNorm_0"]["mean"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fstats["norm2_var"]),
                               np.asarray(bstats["BatchNorm_1"]["var"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fstats["norm3_mean"]),
                               np.asarray(bstats["BatchNorm_2"]["mean"]),
                               rtol=1e-4, atol=1e-5)

    # gradients: same scalar loss through both stacks, compared on the
    # shared parameter layout (gamma3 is zero-init, so include stats
    # cotangents implicitly via the running mean of the block output)
    def loss_fused(p):
        y, _ = fused.apply({"params": p,
                            "batch_stats": fvars["batch_stats"]},
                           x, train=True, mutable=["batch_stats"])
        return (y * y).mean()

    def loss_base(p):
        y, _ = base.apply({"params": p,
                           "batch_stats": bvars["batch_stats"]},
                          x, mutable=["batch_stats"])
        return (y * y).mean()

    gf = jax.grad(loss_fused)(fvars["params"])
    gb = jax.grad(loss_base)(bvars["params"])
    np.testing.assert_allclose(
        np.asarray(gf["conv1_kernel"]),
        np.asarray(gb["Conv_0"]["kernel"]).reshape(cin, f),
        rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(gf["conv3_kernel"]),
        np.asarray(gb["Conv_2"]["kernel"]).reshape(f, 4 * f),
        rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(gf["conv2_kernel"]),
        np.asarray(gb["Conv_1"]["kernel"]),
        rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(gf["norm2_scale"]),
        np.asarray(gb["BatchNorm_1"]["scale"]),
        rtol=2e-3, atol=2e-4)
    if needs_proj:
        np.testing.assert_allclose(
            np.asarray(gf["proj_kernel"]),
            np.asarray(gb["conv_proj"]["kernel"]).reshape(cin, 4 * f),
            rtol=2e-3, atol=2e-4)


def test_fused_block_eval_uses_running_stats():
    f, cin = 16, 64
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, cin)), jnp.float32)
    fused = FusedBottleneckBlock(f, dtype=jnp.float32)
    fvars = fused.init(jax.random.PRNGKey(0), x, train=True)
    base = _baseline_block(f, (1, 1), jnp.float32)
    # eval-mode baseline reads running stats
    base = BottleneckBlock(
        f,
        conv=functools.partial(nn.Conv, use_bias=False, dtype=jnp.float32),
        norm=functools.partial(nn.BatchNorm, use_running_average=True,
                               momentum=0.9, epsilon=1e-5,
                               dtype=jnp.float32))
    bvars = _map_params(fvars, cin, f, needs_proj=False)
    ye = fused.apply(fvars, x, train=False)
    yb = base.apply(bvars, x)
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yb),
                               rtol=1e-4, atol=1e-4)


def test_fused_resnet50_trains_and_matches_shapes():
    # Full model in fused mode: one train step must run, produce the
    # same logits shape, and mutate every block's running stats.
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 32, 32, 3)), jnp.float32)
    model = ResNet50(num_classes=10, dtype=jnp.float32,
                     norm_variant="fused")
    v = model.init(jax.random.PRNGKey(0), x, train=True)
    logits, mut = model.apply(v, x, train=True, mutable=["batch_stats"])
    assert logits.shape == (8, 10)
    assert jnp.isfinite(logits).all()

    # grads flow end to end
    def loss(p):
        out, _ = model.apply({"params": p,
                              "batch_stats": v["batch_stats"]},
                             x, train=True, mutable=["batch_stats"])
        return out.std()

    g = jax.grad(loss)(v["params"])
    leaves = jax.tree_util.tree_leaves(g)
    assert leaves and all(jnp.isfinite(l).all() for l in leaves)
    # at least one fused block updated its stats away from init
    flat = jax.tree_util.tree_leaves(mut["batch_stats"])
    assert any(float(jnp.abs(l).max()) > 0 for l in flat)


def test_fused_resnet50_close_to_bn_variant():
    # Same parameters (mapped), same input -> logits must agree between
    # norm_variant="bn" and "fused" in f32.
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 32, 32, 3)), jnp.float32)
    fused_model = ResNet50(num_classes=10, dtype=jnp.float32,
                           norm_variant="fused")
    fv = fused_model.init(jax.random.PRNGKey(0), x, train=True)
    bn_model = ResNet50(num_classes=10, dtype=jnp.float32,
                        norm_variant="bn")
    bv = bn_model.init(jax.random.PRNGKey(0), x, train=True)

    # map fused params onto the bn tree block by block
    bparams = dict(bv["params"])
    bstats = dict(bv["batch_stats"])
    fparams = fv["params"]
    stage_sizes = (3, 4, 6, 3)
    filters = 64
    bn_names = [n for n in bparams if n.startswith("BottleneckBlock_")]
    fused_names = [n for n in fparams if n.startswith("FusedBottleneckBlock_")]
    assert len(bn_names) == len(fused_names) == sum(stage_sizes)
    # widths per block to reshape the 1x1 kernels
    cins, fs = [], []
    cin, i_ = 64, 0
    for si, count in enumerate(stage_sizes):
        f = filters * 2 ** si
        for j in range(count):
            cins.append(cin)
            fs.append(f)
            cin = 4 * f
    for idx in range(sum(stage_sizes)):
        fn, bn_ = f"FusedBottleneckBlock_{idx}", f"BottleneckBlock_{idx}"
        sub = _map_params({"params": fparams[fn]}, cins[idx], fs[idx],
                          needs_proj="proj_kernel" in fparams[fn])
        bparams[bn_] = sub["params"]
        bstats[bn_] = sub["batch_stats"]
    bparams["conv_init"] = fparams["conv_init"]
    bparams["bn_init"] = fparams["bn_init"]
    bparams["Dense_0"] = fparams["Dense_0"]
    bstats["bn_init"] = fv["batch_stats"]["bn_init"]

    yf, _ = fused_model.apply(fv, x, train=True, mutable=["batch_stats"])
    yb, _ = bn_model.apply({"params": bparams, "batch_stats": bstats},
                           x, train=True, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yb),
                               rtol=5e-3, atol=5e-3)


def test_fused3_resnet50_close_to_bn_variant():
    # The fully fused form (Pallas 3x3 with on-read norm1 + stats
    # epilogue for norm2) must match the bn variant the same way the
    # 1x1-only form does.
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 32, 32, 3)), jnp.float32)
    f3 = ResNet50(num_classes=10, dtype=jnp.float32,
                  norm_variant="fused3")
    v3 = f3.init(jax.random.PRNGKey(0), x, train=True)
    f1 = ResNet50(num_classes=10, dtype=jnp.float32, norm_variant="fused")
    # identical param trees by construction — reuse directly
    y3, _ = f3.apply(v3, x, train=True, mutable=["batch_stats"])
    y1, _ = f1.apply(v3, x, train=True, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y1),
                               rtol=2e-3, atol=2e-3)

    def loss3(p):
        out, _ = f3.apply({"params": p, "batch_stats": v3["batch_stats"]},
                          x, train=True, mutable=["batch_stats"])
        return out.std()

    g = jax.grad(loss3)(v3["params"])
    leaves = jax.tree_util.tree_leaves(g)
    assert leaves and all(jnp.isfinite(l).all() for l in leaves)
