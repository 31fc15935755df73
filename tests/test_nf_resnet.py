"""Normalizer-free ResNet (``norm_variant="nf"``) — the variant that
deletes the activation-norm HBM pass instead of fusing it.

Context (an investigation on a v5e in 2026-07, before the benchmark):
normalization cost 29% of the ResNet-50 step, the cost is the
unfused normalize read-modify-write (not the stat reduction), and the
Pallas conv+BN fusions measured SLOWER than XLA's convs. The remaining
honest lever is weight-space normalization: scaled weight
standardization + analytic variance tracking (Brock et al.,
arXiv:2102.06171) — per-parameter cost, zero activation traffic.

These tests pin what makes the variant credible without hardware:
unit-variance signal propagation at init (the property the scheme is
built around), identity-at-init residuals (skip_gain zero-init), and a
small training fixture where NF must keep pace with the BN twin.
Reference counterpart: none — the reference has no ResNet; this model
exists for BASELINE.json config 4.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.models.resnet import (
    _GAMMA_RELU, NFBottleneckBlock, ResNet, WSConv)


def _rng(seed=0):
    return jax.random.PRNGKey(seed)


class TestWSConv:
    def test_unit_variance_propagation_at_init(self):
        # unit-gaussian input -> WS conv output variance ~1 per channel
        # (the invariant the whole NF scheme is built on)
        x = jax.random.normal(_rng(1), (4, 16, 16, 64), jnp.float32)
        conv = WSConv(128, (3, 3), dtype=jnp.float32)
        vs = conv.init(_rng(2), x)
        y = conv.apply(vs, x)
        assert y.shape == (4, 16, 16, 128)
        v = float(jnp.var(y))
        assert 0.5 < v < 2.0, f"WS conv output variance {v} not ~1"

    def test_standardization_invariant_to_kernel_shift_and_scale(self):
        # standardization must remove per-channel mean/scale of the raw
        # kernel: shifting+scaling the stored param leaves output
        # unchanged (up to fp noise)
        x = jax.random.normal(_rng(3), (2, 8, 8, 16), jnp.float32)
        conv = WSConv(32, (1, 1), dtype=jnp.float32)
        vs = conv.init(_rng(4), x)
        y0 = conv.apply(vs, x)
        w = vs["params"]["kernel"]
        vs2 = {"params": {**vs["params"], "kernel": w * 3.0 + 0.7}}
        y1 = conv.apply(vs2, x)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-3, atol=2e-3)

    def test_gain_scales_output(self):
        x = jax.random.normal(_rng(5), (2, 8, 8, 16), jnp.float32)
        conv = WSConv(32, (1, 1), dtype=jnp.float32)
        vs = conv.init(_rng(6), x)
        y0 = conv.apply(vs, x)
        vs2 = {"params": {**vs["params"],
                          "gain": vs["params"]["gain"] * 2.0}}
        y1 = conv.apply(vs2, x)
        # bias is zero at init, so doubling the gain doubles the output
        np.testing.assert_allclose(np.asarray(y1), 2.0 * np.asarray(y0),
                                   rtol=1e-5, atol=1e-5)

    def test_bias_param_exists_and_shifts_output(self):
        # the ScaledStdConv bias: WS pins kernels to zero channel mean,
        # so this is the ONLY activation-shift dof on the nf path
        x = jax.random.normal(_rng(20), (2, 8, 8, 16), jnp.float32)
        conv = WSConv(32, (1, 1), dtype=jnp.float32)
        vs = conv.init(_rng(21), x)
        assert vs["params"]["bias"].shape == (32,)
        vs2 = {"params": {**vs["params"],
                          "bias": vs["params"]["bias"] + 1.5}}
        y0, y1 = conv.apply(vs, x), conv.apply(vs2, x)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0) + 1.5,
                                   rtol=1e-5, atol=1e-5)


class TestNFBlock:
    def test_identity_at_init(self):
        # skip_gain zero-init: a non-transition block is exactly the
        # identity at init (the NF analog of BN's zero-init gamma)
        x = jax.random.normal(_rng(7), (2, 8, 8, 64), jnp.float32)
        blk = NFBottleneckBlock(16, dtype=jnp.float32)  # 4*16 == 64 -> no proj
        vs = blk.init(_rng(8), x)
        y = blk.apply(vs, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-6, atol=1e-6)

    def test_scaled_relu_restores_unit_variance(self):
        # gamma * relu(unit gaussian) has variance ~1 — the constant the
        # pre-activation uses
        x = jax.random.normal(_rng(9), (100_000,), jnp.float32)
        y = jnp.maximum(x, 0.0) * _GAMMA_RELU
        assert 0.93 < float(jnp.var(y)) < 1.07

    def test_transition_block_projects_shortcut(self):
        x = jax.random.normal(_rng(10), (2, 8, 8, 64), jnp.float32)
        blk = NFBottleneckBlock(32, strides=(2, 2), dtype=jnp.float32)
        vs = blk.init(_rng(11), x)
        y = blk.apply(vs, x)
        assert y.shape == (2, 4, 4, 128)
        assert "conv_proj" in vs["params"]

    def test_no_batch_stats_collection(self):
        x = jnp.ones((1, 8, 8, 64), jnp.float32)
        vs = NFBottleneckBlock(16, dtype=jnp.float32).init(_rng(12), x)
        assert set(vs.keys()) == {"params"}


class TestNFResNet:
    def _tiny(self, norm):
        return ResNet(stage_sizes=(1, 1), num_classes=4, num_filters=8,
                      dtype=jnp.float32, norm_variant=norm)

    def test_forward_shapes_and_finite(self):
        m = self._tiny("nf")
        x = jax.random.normal(_rng(13), (2, 32, 32, 3), jnp.float32)
        vs = m.init(_rng(14), x)
        y = m.apply(vs, x)
        assert y.shape == (2, 4)
        assert bool(jnp.all(jnp.isfinite(y)))
        assert "batch_stats" not in vs

    def test_signal_propagation_full_depth(self):
        # full ResNet-50 depth at init on a small image: pre-head
        # features must neither die nor explode across 16 blocks (the
        # failure mode of unnormalized resnets the beta schedule fixes)
        m = ResNet(stage_sizes=(3, 4, 6, 3), num_classes=10,
                   num_filters=8, dtype=jnp.float32, norm_variant="nf")
        x = jax.random.normal(_rng(15), (2, 64, 64, 3), jnp.float32)
        vs = m.init(_rng(16), x)
        y = m.apply(vs, x)
        assert bool(jnp.all(jnp.isfinite(y)))
        # logits at init stay O(1): Dense over GAP'd ~unit features
        assert float(jnp.abs(y).max()) < 50.0

    def test_trains_and_keeps_pace_with_bn(self):
        # 60 adam steps on a separable 4-class synthetic set: NF must
        # reach a loss comparable to the BN twin (same seed, same data)
        import optax

        rng = np.random.default_rng(0)
        n, hw = 64, 16
        labels = rng.integers(0, 4, (n,)).astype(np.int32)
        imgs = rng.normal(0, 0.3, (n, hw, hw, 3)).astype(np.float32)
        # class-dependent mean shift makes the task separable
        for k in range(4):
            imgs[labels == k] += 0.5 * np.sin(k + np.arange(3))

        def run(norm):
            m = ResNet(stage_sizes=(1, 1), num_classes=4, num_filters=8,
                       dtype=jnp.float32, norm_variant=norm)
            vs = m.init(_rng(17), imgs[:2])
            params = vs["params"]
            stats = vs.get("batch_stats")
            tx = optax.adam(3e-3)
            opt = tx.init(params)

            def loss_fn(p, s):
                variables = {"params": p}
                if s is not None:
                    variables["batch_stats"] = s
                    logits, new = m.apply(variables, imgs, train=True,
                                          mutable=["batch_stats"])
                    s = new["batch_stats"]
                else:
                    logits = m.apply(variables, imgs)
                one_hot = jax.nn.one_hot(labels, 4)
                l = optax.softmax_cross_entropy(logits, one_hot).mean()
                return l, s

            @jax.jit
            def step(p, s, o):
                (l, s2), g = jax.value_and_grad(loss_fn, has_aux=True)(p, s)
                u, o = tx.update(g, o, p)
                return optax.apply_updates(p, u), s2, o, l

            first = last = None
            for _ in range(60):
                params, stats, opt, l = step(params, stats, opt)
                if first is None:
                    first = float(l)
                last = float(l)
            return first, last

        nf_first, nf_last = run("nf")
        _, bn_last = run("bn")
        assert nf_last < 0.7 * nf_first, (
            f"nf did not train: {nf_first} -> {nf_last}")
        assert nf_last < max(2.0 * bn_last, 0.35), (
            f"nf lags bn too far: nf={nf_last}, bn={bn_last}")
