"""ResNet-50 (v1.5) for the BASELINE.json config-4 workload
("ResNet-50 ImageNet data-parallel across v5e-8, ICI allreduce").

The reference has no ResNet — this model exists to satisfy the north-star
benchmark configs, so it is written TPU-first rather than for parity:
bfloat16 compute / float32 params and batch-norm statistics, NHWC layout
(XLA:TPU's native conv layout), stride-2 in the 3×3 bottleneck conv
(the "v1.5" placement used by standard reference implementations).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


def space_to_depth(x: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """(B, H, W, C) -> (B, H/block, W/block, C*block*block).

    Pure layout rearrangement (no FLOPs): each output "pixel" stacks a
    block x block patch of input pixels along channels. Used by the s2d
    stem so the first conv contracts over C*block^2 channels instead of
    3 — the stem's MXU contraction dim grows from KH*KW*3 toward the
    128-lane tile the systolic array actually loads, which is the
    standard TPU ResNet stem optimization (cf. MLPerf ResNet)."""
    b, h, w, c = x.shape
    if h % block or w % block:
        raise ValueError(
            f"space_to_depth needs H,W divisible by {block}, got {h}x{w}")
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, c * block * block)


class BottleneckBlock(nn.Module):
    features: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.features, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.features, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.features * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.features * 4, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


# Variance gain of relu on a unit gaussian: sqrt(2 / (1 - 1/pi)). Scaled
# weight standardization + this constant keep every NF conv's output at
# ~unit variance without reading activation statistics (Brock et al.,
# "Characterizing signal propagation to close the performance gap in
# unnormalized ResNets", and NFNets, arXiv:2102.06171).
_GAMMA_RELU = 1.7139588594436646


def _pin_to_batch_sharding(x: jnp.ndarray) -> jnp.ndarray:
    """Pin an NHWC activation to the data-parallel batch sharding (the
    sharding the batch arrives in). The forward is already there; what
    this buys is the BACKWARD — ``with_sharding_constraint`` transposes
    to itself, so the cotangents of the NF blocks' elementwise muls
    stay batch-sharded instead of inheriting the weight-grad reduce's
    channel sharding, which the dp x fsdp partitioner could only reach
    by involuntary full rematerialization (a replicate-then-reshard
    warning per block on the MULTICHIP trail). No-op off-mesh."""
    from jax.sharding import PartitionSpec as P

    from pyspark_tf_gke_tpu.parallel.mesh import DATA_AXES, ambient_mesh

    mesh = ambient_mesh()
    if mesh is None or mesh.shape.get("fsdp", 1) <= 1:
        return x
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(DATA_AXES, *([None] * (x.ndim - 1)))))


def _pin_to_param_sharding(w: jnp.ndarray) -> jnp.ndarray:
    """``with_sharding_constraint`` to the sharding ``fsdp_shardings``
    gives a param of this shape (the shape-based partitioner ResNets
    use), read from the ambient mesh context — a no-op off-mesh or
    without an fsdp axis, so single-chip and dp-only runs are
    untouched."""
    from pyspark_tf_gke_tpu.parallel.mesh import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None or mesh.shape.get("fsdp", 1) <= 1:
        return w
    from jax.sharding import NamedSharding

    from pyspark_tf_gke_tpu.parallel.sharding import fsdp_spec

    return jax.lax.with_sharding_constraint(
        w, NamedSharding(mesh, fsdp_spec(w.shape, mesh)))


class WSConv(nn.Module):
    """Scaled weight-standardized conv for the normalizer-free variant.

    The kernel is standardized per OUTPUT channel over its fan-in and
    scaled by ``1/sqrt(fan_in)`` so a unit-variance input yields a
    unit-variance output at init, with a learnable per-channel ``gain``
    on top. The whole standardization runs in weight space — cost is
    per-parameter, not per-activation, which is the entire point: the
    activation-norm HBM traffic between convs has no analog here.
    Convs stay XLA convs (the Pallas replacements of
    ``FusedBottleneckBlock`` measured slower), and XLA hoists nothing: the standardize recomputes each
    step in f32 over ~25M weights, noise next to the conv FLOPs.

    Carries a learnable per-channel bias (the ScaledStdConv recipe):
    standardization pins every kernel to zero output-channel mean and
    the NF path has no norm offsets, so without this bias nothing in
    the network could shift a pre-relu activation."""

    features: int
    kernel_size: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: Any = "SAME"
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        cin = x.shape[-1]
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (kh, kw, cin, self.features), jnp.float32)
        gain = self.param("gain", nn.initializers.ones_init(),
                          (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), jnp.float32)
        fan_in = kh * kw * cin
        mean = w.mean(axis=(0, 1, 2), keepdims=True)
        var = w.var(axis=(0, 1, 2), keepdims=True)
        w = (w - mean) * jax.lax.rsqrt(var * fan_in + 1e-4)
        w = w * gain[None, None, None, :]
        # Pin the standardized kernel (and with it the whole
        # standardization chain's backward) to the PARAM's fsdp
        # sharding: without the explicit constraint the dp x fsdp
        # partitioner propagates the batch sharding from the conv side
        # into the weight-standardization muls and then "involuntarily
        # fully rematerializes" (replicates) the tensor to reach the
        # param sharding the gradient needs — an spmd_partitioner
        # warning per block on the MULTICHIP trail. Function-of-params
        # stays sharded like the params; the conv's all-gather happens
        # once, on the finished kernel.
        w = _pin_to_param_sharding(w)
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype), w.astype(self.dtype), self.strides,
            self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + bias.astype(y.dtype)[None, None, None, :]


class NFBottleneckBlock(nn.Module):
    """Pre-activation normalizer-free bottleneck:
    ``h' = h + alpha * skip_gain * f(relu(h / beta) * gamma)``.

    ``beta = sqrt(E[Var(h)])`` is a COMPILE-TIME constant from the
    analytic variance recursion (var grows by ``alpha**2`` per block,
    resets at transitions) — so the only activation-space work this
    block adds over bare convs is the relu chain the BN model also has,
    with two scalar multiplies XLA folds into those same elementwise
    passes. No statistics reduction, no normalize read-modify-write.
    ``skip_gain`` is the NFNets zero-init scalar: blocks start as
    identity, which replaces BatchNorm's zero-init gamma on norm3 in
    the BN twin (BottleneckBlock above)."""

    features: int
    strides: Tuple[int, int] = (1, 1)
    alpha: float = 0.2
    beta: float = 1.0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        f = self.features
        conv = functools.partial(WSConv, dtype=self.dtype)
        y = _pin_to_batch_sharding(
            (nn.relu(x.astype(jnp.float32)) *
             (_GAMMA_RELU / self.beta)).astype(self.dtype))
        needs_proj = self.strides != (1, 1) or x.shape[-1] != 4 * f
        # transition blocks route the shortcut through the NORMALIZED
        # pre-activation (variance resets to ~1 downstream)
        shortcut = conv(4 * f, (1, 1), self.strides,
                        name="conv_proj")(y) if needs_proj else x
        z = conv(f, (1, 1), name="conv1")(y)
        z = _pin_to_batch_sharding(
            (nn.relu(z.astype(jnp.float32)) * _GAMMA_RELU).astype(self.dtype))
        z = conv(f, (3, 3), self.strides, name="conv2")(z)
        z = _pin_to_batch_sharding(
            (nn.relu(z.astype(jnp.float32)) * _GAMMA_RELU).astype(self.dtype))
        z = conv(4 * f, (1, 1), name="conv3")(z)
        skip_gain = self.param("skip_gain", nn.initializers.zeros_init(),
                               (), jnp.float32)
        out = (shortcut.astype(jnp.float32) +
               self.alpha * skip_gain * z.astype(jnp.float32))
        return out.astype(self.dtype)


class _Identity(nn.Module):
    """Norm stand-in for the ``norm_variant="none"`` diagnostic: accepts
    and ignores the kwargs the real norm factory receives."""

    @nn.compact
    def __call__(self, x):
        return x


class FusedBottleneckBlock(nn.Module):
    """Bottleneck block with the 1x1 convs as Pallas matmul kernels that
    absorb the surrounding BatchNorm passes (``norm_variant="fused"``).

    The round-4 MFU probe measured normalization at 8.2 ms = 29% of the
    ResNet-50 step — all unfused HBM read-modify-writes of activation
    tensors between convs. This block removes the
    removable passes:

    - conv1/conv3/proj write their raw output AND its per-channel
      sum/sumsq in one kernel pass (no separate statistics read);
    - conv3 reads conv2's RAW output and applies norm2's normalize+relu
      on tiles in VMEM (no materialized normalized tensor);
    - norm3+proj-norm+residual+relu remain one fused XLA elementwise
      pass (they already were — XLA fuses elementwise chains fine; only
      passes *adjacent to convs* needed kernel help).

    By default the 3x3 conv stays an XLA conv: its normalized input
    (norm1) is materialized, and its statistics cost one reduction
    read. ``pallas_conv3=True`` (``norm_variant="fused3"``) removes
    those too for stride-1 blocks via the fused 3x3 kernel
    (``ops/pallas/fused_conv3.py``).

    BatchNorm semantics match ``nn.BatchNorm(momentum=0.9, eps=1e-5)``:
    biased batch variance, running-average updates in train mode, the
    zero-init gamma on norm3. Statistics are batch-local to the device
    set visible to the kernel (single-chip bench path; a dp-sharded
    multi-chip wrapper needs a psum of the sum/sumsq vectors, which is
    exactly what the epilogue exposes them for).
    """

    features: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Any = jnp.bfloat16
    momentum: float = 0.9
    epsilon: float = 1e-5
    # Own the 3x3 conv too (ops/pallas/fused_conv3.py): norm1 never
    # materializes (applied on-read inside the conv) and norm2's stats
    # come from the conv's epilogue. Stride-2 blocks always use the XLA
    # conv (3 of 16 blocks; see fused_conv3's docstring).
    pallas_conv3: bool = False

    def _bn_params(self, name: str, dim: int, zero_scale: bool = False):
        scale = self.param(
            f"{name}_scale",
            nn.initializers.zeros_init() if zero_scale
            else nn.initializers.ones_init(), (dim,), jnp.float32)
        bias = self.param(f"{name}_bias", nn.initializers.zeros_init(),
                          (dim,), jnp.float32)
        ra_mean = self.variable("batch_stats", f"{name}_mean",
                                lambda: jnp.zeros((dim,), jnp.float32))
        ra_var = self.variable("batch_stats", f"{name}_var",
                               lambda: jnp.ones((dim,), jnp.float32))
        return scale, bias, ra_mean, ra_var

    def _update_ra(self, ra_mean, ra_var, mean, var):
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
            ra_var.value = m * ra_var.value + (1.0 - m) * var

    def _fold_stats(self, bn, train, stats=None, count=None, moments=None):
        """moments -> running-average update -> folded ``(a, b)``.

        SINGLE home for this tail across every conv+BN site (the 1x1
        helper below, the Pallas 3x3 branch, the XLA 3x3 branch): pass
        ``stats=(sum, sumsq)`` + ``count`` from a kernel epilogue, or
        ``moments=(mean, var)`` from an XLA reduction. A future
        dp-sharded wrapper psums the sum/sumsq vectors HERE, one place.
        Eval mode reads the running stats regardless."""
        from pyspark_tf_gke_tpu.ops.pallas.fused_matmul import (
            bn_fold, stats_to_moments)

        scale, bias, ra_mean, ra_var = bn
        if train:
            if stats is not None:
                mean, var = stats_to_moments(*stats, count)
            else:
                mean, var = moments
            self._update_ra(ra_mean, ra_var, mean, var)
        else:
            mean, var = ra_mean.value, ra_var.value
        return bn_fold(mean, var, scale, bias, self.epsilon)

    def _fused_conv_bn(self, x_flat, w, bn, train, a_in=None, b_in=None):
        """One fused 1x1-conv + BN-stat step: Pallas matmul (optional
        on-read normalize+relu via ``a_in``/``b_in``), then the shared
        ``_fold_stats`` tail. Returns ``(y_raw, a, b)``."""
        from pyspark_tf_gke_tpu.ops.pallas.fused_matmul import (
            norm_relu_matmul)

        dt = self.dtype
        if train:
            y, s, ss = norm_relu_matmul(x_flat, w.astype(dt), a_in, b_in,
                                        relu=a_in is not None,
                                        want_stats=True)
            a, b = self._fold_stats(bn, train, stats=(s, ss),
                                    count=y.shape[0])
        else:
            y = norm_relu_matmul(x_flat, w.astype(dt), a_in, b_in,
                                 relu=a_in is not None)
            a, b = self._fold_stats(bn, train)
        return y, a, b

    @nn.compact
    def __call__(self, x, train: bool = True):
        b_, h, w_, cin = x.shape
        f = self.features
        init = nn.initializers.lecun_normal()
        w1 = self.param("conv1_kernel", init, (cin, f), jnp.float32)
        w3 = self.param("conv3_kernel", init, (f, f * 4), jnp.float32)
        bn1 = self._bn_params("norm1", f)
        bn2 = self._bn_params("norm2", f)
        bn3 = self._bn_params("norm3", f * 4, zero_scale=True)
        needs_proj = (self.strides != (1, 1)) or (cin != f * 4)
        if needs_proj:
            wp = self.param("proj_kernel", init, (cin, f * 4), jnp.float32)
            bnp_ = self._bn_params("norm_proj", f * 4)

        dt = self.dtype
        x = x.astype(dt)
        x_flat = x.reshape(-1, cin)

        # conv1 (1x1): raw output + stats in one Pallas pass
        y1, a1, b1 = self._fused_conv_bn(x_flat, w1, bn1, train)

        w2 = self.param("conv2_kernel", init, (3, 3, f, f), jnp.float32)
        if self.pallas_conv3 and self.strides == (1, 1):
            # fully fused 3x3: reads RAW y1 (norm1 applied on tiles in
            # VMEM — nothing materializes) and emits norm2's stats from
            # the output-writing epilogue
            from pyspark_tf_gke_tpu.ops.pallas.fused_conv3 import (
                conv3_norm_stats)

            y1_4d = y1.reshape(b_, h, w_, f)
            if train:
                y2, s2, ss2 = conv3_norm_stats(
                    y1_4d, w2.astype(dt), a1, b1, relu=True,
                    want_stats=True)
                a2, b2 = self._fold_stats(
                    bn2, train, stats=(s2, ss2),
                    count=y2.shape[0] * y2.shape[1] * y2.shape[2])
            else:
                y2 = conv3_norm_stats(y1_4d, w2.astype(dt), a1, b1,
                                      relu=True)
                a2, b2 = self._fold_stats(bn2, train)
        else:
            # norm1+relu materializes for the XLA 3x3 conv (one fused
            # elementwise pass; the stats read was already saved above)
            n1 = jnp.maximum(
                y1.astype(jnp.float32) * a1[None, :] + b1[None, :], 0.0
            ).astype(dt).reshape(b_, h, w_, f)
            y2 = jax.lax.conv_general_dilated(
                n1, w2.astype(dt), self.strides, "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            # norm2 statistics: one XLA reduction read of y2 (both
            # moments in a single pass); the *normalize* is free —
            # conv3 applies it on-read below
            if train:
                y2f = y2.astype(jnp.float32)
                mean2 = y2f.mean(axis=(0, 1, 2))
                var2 = jnp.maximum((y2f * y2f).mean(axis=(0, 1, 2))
                                   - mean2 * mean2, 0.0)
                a2, b2 = self._fold_stats(bn2, train,
                                          moments=(mean2, var2))
            else:
                a2, b2 = self._fold_stats(bn2, train)
        h2, w2_ = y2.shape[1], y2.shape[2]

        # conv3 (1x1): normalize+relu on-read from RAW y2, stats epilogue
        y3, a3, b3 = self._fused_conv_bn(y2.reshape(-1, f), w3, bn3, train,
                                         a_in=a2, b_in=b2)

        # residual path
        if needs_proj:
            xs = x[:, ::self.strides[0], ::self.strides[1], :]
            yp, ap, bp = self._fused_conv_bn(xs.reshape(-1, cin), wp, bnp_,
                                             train)
            res = yp.astype(jnp.float32) * ap[None, :] + bp[None, :]
        else:
            res = x_flat.astype(jnp.float32)

        # norm3 + residual add + relu: one fused XLA elementwise pass
        out = jnp.maximum(
            y3.astype(jnp.float32) * a3[None, :] + b3[None, :] + res, 0.0)
        return out.astype(dt).reshape(b_, h2, w2_, f * 4)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Optional[Any] = jnp.bfloat16
    # s2d stem: rearrange the input 2x space-to-depth and replace the
    # 7x7/stride-2 conv (contraction dim 7*7*3 = 147, of which only 3
    # channels feed each MXU lane group) with an equivalent-receptive-
    # field 4x4/stride-1 conv over 12 channels (covers 8x8 input pixels
    # at stride 2, i.e. the 7x7 window padded by one). Same output
    # shape; ~31% more raw stem MACs (192 vs 147 per output element —
    # the stem is <1% of total model FLOPs) traded for a contraction
    # dim the MXU can actually fill. A variant, not a drop-in
    # weight-compatible swap.
    s2d_stem: bool = False
    # Normalization lever:
    # "bn" (default, bf16 normalize / f32 stats), "bn_f32" (the whole
    # norm in f32 — isolates bf16 round-trips around the stat
    # reductions), "gn" (GroupNorm-32: no batch reduction, fuses as
    # plain elementwise), "none" (identity — bounds the total norm cost;
    # diagnostic only, does not train well), "fused" (BN semantics with
    # the bottleneck 1x1 convs as Pallas kernels absorbing the norm
    # passes — see FusedBottleneckBlock), "nf" (normalizer-free: scaled
    # weight-standardized convs + analytic variance tracking, no
    # activation norms AT ALL: where fusing the normalize pass into
    # the convs did not pay, this deletes the pass). No cell of the
    # benchmark runs any of them; the training default stays "bn".
    norm_variant: str = "bn"

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.norm_variant == "nf":
            return self._nf_forward(x)
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        if self.norm_variant in ("bn", "fused", "fused3"):
            # "fused" uses BatchNorm semantics; the stem norm (one small
            # tensor, between a 7x7 conv and a maxpool) stays nn.BatchNorm
            # — only the bottleneck blocks swap to the Pallas path.
            norm = functools.partial(
                nn.BatchNorm, use_running_average=not train, momentum=0.9,
                epsilon=1e-5, dtype=self.dtype,
            )
        elif self.norm_variant == "bn_f32":
            norm = functools.partial(
                nn.BatchNorm, use_running_average=not train, momentum=0.9,
                epsilon=1e-5, dtype=jnp.float32,
            )
        elif self.norm_variant == "gn":
            norm = functools.partial(
                nn.GroupNorm, num_groups=32, epsilon=1e-5, dtype=self.dtype,
            )
        elif self.norm_variant == "none":
            def norm(**kw):  # swallow factory kwargs (scale_init, ...)
                return _Identity(name=kw.get("name"))
        else:
            raise ValueError(
                f"norm_variant must be bn|bn_f32|gn|none|fused|fused3|nf, "
                f"got {self.norm_variant!r}")
        x = x.astype(self.dtype) if self.dtype else x
        if self.s2d_stem:
            x = space_to_depth(x, 2)
            x = conv(self.num_filters, (4, 4), (1, 1), padding="SAME",
                     name="conv_init_s2d")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                if self.norm_variant in ("fused", "fused3"):
                    x = FusedBottleneckBlock(
                        self.num_filters * 2 ** i, strides=strides,
                        dtype=self.dtype or jnp.float32,
                        pallas_conv3=self.norm_variant == "fused3",
                    )(x, train=train)
                else:
                    x = BottleneckBlock(
                        self.num_filters * 2 ** i, conv=conv, norm=norm,
                        strides=strides,
                    )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)

    def _nf_forward(self, x):
        """Normalizer-free path (``norm_variant="nf"``): WS-conv stem,
        NF bottleneck stack with the analytic beta schedule, no
        train/eval mode split (no statistics exist to toggle)."""
        dt = self.dtype or jnp.float32
        x = x.astype(dt)
        if self.s2d_stem:
            x = space_to_depth(x, 2)
            x = WSConv(self.num_filters, (4, 4), (1, 1), "SAME", dtype=dt,
                       name="conv_init_s2d")(x)
        else:
            x = WSConv(self.num_filters, (7, 7), (2, 2),
                       [(3, 3), (3, 3)], dtype=dt, name="conv_init")(x)
        x = (nn.relu(x.astype(jnp.float32)) * _GAMMA_RELU).astype(dt)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        alpha = 0.2
        expected_var = 1.0
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = NFBottleneckBlock(
                    self.num_filters * 2 ** i, strides=strides, alpha=alpha,
                    beta=float(expected_var) ** 0.5, dtype=dt)(x)
                if j == 0:
                    # transition (width x4 and/or stride): the shortcut
                    # consumed the normalized pre-activation
                    expected_var = 1.0
                expected_var += alpha * alpha
        x = nn.relu(x.astype(jnp.float32)).astype(dt)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=dt)(x)
        return x.astype(jnp.float32)


ResNet50 = functools.partial(ResNet, stage_sizes=(3, 4, 6, 3))
