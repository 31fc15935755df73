"""The new kernels compiled for a described TPU v5e at the widths the
Kimi-Linear, Nemotron-H and Trinity-Mini cells run them at: what interpret mode cannot show (tiling, VMEM,
what Mosaic lowers). Nothing runs; no chip is needed. One file, so that one
xdist worker loads the TPU's library."""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("taps", [4, 0], ids=["projections_and_taps", "already_mixed"])
@pytest.mark.parametrize("mxu", ["bfloat16", "float32"])
def test_kda_kernels_compile_at_the_cells_widths(one_chip, no_compile_cache, mxu, taps):
    from pyspark_tf_gke_tpu.ops.pallas import kda as K

    b, s, h, d = 1, 1024, 4, 128
    mxu = jnp.dtype(mxu)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    x, g = shape((b, s, h * d), jnp.bfloat16), shape((b, s, h * d), jnp.float32)
    beta = shape((b, s, h), jnp.float32)
    # the short convolution's taps, or none: q, k, v come convolved
    conv = (shape((taps, h * d), jnp.float32),) * 3 if taps else None
    kw = dict(heads=h, eps=1e-5, mxu=mxu, interpret=False, caller="")
    lowered = jax.jit(lambda *a: K._forward(*a, **kw)).lower(x, x, x, g, beta, conv)
    nb = s // K.block_rows(s)
    kept = shape((b, h, nb, d, d), jnp.float32)
    assert [(o.shape, o.dtype) for o in lowered.out_info] == [
        (x.shape, x.dtype), (kept.shape, kept.dtype)]
    assert lowered.compile().as_text().count("tpu_custom_call") >= 1
    lowered = jax.jit(lambda *a: K._backward(*a, **kw)).lower(x, x, x, g, beta, conv, kept, x)
    # dq dk dv as q k v, dg float32, dbeta a lane-dense row a head a block, and
    # the taps' gradients a row and a head, summed outside
    assert [(o.shape, o.dtype) for o in jax.tree.leaves(lowered.out_info)] == [
        (x.shape, x.dtype)] * 3 + [(g.shape, g.dtype),
                                   ((b, h, nb, 1, s // nb), jnp.dtype("float32"))] + [
        ((b, h, taps, d), jnp.dtype("float32"))] * (3 if taps else 0)
    assert lowered.compile().as_text().count("tpu_custom_call") >= 1


@pytest.mark.parametrize("mxu", ["bfloat16", "float32"])
def test_ssd_kernels_compile_at_the_cells_widths(one_chip, no_compile_cache, mxu):
    """64 heads of 64 in 8 groups, a state of 128, chunks of 128: a group's
    block is four 128-lane slabs of two heads, ``dt`` a 64-lane block."""
    from pyspark_tf_gke_tpu.ops.pallas import ssd as K

    b, s, h, g, p, n = 1, 1024, 64, 8, 64, 128
    dtype = jnp.dtype(mxu)
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    x, keys = shape((b, s, h * p), dtype), shape((b, s, g * n), dtype)
    dt, per_head = shape((b, s, h), jnp.float32), shape((h,), jnp.float32)
    kw = dict(heads=h, groups=g, chunk=128, mxu=dtype, interpret=False, caller="")
    lowered = jax.jit(lambda *a: K._forward(*a, **kw)).lower(x, dt, per_head, keys, keys, per_head)
    kept = shape((b, g, s // 256, 4, 128, n), jnp.float32)
    assert [(o.shape, o.dtype) for o in lowered.out_info] == [
        (x.shape, x.dtype), (kept.shape, kept.dtype)]
    assert lowered.compile().as_text().count("tpu_custom_call") >= 1
    lowered = jax.jit(lambda *a: K._backward(*a, **kw)).lower(
        x, dt, per_head, keys, keys, per_head, kept, x)
    # dx as x, ddt a group's own [S, H] (zero off its columns), da and dd a
    # row and a group, all summed outside; db and dc as b and c
    sums = ((b, g, 1, h), jnp.dtype("float32"))
    assert [(o.shape, o.dtype) for o in lowered.out_info] == [
        (x.shape, x.dtype), ((b, g, s, h), jnp.dtype("float32")), sums,
        (keys.shape, keys.dtype), (keys.shape, keys.dtype), sums]
    assert lowered.compile().as_text().count("tpu_custom_call") >= 1


def test_flash_kernels_compile_with_keys_of_192_and_values_of_128(one_chip, no_compile_cache):
    """MLA at S 8192: k and q are lane-padded to 256 and held whole, past the
    16 MiB a kernel may take by default; ``_vmem`` raises the limit."""
    F = importlib.import_module("pyspark_tf_gke_tpu.ops.pallas.flash_attention")
    bh, s, d, dv = 4, 8192, 192, 128
    q = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((bh, s, dv), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, 1, s), jnp.float32, sharding=one_chip)
    blk = F._pick_seq_block(s, F.DEFAULT_BLOCK_Q)
    kw = dict(causal=True, block_q=blk, block_k=blk, interpret=False, caller="a")
    # as the program runs them: tests/conftest.py's "highest" is for the CPU
    # comparisons, and Mosaic refuses bf16 operands at it
    with jax.default_matmul_precision("default"):
        jax.jit(lambda q, k, v: F._fwd_call(q, k, v, None, None, **kw)).lower(q, q, v).compile()
        jax.jit(lambda q, k, v, l, o, do: F._bwd_call(
            q, k, v, None, l, o, do, None, None, **kw)).lower(q, q, v, lse, v, v).compile()


@pytest.mark.parametrize("window", [2048, 1000], ids=["mirrored", "banded"])
def test_flash_kernels_compile_inside_a_window(one_chip, no_compile_cache, window):
    """A window layer of the Trinity-Mini cell: heads of 128 at S 8192, four
    512-row blocks to the window (the edge strips behind one ``lax.cond`` a
    grid step, which interpret mode does not lower); and a window that is no
    multiple of the block (every tile through a mask of positions)."""
    F = importlib.import_module("pyspark_tf_gke_tpu.ops.pallas.flash_attention")
    bh, s, d = 4, 8192, 128
    q = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, 1, s), jnp.float32, sharding=one_chip)
    blk = F._pick_seq_block(s, F.DEFAULT_BLOCK_Q)
    kw = dict(causal=True, block_q=blk, block_k=blk, interpret=False, caller="a", window=window)
    assert F._schedule(s, blk, blk, True, window=window).mirrored == (window == 2048)
    with jax.default_matmul_precision("default"):
        fwd = jax.jit(lambda q, k, v: F._fwd_call(q, k, v, None, None, **kw)).lower(q, q, q)
        assert "window_flash_fwd" in fwd.compile().as_text()
        bwd = jax.jit(lambda q, k, v, l, o, do: F._bwd_call(
            q, k, v, None, l, o, do, None, None, **kw)).lower(q, q, q, lse, q, q)
        text = bwd.compile().as_text()
        assert "window_flash_dq" in text and "window_flash_dkv" in text
