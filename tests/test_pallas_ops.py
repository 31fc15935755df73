"""Pallas kernels run in interpret mode on the CPU fake slice; numerics are
checked against the dense implementations in ops.attention / flax LN."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pyspark_tf_gke_tpu.ops.attention import dot_product_attention
from pyspark_tf_gke_tpu.ops.pallas.flash_attention import flash_attention
from pyspark_tf_gke_tpu.ops.pallas.layernorm import fused_layernorm


def _qkv(b=2, s=64, h=2, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype=jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_with_padding_mask():
    q, k, v = _qkv(b=2, s=64)
    mask = np.ones((2, 64), dtype=bool)
    mask[:, 48:] = False
    out = flash_attention(q, k, v, kv_mask=jnp.asarray(mask), block_q=32,
                          block_k=32, interpret=True)
    ref = dot_product_attention(q, k, v, mask=jnp.asarray(mask)[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_fully_masked_rows_zero():
    q, k, v = _qkv(b=1, s=32)
    mask = np.zeros((1, 32), dtype=bool)
    out = flash_attention(q, k, v, kv_mask=jnp.asarray(mask), block_q=32,
                          block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0)


def test_flash_grad_matches_dense():
    q, k, v = _qkv(b=1, s=32, h=1, d=8)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16,
                                interpret=True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_bad_block_size():
    q, k, v = _qkv(b=1, s=48)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)


def test_fused_layernorm_matches_flax():
    x = jax.random.normal(jax.random.key(0), (6, 10, 32)) * 3 + 1
    scale = jax.random.normal(jax.random.key(1), (32,))
    bias = jax.random.normal(jax.random.key(2), (32,))
    out = fused_layernorm(x, scale, bias, eps=1e-6, interpret=True)
    ln = nn.LayerNorm(epsilon=1e-6)
    ref = ln.apply({"params": {"scale": scale, "bias": bias}}, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fused_layernorm_grad():
    x = jax.random.normal(jax.random.key(0), (8, 16))
    scale = jnp.ones((16,))
    bias = jnp.zeros((16,))

    def loss_fused(x, s, b):
        return (fused_layernorm(x, s, b, interpret=True) ** 2).sum()

    def loss_ref(x, s, b):
        ln = nn.LayerNorm(epsilon=1e-6)
        return (ln.apply({"params": {"scale": s, "bias": b}}, x) ** 2).sum()

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_fused_layernorm_odd_rows():
    # 7 rows: block search must fall back to a divisor (7)
    x = jax.random.normal(jax.random.key(0), (7, 24))
    out = fused_layernorm(x, jnp.ones((24,)), jnp.zeros((24,)), interpret=True)
    assert out.shape == (7, 24)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_dense_multiblock(causal):
    """Backward kernels across multiple Q/K blocks (+ causal block skip)."""
    q, k, v = _qkv(b=2, s=64, h=2, d=16, seed=3)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                                interpret=True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_flash_grad_with_padding_mask():
    q, k, v = _qkv(b=2, s=32, h=1, d=8, seed=4)
    mask = np.ones((2, 32), dtype=bool)
    mask[:, 20:] = False
    jmask = jnp.asarray(mask)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, kv_mask=jmask, block_q=16, block_k=16,
                                interpret=True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v, mask=jmask[:, None, None, :]) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_bert_trains_with_flash_attention(devices):
    """Full model path through the Pallas forward AND backward kernels
    (interpret mode on CPU): loss must descend."""
    import jax.numpy as jnp
    from pyspark_tf_gke_tpu.data.pipeline import put_global_batch
    from pyspark_tf_gke_tpu.models import BertConfig, BertForPretraining
    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding, make_mesh
    from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    mesh = make_mesh({"dp": 1}, jax.devices()[:1])
    cfg = BertConfig(vocab_size=96, hidden_size=32, num_layers=2, num_heads=2,
                     intermediate_size=64, max_position_embeddings=64,
                     dtype=jnp.float32, use_flash=True)
    model = BertForPretraining(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(0, 96, (4, 32)).astype(np.int32),
        "attention_mask": np.ones((4, 32), dtype=np.int32),
        "labels": rng.integers(0, 2, (4,)).astype(np.int32),
    }
    trainer = Trainer(model, TASKS["bert_classification"](), mesh,
                      learning_rate=1e-2)
    state = trainer.init_state(make_rng(0), batch)
    gb = put_global_batch(batch, batch_sharding(mesh))
    losses = []
    for _ in range(4):
        state, metrics = trainer.step(state, gb)
        losses.append(float(jax.device_get(metrics["loss"])))
    assert all(np.isfinite(l) for l in losses) and losses[-1] < losses[0]


def test_bert_flash_and_fused_ln_on_dp_mesh(devices):
    """The shard_map-wrapped Pallas paths (flash attention + fused LN)
    on a sharded dp×tp mesh: the partitioner can't split an opaque
    custom call, so models/bert.py must wrap it per-shard. Output must
    match the dense/unfused model run on the same mesh."""
    import jax.numpy as jnp
    from pyspark_tf_gke_tpu.data.pipeline import put_global_batch
    from pyspark_tf_gke_tpu.models import BertConfig, BertForPretraining
    from pyspark_tf_gke_tpu.parallel.mesh import batch_sharding, make_mesh
    from pyspark_tf_gke_tpu.train.trainer import TASKS, Trainer
    from pyspark_tf_gke_tpu.utils.seeding import make_rng

    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    base = dict(vocab_size=96, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_position_embeddings=64,
                dtype=jnp.float32)
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(0, 96, (8, 32)).astype(np.int32),
        "attention_mask": np.ones((8, 32), dtype=np.int32),
        "labels": rng.integers(0, 2, (8,)).astype(np.int32),
    }
    batch["attention_mask"][:, 28:] = 0

    outs = {}
    for name, flags in (
        ("pallas", dict(use_flash=True, use_fused_ln=True)),
        ("dense", dict(use_flash=False, use_fused_ln=False)),
    ):
        cfg = BertConfig(**base, **flags)
        model = BertForPretraining(cfg, mesh=mesh)
        trainer = Trainer(model, TASKS["bert_classification"](), mesh,
                          learning_rate=1e-2)
        state = trainer.init_state(make_rng(0), batch)
        gb = put_global_batch(batch, batch_sharding(mesh))
        losses = []
        for _ in range(3):
            state, metrics = trainer.step(state, gb)
            losses.append(float(jax.device_get(metrics["loss"])))
        outs[name] = losses
    np.testing.assert_allclose(outs["pallas"], outs["dense"], rtol=2e-3)


def test_flash_segment_ids_match_dense():
    """Packed-sequence masking: segment_ids confine attention within
    matching ids, composed with a padding mask, fwd and bwd."""
    q, k, v = _qkv(b=2, s=64, h=2, d=16, seed=5)
    seg = np.zeros((2, 64), np.int32)
    seg[:, 20:40] = 1
    seg[:, 40:] = 2
    seg = jnp.asarray(seg)
    mask = np.ones((2, 64), bool)
    mask[:, 60:] = False
    mask = jnp.asarray(mask)
    dense_mask = (seg[:, None, :, None] == seg[:, None, None, :]) & mask[:, None, None, :]

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, kv_mask=mask, segment_ids=seg,
                                block_q=16, block_k=16, interpret=True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v, mask=dense_mask) ** 2).sum()

    out = flash_attention(q, k, v, kv_mask=mask, segment_ids=seg,
                          block_q=16, block_k=16, interpret=True)
    ref = dot_product_attention(q, k, v, mask=dense_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_flash_attention_block_lse_merge():
    """flash_attention_block + logsumexp merging must reconstruct full
    attention from two disjoint K/V halves — the ring-attention
    contract, including the lse cotangent path."""
    from pyspark_tf_gke_tpu.ops.attention import _merge_partial
    from pyspark_tf_gke_tpu.ops.pallas.flash_attention import (
        flash_attention_block,
    )

    q, k, v = _qkv(b=2, s=32, h=2, d=16, seed=6)
    k1, k2 = k[:, :16], k[:, 16:]
    v1, v2 = v[:, :16], v[:, 16:]
    mask = np.ones((2, 32), bool)
    mask[:, 28:] = False
    m1, m2 = jnp.asarray(mask[:, :16]), jnp.asarray(mask[:, 16:])

    def merged(q, k1, v1, k2, v2):
        o1, l1 = flash_attention_block(q[:, :16], k1, v1, kv_mask=m1,
                                       block_q=16, block_k=16, interpret=True)
        o2, l2 = flash_attention_block(q[:, :16], k2, v2, kv_mask=m2,
                                       block_q=16, block_k=16, interpret=True)
        o = jnp.zeros_like(o1, dtype=jnp.float32)
        lse = jnp.full(o1.shape[:-1], -1e30, dtype=jnp.float32)
        o, lse = _merge_partial(o, lse, o1, l1)
        o, lse = _merge_partial(o, lse, o2, l2)
        return o.astype(q.dtype)

    out = merged(q, k1, v1, k2, v2)
    ref = dot_product_attention(q[:, :16], k, v,
                                mask=jnp.asarray(mask)[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    g1 = jax.grad(lambda *a: (merged(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4))(
        q, k1, v1, k2, v2)
    gref = jax.grad(lambda q, k, v: (dot_product_attention(
        q[:, :16], k, v, mask=jnp.asarray(mask)[:, None, None, :]) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(gref[0]), atol=1e-3)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([g1[1], g1[3]], axis=1)),
                               np.asarray(gref[1]), atol=1e-3)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([g1[2], g1[4]], axis=1)),
                               np.asarray(gref[2]), atol=1e-3)


def test_flash_causal_with_segment_ids_matches_dense():
    """The doc-masking production config: causal AND segment_ids
    composed in the kernel (fwd + bwd) must match dense attention with
    the combined block-diagonal causal mask."""
    q, k, v = _qkv(b=2, s=64, h=2, d=16, seed=9)
    seg = np.zeros((2, 64), np.int32)
    seg[:, 24:48] = 1
    seg[:, 48:] = 2
    seg = jnp.asarray(seg)
    dense_mask = (seg[:, None, :, None] == seg[:, None, None, :])

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, segment_ids=seg,
                                block_q=16, block_k=16,
                                interpret=True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v, mask=dense_mask,
                                      causal=True) ** 2).sum()

    out_f = flash_attention(q, k, v, causal=True, segment_ids=seg,
                            block_q=16, block_k=16, interpret=True)
    out_d = dot_product_attention(q, k, v, mask=dense_mask, causal=True)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               atol=2e-2, rtol=2e-2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-2, rtol=5e-2)


# -- PR 26: the causal schedule, no bias operand without a kv_mask -----------

def _flash_module():
    import importlib

    return importlib.import_module(
        "pyspark_tf_gke_tpu.ops.pallas.flash_attention")


def _coverage(sched):
    """[grid position, walk position] counts of what a kernel that follows
    ``sched`` computes, and of what it passes through the mask, by brute
    force over every step and strip of every grid block."""
    s = sched.s
    computed = np.zeros((s, s), np.int32)
    masked = np.zeros((s, s), np.int32)
    for i in range(s // sched.block):
        rows = slice(i * sched.block, (i + 1) * sched.block)
        lo, hi = sched.full_steps(i)
        for j in range(lo, hi):
            computed[rows, j * sched.walk:(j + 1) * sched.walk] += 1
        corner = i * sched.block
        for (first, size), (start, width) in zip(sched.sub_blocks(),
                                                 sched.strips()):
            g = slice(corner + first, corner + first + size)
            computed[g, corner + start:corner + start + width] += 1
            d0 = corner + start + (0 if sched.walks_rows else width - size)
            masked[g, d0:d0 + size] += 1
    return computed, masked


@pytest.mark.parametrize("walks_rows", [False, True])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,block_q,block_k", [
    (128, 128, 128), (384, 128, 128), (384, 384, 384), (640, 128, 128),
    (640, 640, 640), (1024, 512, 512), (1024, 256, 512), (1024, 512, 128),
    (2048, 512, 512), (2048, 256, 256), (2048, 1024, 1024),
    (1024, 1024, 1024), (64, 32, 32), (64, 16, 32)])
def test_flash_schedule_covers_the_triangle_once(s, block_q, block_k, causal,
                                                 walks_rows):
    fa = _flash_module()
    sched = fa._schedule(s, block_q, block_k, causal, walks_rows)
    computed, masked = _coverage(sched)
    grid_pos, walk_pos = np.mgrid[:s, :s]
    row, key = (walk_pos, grid_pos) if walks_rows else (grid_pos, walk_pos)
    wanted = (row >= key) if causal else np.ones((s, s), bool)
    # every wanted pair exactly once, nothing twice
    assert computed.max() == 1
    assert (computed[wanted] == 1).all()
    # what is computed and not wanted lies in a masked (diagonal) tile
    assert (masked[(computed == 1) & ~wanted] == 1).all()
    assert ((masked == 1) <= (computed == 1)).all()
    if causal:
        # a diagonal tile is square, on the diagonal, and holds wanted pairs:
        # nothing is computed beyond the edge of the tile the diagonal crosses
        t = sched.tile
        assert (np.abs(row - key)[masked == 1] < t).all()
        assert (row // t == key // t)[masked == 1].all()
    else:
        assert masked.sum() == 0 and sched.strips() == []
    n_computed, n_masked, n_wasted = sched.counts()
    assert n_computed == computed.sum()
    assert n_masked == masked.sum()
    assert n_wasted == ((computed == 1) & ~wanted).sum()


def test_flash_schedule_counts_at_the_benchmark_cell():
    fa = _flash_module()
    block = fa._pick_seq_block(1024, fa.DEFAULT_BLOCK_Q)
    assert block == fa._pick_seq_block(1024, fa.DEFAULT_BLOCK_K) == 1024
    # longer sequences keep the 512-row grid block
    assert fa._pick_seq_block(2048, fa.DEFAULT_BLOCK_Q) == 512
    assert fa._pick_seq_block(1152, fa.DEFAULT_BLOCK_K) == 384
    for walks_rows in (False, True):
        sched = fa._schedule(1024, block, block, True, walks_rows)
        # one block a head: no full step, eight strips, all of it static
        assert (sched.tile, sched.full_steps(0)) == (128, (0, 0))
        assert len(sched.strips()) == 8
        assert sched.counts() == (589_824, 131_072, 65_024)
        # the same triangle from 512-row grid blocks and a 512-wide walk
        sched = fa._schedule(1024, 512, 512, True, walks_rows)
        assert (sched.walk, sched.tile) == (512, 128)
        assert sched.counts() == (589_824, 131_072, 65_024)
    # the tests' narrow blocks and a sequence taken whole: one masked tile
    assert fa._schedule(64, 32, 32, True).tile == 32
    assert fa._schedule(200, 200, 200, True).counts() == (200 * 200, 200 * 200,
                                                          200 * 199 // 2)
    # not causal: the walk is capped, every step is full
    sched = fa._schedule(2048, 1024, 1024, False)
    assert (sched.walk, sched.full_steps(1), sched.strips()) == (512, (0, 4), [])


def _real_tiling_inputs(d=64, dtype=jnp.bfloat16, seed=3, s=1024):
    ks = jax.random.split(jax.random.key(seed), 4)
    return tuple(jax.random.normal(k, (1, s, 2, d), jnp.float32).astype(dtype)
                 for k in ks)


def _out_and_grads(attend, q, k, v, g):
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + vjp(g)


_REAL_TILING_CASES = {
    "causal": dict(causal=True),
    "causal+segments": dict(causal=True, segments=True),
    "causal+kv_mask": dict(causal=True, masked=True),
    "full": dict(causal=False),
    # 512-row grid blocks against a 256-wide walk: the dynamic loop over full
    # steps, then the strips on its carry
    "causal,blocks=512x256": dict(causal=True, block_q=512, block_k=256),
    "causal+segments+kv_mask,blocks=256x512": dict(
        causal=True, segments=True, masked=True, block_q=256, block_k=512),
    # 16 full steps of 128 keys: more than are unrolled, a loop of static bounds
    "full+kv_mask,S=2048,blocks=256x128": dict(
        causal=False, masked=True, s=2048, block_q=256, block_k=128),
}


@pytest.mark.parametrize("case", list(_REAL_TILING_CASES))
def test_flash_real_tiling_matches_dense(case):
    """The benchmark cell's tiling (S 1024, head_dim 64, bf16, default
    blocks: one grid block a head, tile 128) and narrower grid blocks:
    forward and all three gradients."""
    opts = _REAL_TILING_CASES[case]
    q, k, v, g = _real_tiling_inputs(s=opts.get("s", 1024))
    s = q.shape[1]
    kv_mask = seg = None
    mask = None
    if opts.get("masked"):
        kv_mask = jnp.asarray(np.arange(s) < s - 124)[None, :]
        mask = kv_mask[:, None, None, :]
    if opts.get("segments"):
        seg = jnp.asarray(np.searchsorted([300, 512, 777], np.arange(s),
                                          side="right"), jnp.int32)[None, :]
        same = seg[:, None, :, None] == seg[:, None, None, :]
        mask = same if mask is None else same & mask
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, kv_mask=kv_mask,
                                        causal=opts["causal"], segment_ids=seg,
                                        block_q=opts.get("block_q"),
                                        block_k=opts.get("block_k"),
                                        interpret=True), q, k, v, g)
    want = _out_and_grads(
        lambda q, k, v: dot_product_attention(q, k, v, mask=mask,
                                              causal=opts["causal"]), q, k, v, g)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-2, rtol=2e-2, err_msg=name)


@pytest.mark.parametrize("d", [64, 96])
def test_flash_head_dim_matches_dense(d):
    """A scale that is a power of two (head_dim 64) and one that is not."""
    q, k, v, g = _real_tiling_inputs(d=d, dtype=jnp.float32)
    q, k, v, g = (x[:, :256] for x in (q, k, v, g))
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True),
        q, k, v, g)
    want = _out_and_grads(
        lambda q, k, v: dot_product_attention(q, k, v, causal=True), q, k, v, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_no_kv_mask_equals_all_true_kv_mask(causal):
    q, k, v, g = _real_tiling_inputs()
    q, k, v, g = (x[:, :256] for x in (q, k, v, g))
    all_true = jnp.ones((1, 256), bool)
    without, with_mask = (
        _out_and_grads(
            lambda q, k, v: flash_attention(q, k, v, kv_mask=m, causal=causal,
                                            interpret=True), q, k, v, g)
        for m in (None, all_true))
    for a, b in zip(without, with_mask):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
