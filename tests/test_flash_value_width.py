"""Flash attention whose values are narrower (or wider) than its keys (MLA:
keys of 192, values of 128), against ``dot_product_attention``; and at equal
widths the launches are the parent's, operation for operation."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import pytest

from pyspark_tf_gke_tpu.ops.attention import dot_product_attention

F = importlib.import_module("pyspark_tf_gke_tpu.ops.pallas.flash_attention")


def qkv(seed, s, d, dv, b=2, h=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d)), jax.random.normal(ks[1], (b, s, h, d)),
            jax.random.normal(ks[2], (b, s, h, dv)), jax.random.normal(ks[3], (b, s, h, dv)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d,dv,s,block", [(24, 16, 256, 128), (48, 32, 128, 128),
                                          (16, 32, 256, 128), (192, 128, 256, 256)])
def test_forward_and_gradients_with_a_value_width_of_their_own(causal, d, dv, s, block):
    q, k, v, w = qkv(1, s, d, dv)
    mask = None if causal else (jnp.arange(s)[None, :] < jnp.array([[s], [s - 37]]))
    flash = lambda q, k, v: F.flash_attention(q, k, v, kv_mask=mask, causal=causal,
                                              block_q=block, block_k=block, interpret=True)
    dense = lambda q, k, v: dot_product_attention(
        q, k, v, mask=None if mask is None else mask[:, None, None, :], causal=causal)
    out = flash(q, k, v)
    assert out.shape == v.shape
    assert float(jnp.max(jnp.abs(out - dense(q, k, v)))) < 2e-5
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        assert float(jnp.max(jnp.abs(g - r))) < 5e-5 * max(1.0, float(jnp.max(jnp.abs(r))))


def test_flash_attention_block_returns_the_values_width():
    q, k, v, _ = qkv(2, 128, 24, 16)
    out, lse = F.flash_attention_block(q, k, v, block_q=128, block_k=128, interpret=True)
    assert out.shape == v.shape and lse.shape == q.shape[:3]
    assert float(jnp.max(jnp.abs(out - dot_product_attention(q, k, v)))) < 2e-5


# sha256 of the jaxprs of ``_fwd_call`` + ``_bwd_call`` (causal, bf16, default
# blocks, matmul precision "highest" as tests/conftest.py sets it) at the
# PARENT commit (ea20401, PR 26), made by this test's own lines on an unpacked
# ``git archive`` of it: the gpt2-medium cell's shape, head_dim 128 at S 8192,
# and a looped D 64
PARENT_LAUNCHES = {(128, 1024, 64): "2066fd15aa2dff7f", (64, 8192, 128): "7442d9cd7641a25a",
                   (8, 2048, 64): "403093068c33ff1b"}


@pytest.mark.parametrize("shape", sorted(PARENT_LAUNCHES))
def test_at_equal_widths_the_launches_are_the_parents(shape):
    """Kernel bodies, schedule, block specs and compiler parameters: the jaxpr
    of the two launches is the parent's, so the gpt2-medium program is."""
    bh, s, d = shape
    x = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)
    blk = F._pick_seq_block(s, F.DEFAULT_BLOCK_Q)
    kw = dict(causal=True, block_q=blk, block_k=blk, interpret=False, caller="a")
    with jax.default_matmul_precision("highest"):
        fwd = jax.make_jaxpr(lambda q, k, v: F._fwd_call(q, k, v, None, None, **kw))(x, x, x)
        bwd = jax.make_jaxpr(lambda q, k, v, l, o, do: F._bwd_call(
            q, k, v, None, l, o, do, None, None, **kw))(x, x, x, lse, x, x)
    digest = hashlib.sha256((str(fwd) + str(bwd)).encode()).hexdigest()[:16]
    assert digest == PARENT_LAUNCHES[shape]


@pytest.mark.parametrize("s,d,dv,raised", [(1024, 64, 64, False), (8192, 128, 128, False),
                                           (8192, 64, 64, False), (8192, 192, 128, True)])
def test_the_vmem_limit_is_raised_only_past_the_default(s, d, dv, raised):
    params = F._vmem(s, d, dv, jnp.bfloat16)
    assert bool(params) is raised
    if raised:
        assert params["compiler_params"].vmem_limit_bytes > 16 * 2 ** 20
