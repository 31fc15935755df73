"""``HybridLM``'s remat keeps what its mixer kernels' forward launches gave
(``ops/pallas/scope.py::KERNEL_RESULTS``): the gradient of a rematted model
launches each forward kernel once a layer, where a remat with no policy
launches it twice, and the gradients are the same either way. The kernels run
in the Pallas interpreter, as the tests of the decoders run them."""

import collections
import dataclasses
import functools
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from pyspark_tf_gke_tpu.models import hybrid_lm
from pyspark_tf_gke_tpu.models.hybrid_lm import HybridLM, config_from_file

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "benchmark", "tests", "data", "configs")
FAMILIES = ("kimi", "nemotron", "afmoe")
# the forward kernel each mixer kind launches; the windowed launch,
# ``window_flash_fwd``, is counted with flash's
KERNEL_OF_KIND = {"kda": "kda_fwd", "mamba2": "ssd_fwd", "mla": "flash_fwd",
                  "gqa": "flash_fwd", "gated_full": "flash_fwd", "gated_sliding": "flash_fwd"}


@pytest.fixture
def kernels(monkeypatch):
    """Every mixer through its Pallas kernels, in the interpreter."""
    monkeypatch.setattr(hybrid_lm, "kda", functools.partial(hybrid_lm.kda, interpret=True))
    monkeypatch.setattr(hybrid_lm, "ssd", functools.partial(hybrid_lm.ssd, interpret=True))
    return monkeypatch


def model_and_loss(family, rows=2):
    with open(os.path.join(DATA, f"tiny-{family}.json")) as f:
        cfg = config_from_file(json.load(f), dtype=jnp.float32, remat=True)
    model = HybridLM(dataclasses.replace(cfg, use_flash=True))
    ids = jax.random.randint(jax.random.PRNGKey(0), (rows, 128), 0, 256)

    def loss(params):
        logits = model.apply({"params": params}, ids, mutable=["counters"])[0]
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], -1))

    return cfg, model, ids, loss


def forward_launches(jaxpr) -> collections.Counter:
    """The ``pallas_call`` equations of ``jaxpr`` and every jaxpr inside it
    whose launch name holds ``fwd``, by the forward kernel they launch."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = str(eqn.source_info.name_stack).split("/")[-1]
            kernel = next((k for k in set(KERNEL_OF_KIND.values()) if name.endswith(k)), None)
            if "fwd" in name:
                found[kernel or name] += 1
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    found += forward_launches(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    found += forward_launches(sub)
    return found


@pytest.mark.parametrize("family", FAMILIES)
def test_a_rematted_block_launches_each_forward_kernel_once(kernels, family):
    cfg, model, ids, loss = model_and_loss(family)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    layers = collections.Counter(KERNEL_OF_KIND[kind] for kind in cfg.attention if kind != "none")
    assert forward_launches(jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == layers
    # a policy that keeps nothing is remat as it was without one: the
    # backward's rerun of each block launches its forward kernel again
    kernels.setattr(hybrid_lm, "KERNEL_RESULT_NAMES", ())
    twice = collections.Counter({kernel: 2 * n for kernel, n in layers.items()})
    assert forward_launches(jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == twice


@pytest.mark.parametrize("family", FAMILIES)
def test_the_kept_results_give_the_gradients_a_full_rerun_gives(kernels, family):
    _, model, ids, loss = model_and_loss(family, rows=1)
    params = nn.unbox(model.init(jax.random.PRNGKey(1), ids)["params"])
    kept = jax.jit(jax.grad(loss))(params)
    kernels.setattr(hybrid_lm, "KERNEL_RESULT_NAMES", ())
    rerun = jax.jit(jax.grad(loss))(params)
    paths = jax.tree_util.tree_leaves_with_path(rerun)
    assert len(paths) == len(jax.tree.leaves(kept)) > 0
    for (path, r), got in zip(paths, jax.tree.leaves(kept)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-7)
        assert float(jnp.max(jnp.abs(got - r))) <= 2e-4 * scale, jax.tree_util.keystr(path)
