"""Test harness: a virtual 8-device CPU "fake slice".

This is the SURVEY §4 design: the reference tests distributed behavior
without a cluster via kind+MetalLB; we do it with
``--xla_force_host_platform_device_count=8`` so every sharding/collective
path (dp, fsdp, tp, sp rings) compiles and runs in-process. Env vars must
be set before jax initializes, hence at conftest import time.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# config.update wins over a JAX_PLATFORMS env var as long as it runs
# before any backend is initialized.
jax.config.update("jax_platforms", "cpu")

# Numerical comparisons in tests assume real f32 matmuls, not bf16 passes.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) >= 8, f"fake slice needs 8 devices, got {len(d)}"
    return d


@pytest.fixture()
def mesh_dp(devices):
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

    return make_mesh({"dp": 8})


@pytest.fixture()
def mesh_dp_fsdp(devices):
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

    return make_mesh({"dp": 2, "fsdp": 4})


@pytest.fixture()
def mesh_tp(devices):
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

    return make_mesh({"dp": 2, "fsdp": 2, "tp": 2})


@pytest.fixture()
def mesh_sp(devices):
    from pyspark_tf_gke_tpu.parallel.mesh import make_mesh

    return make_mesh({"dp": 2, "sp": 4})
