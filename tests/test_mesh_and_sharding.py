import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pyspark_tf_gke_tpu.parallel.distributed import (
    build_coordinator_address,
    process_ordinal_from_hostname,
    validate_ipv4,
)
from pyspark_tf_gke_tpu.parallel.mesh import (
    batch_sharding,
    make_hybrid_mesh,
    make_mesh,
)
from pyspark_tf_gke_tpu.parallel.sharding import fsdp_spec


def test_make_mesh_default_all_dp(devices):
    mesh = make_mesh()
    assert mesh.shape["dp"] == len(devices)


def test_make_mesh_wildcard(devices):
    mesh = make_mesh({"dp": -1, "tp": 2})
    assert mesh.shape["dp"] == len(devices) // 2
    assert mesh.shape["tp"] == 2


def test_make_mesh_bad_product(devices):
    with pytest.raises(ValueError):
        make_mesh({"dp": 3})
    with pytest.raises(ValueError):
        make_mesh({"bogus": 8})


def test_batch_sharding_spec(mesh_dp_fsdp):
    s = batch_sharding(mesh_dp_fsdp, ndim=2)
    assert s.spec == P(("dp", "fsdp"), None)


def test_fsdp_spec_shards_large_divisible(mesh_dp_fsdp):
    # fsdp axis = 4; big divisible dim → sharded on it
    spec = fsdp_spec((1024, 512), mesh_dp_fsdp, min_size=1024)
    assert spec == P("fsdp", None)
    # small param → replicated (the MinSizePartitioner contract)
    assert fsdp_spec((16,), mesh_dp_fsdp, min_size=1024) == P()
    # indivisible dims → replicated
    assert fsdp_spec((33, 7), mesh_dp_fsdp, min_size=1) == P()


def test_fsdp_spec_no_fsdp_axis(mesh_dp):
    assert fsdp_spec((1024, 1024), mesh_dp, min_size=1) == P()


def test_process_ordinal():
    assert process_ordinal_from_hostname("tpu-worker-3") == 3
    assert process_ordinal_from_hostname("tf-trainer-ps-0") == 0
    assert process_ordinal_from_hostname("nohyphenordinal") is None


def test_coordinator_address_convention():
    assert build_coordinator_address() == "tpu-worker-0.tpu-worker-headless:8476"
    assert build_coordinator_address("10.0.0.5", 1234) == "10.0.0.5:1234"
    assert build_coordinator_address("10.0.0.5:99") == "10.0.0.5:99"


def test_validate_ipv4_rejects_bad():
    with pytest.raises(RuntimeError):
        validate_ipv4("fe80::1")
    with pytest.raises(RuntimeError):
        validate_ipv4("http://10.0.0.1/x")
    with pytest.raises(RuntimeError):
        validate_ipv4("300.1.1.1")
    validate_ipv4("192.168.1.10")  # ok
    validate_ipv4("my-host.example:8476")  # DNS names ok


def test_hybrid_mesh_slice_major_order(devices):
    # 2 "slices" of 4 devices: dp over DCN, fsdp x tp inside a slice.
    # Every intra-slice axis group must hold devices of ONE slice.
    mesh = make_hybrid_mesh({"dp": 2}, {"fsdp": 2, "tp": 2},
                            devices, force_contiguous=True)
    assert mesh.shape["dp"] == 2
    assert mesh.shape["fsdp"] == 2 and mesh.shape["tp"] == 2
    arr = mesh.devices  # canonical order (dp, fsdp, pp, tp, sp, ep)
    slice0 = set(d.id for d in devices[:4])
    slice1 = set(d.id for d in devices[4:])
    dp0 = {d.id for d in arr[0].flatten()}
    dp1 = {d.id for d in arr[1].flatten()}
    assert dp0 == slice0 and dp1 == slice1


def test_hybrid_mesh_axis_spanning_both_networks(devices):
    # dp = 2 slices x 2 in-slice -> global dp=4 with the DCN component
    # varying slowest: dp rows [0,1] come from slice 0, [2,3] from slice 1.
    mesh = make_hybrid_mesh({"dp": 2}, {"dp": 2, "tp": 2},
                            devices, force_contiguous=True)
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    arr = mesh.devices
    slice0 = set(d.id for d in devices[:4])
    first_half = {d.id for d in arr[:2].flatten()}
    assert first_half == slice0


def test_hybrid_mesh_validation(devices):
    with pytest.raises(ValueError):
        make_hybrid_mesh({"dp": 3}, {"tp": 2}, devices)  # 6 != 8
    with pytest.raises(ValueError):
        make_hybrid_mesh({"bogus": 2}, {"tp": 4}, devices)
    with pytest.raises(ValueError):  # two wildcards
        make_hybrid_mesh({"dp": -1}, {"tp": -1}, devices)


def test_hybrid_mesh_executes_collectives(devices):
    # A data-sharded mean over the hybrid mesh must equal the local mean:
    # the psum rides dp (cross-slice) and fsdp (in-slice) together.
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    mesh = make_hybrid_mesh({"dp": 2}, {"fsdp": 2, "tp": 2},
                            devices, force_contiguous=True)
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    xs = jax.device_put(x, batch_sharding(mesh, ndim=2))
    out = jax.jit(lambda a: jnp.mean(a, axis=0),
                  out_shardings=NamedSharding(mesh, P()))(xs)
    np.testing.assert_allclose(np.asarray(out), x.mean(axis=0), rtol=1e-6)


def test_mesh_extent_for_follows_rules(devices):
    # Divisibility guards derive shard extents from LOGICAL_RULES, not
    # hardcoded mesh-axis names: remapping a rule must
    # move every guard with it.
    from pyspark_tf_gke_tpu.parallel.sharding import mesh_extent_for

    mesh = make_mesh({"dp": 2, "tp": 4}, devices)
    assert mesh_extent_for("heads", mesh) == 4      # ("heads","tp")
    assert mesh_extent_for("batch", mesh) == 2      # ("dp","fsdp"), fsdp=1
    assert mesh_extent_for("head_dim", mesh) == 1   # mapped to None
    assert mesh_extent_for("nonexistent", mesh) == 1
    assert mesh_extent_for("heads", None) == 1
    remapped = (("heads", "dp"),)
    assert mesh_extent_for("heads", mesh, rules=remapped) == 2
