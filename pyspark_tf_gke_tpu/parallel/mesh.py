"""Device-mesh construction.

The reference expresses parallelism as a *process topology* (N worker pods,
M parameter-server pods, ``train_tf_ps.py:385-437``). The TPU-native design
expresses it as a *device mesh*: one logical array of chips with named
axes, over which arrays are sharded with ``NamedSharding``. XLA inserts the
collectives (allreduce over ICI replaces PS variable push/pull over gRPC).

Canonical axis names (any subset may be size 1 / absent):

``dp``    pure data parallelism (params replicated)
``fsdp``  data parallelism with parameter/optimizer sharding — the analog
          of the reference's ``MinSizePartitioner`` across PS replicas
          (``train_tf_ps.py:505-507``), but sharding *all* state, not just
          large variables on dedicated servers.
``tp``    tensor (model) parallelism within a layer
``sp``    sequence/context parallelism (ring attention)
``ep``    expert parallelism (MoE)
``pp``    pipeline parallelism across layer groups
"""

from __future__ import annotations

import logging
from typing import Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")

# Axes a global batch is split over. fsdp is "data parallelism that also
# shards params", so the batch dimension spans both.
DATA_AXES = ("dp", "fsdp")


def make_mesh(
    axes: Optional[Mapping[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``Mesh`` over ``devices`` with the canonical axis order.

    ``axes`` maps axis name → size. Missing axes get size 1. An empty/None
    ``axes`` puts every device on ``dp``. Axis sizes must multiply to the
    device count, except that one axis may be -1 ("take the rest"),
    mirroring the UX of the reference's replica-count flags.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = {a: 1 for a in AXES}
    if axes:
        for name, size in axes.items():
            if name not in sizes:
                raise ValueError(f"Unknown mesh axis {name!r}; valid axes: {AXES}")
            sizes[name] = int(size)
    else:
        sizes["dp"] = n

    wildcard = [a for a, s in sizes.items() if s == -1]
    if len(wildcard) > 1:
        raise ValueError("At most one mesh axis may be -1")
    if wildcard:
        fixed = int(np.prod([s for s in sizes.values() if s != -1]))
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by fixed axes product {fixed}")
        sizes[wildcard[0]] = n // fixed

    total = int(np.prod(list(sizes.values())))
    if total != n:
        raise ValueError(f"Mesh axes {dict(sizes)} require {total} devices, have {n}")

    shape = tuple(sizes[a] for a in AXES)
    device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, AXES)


def make_hybrid_mesh(
    dcn_axes: Optional[Mapping[str, int]] = None,
    ici_axes: Optional[Mapping[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    force_contiguous: bool = False,
) -> Mesh:
    """Build a multi-slice ``Mesh`` whose device order respects the
    ICI/DCN hierarchy.

    A TPU pod slice is all-to-all connected over ICI; separate slices
    only talk over DCN (data-center network, ~10-100x less bandwidth).
    The reference never faces this — its gRPC parameter servers treat
    every link the same (``train_tf_ps.py:440-511``) — but a mesh that
    interleaves devices from different slices along an axis forces every
    collective on that axis onto DCN. This constructor orders devices
    **slice-major**: for each axis, the DCN component varies slowest, so
    any axis-local group of ``ici_axes[a]`` neighbors is intra-slice and
    XLA:TPU can decompose a cross-slice collective hierarchically
    (reduce-scatter over ICI -> small allreduce over DCN -> all-gather
    over ICI). Same contract as jax's
    ``mesh_utils.create_hybrid_device_mesh``, restricted to the
    canonical axis names.

    ``dcn_axes``  axis -> number of slices it spans (usually ``{"dp": S}``:
                  pure data parallelism is the only strategy cheap enough
                  for DCN bandwidth).
    ``ici_axes``  axis -> size within one slice (fsdp/tp/sp/ep/pp live
                  here, where the collectives are per-step and heavy).
    An axis present in both gets global size ``dcn*ici`` with slice-major
    element order. ``make_mesh``'s flag UX carries over: at most one axis
    (across both specs) may be -1 ("take the rest"), and an empty
    ``ici_axes`` puts each slice's devices on ``dp`` — so adding
    ``--dcn-mesh-shape dp=2`` to any working ``--mesh-shape`` keeps
    working. ``force_contiguous`` skips slice-membership detection and
    groups devices in order (tests pinning the CPU-fake layout).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    dcn = {a: 1 for a in AXES}
    ici = {a: 1 for a in AXES}
    for name, size in (dcn_axes or {}).items():
        if name not in dcn:
            raise ValueError(f"Unknown mesh axis {name!r}; valid axes: {AXES}")
        dcn[name] = int(size)
    if ici_axes:
        for name, size in ici_axes.items():
            if name not in ici:
                raise ValueError(
                    f"Unknown mesh axis {name!r}; valid axes: {AXES}")
            ici[name] = int(size)
    else:
        ici["dp"] = -1  # make_mesh's default: remaining devices on dp

    wildcard = [(spec, a) for spec in (dcn, ici)
                for a, s in spec.items() if s == -1]
    if len(wildcard) > 1:
        raise ValueError("At most one hybrid-mesh axis may be -1")
    if wildcard:
        spec, axis = wildcard[0]
        spec[axis] = 1
        fixed = int(np.prod(list(dcn.values()))) * int(
            np.prod(list(ici.values())))
        if n % fixed:
            raise ValueError(
                f"{n} devices not divisible by fixed axes product {fixed}")
        spec[axis] = n // fixed
    n_slices = int(np.prod(list(dcn.values())))
    per_slice = int(np.prod(list(ici.values())))
    if n_slices * per_slice != n:
        raise ValueError(
            f"dcn {dict((a, s) for a, s in dcn.items() if s > 1)} x ici "
            f"{dict((a, s) for a, s in ici.items() if s > 1)} require "
            f"{n_slices}x{per_slice}={n_slices * per_slice} devices, have {n}")

    # Group devices into slices: real TPU devices carry slice_index;
    # fall back to process grouping (one host per slice is the common
    # multi-slice deployment), then to contiguous chunks (CPU fake).
    key = None
    if not force_contiguous:
        if all(getattr(d, "slice_index", None) is not None for d in devices):
            key = lambda d: d.slice_index  # noqa: E731
        elif n_slices > 1 and len({d.process_index for d in devices}) == n_slices:
            # Heuristic, not ground truth: a single-slice multi-host pod
            # (e.g. v5e-16, 4 hosts) with --dcn-mesh-shape dp=4 lands
            # here too, and the "slices" are really per-host ICI groups
            # — numerically fine, but the hierarchical-collective layout
            # premise (DCN between groups) is wrong. Surface it so a
            # mis-deployed dcn spec is visible instead of silent.
            logging.getLogger(__name__).warning(
                "make_hybrid_mesh: devices carry no slice_index; treating "
                "the %d process groups as the %d DCN slices. If these "
                "processes are hosts of ONE pod slice, the dcn_axes spec "
                "describes ICI links as DCN — pass force_contiguous=True "
                "or drop --dcn-mesh-shape.", n_slices, n_slices)
            key = lambda d: d.process_index  # noqa: E731
    if key is None:
        groups = [devices[i:i + per_slice]
                  for i in range(0, n, per_slice)]
    else:
        by_slice: dict = {}
        for d in devices:
            by_slice.setdefault(key(d), []).append(d)
        groups = [by_slice[k] for k in sorted(by_slice)]
    if len(groups) != n_slices or any(len(g) != per_slice for g in groups):
        raise ValueError(
            f"Device slice grouping gave {[len(g) for g in groups]} devices "
            f"per slice; need {n_slices} slices x {per_slice}")

    dcn_shape = tuple(dcn[a] for a in AXES)
    ici_shape = tuple(ici[a] for a in AXES)
    global_shape = tuple(d * i for d, i in zip(dcn_shape, ici_shape))
    arr = np.empty(global_shape, dtype=object)
    for ordinal, group in enumerate(groups):
        dcn_idx = np.unravel_index(ordinal, dcn_shape)
        block = np.asarray(group, dtype=object).reshape(ici_shape)
        dest = tuple(
            slice(di * isz, (di + 1) * isz)
            for di, isz in zip(dcn_idx, ici_shape)
        )
        arr[dest] = block
    return Mesh(arr, AXES)


def mesh_from_spec(
    ici_axes: Optional[Mapping[str, int]] = None,
    dcn_axes: Optional[Mapping[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Config-level dispatcher: a non-empty ``dcn_axes`` selects the
    slice-major hybrid construction, otherwise the ordinary mesh."""
    if dcn_axes:
        return make_hybrid_mesh(dcn_axes, ici_axes, devices)
    return make_mesh(ici_axes or None, devices)


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` block (the context the
    trainer and the serving engine run their jits under), or None
    outside one. Readable at trace time inside ``jit``, which
    ``jax.sharding.get_mesh`` is not; ``get_abstract_mesh`` only sees
    ``jax.set_mesh`` contexts, which this code base does not enter."""
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def batch_sharding(mesh: Mesh, ndim: int = 1, extra: Optional[P] = None) -> NamedSharding:
    """Sharding for a host-fed batch: leading dim split over the data axes.

    This is the SPMD replacement for the reference's per-worker
    ``dataset.shard(num_input_pipelines, input_pipeline_id)``
    (``train_tf_ps.py:312-313``): each chip sees 1/(dp*fsdp) of the batch.
    """
    if extra is not None:
        return NamedSharding(mesh, P(DATA_AXES, *extra))
    return NamedSharding(mesh, P(DATA_AXES, *([None] * (ndim - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_mesh_for_testing(n: int = 8, axes: Optional[Mapping[str, int]] = None) -> Mesh:
    """Mesh over the first ``n`` local devices — the unit-test "fake slice"
    (SURVEY §4: ``xla_force_host_platform_device_count`` stands in for the
    reference's kind+MetalLB local cluster)."""
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"Need {n} devices for the fake slice, have {len(devices)}. "
            "Set XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu."
        )
    return make_mesh(axes or {"dp": n}, devices)
