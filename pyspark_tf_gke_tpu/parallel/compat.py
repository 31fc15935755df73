"""Flax metadata helper for the sharded trainers."""

from __future__ import annotations


def unbox_without_constraint(tree):
    """Recursively unbox flax ``AxisMetadata`` leaves WITHOUT applying
    the in-jit sharding constraint. Under an ambient mesh,
    ``Partitioned.unbox`` applies ``PartitionSpec(*names)`` literally,
    and models that box LOGICAL names in raw ``nn.Partitioned``
    (models/pipelined_bert.py) crash on any mesh lacking such axes —
    current jax validates axis names strictly at NamedSharding
    construction. Callers (trainer.init_state's ``out_shardings``,
    pipeline_apply's own constraints) pin placement themselves, so the
    skipped constraint changes nothing placed."""
    import jax
    from flax.core import meta as _meta

    is_meta = lambda x: isinstance(x, _meta.AxisMetadata)  # noqa: E731
    return jax.tree_util.tree_map(
        lambda x: unbox_without_constraint(x.unbox(apply_constraint=False))
        if is_meta(x) else x,
        tree, is_leaf=is_meta)
