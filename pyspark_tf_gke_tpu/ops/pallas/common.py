"""Shared helpers for the Pallas TPU kernels."""

from __future__ import annotations

import jax

# Auto-flash threshold (measured on v5e, fwd+bwd per train step): below
# this sequence length XLA's fused dense attention wins (kernel dispatch
# and unfusable reshapes dominate); at/above it the Pallas kernel wins —
# 1.2x at S=1024, 2.3x at S=4096, 6x at S=8192 (where dense hits the
# S^2-materialization memory cliff). Shared by the model dispatch
# (models/bert.py resolve_use_flash), ring and Ulysses attention.
FLASH_MIN_SEQ = 512


def on_tpu() -> bool:
    """True when the active backend compiles Pallas TPU kernels."""
    return jax.default_backend() == "tpu"


def pick_block(n: int, desired: int, multiple: int) -> int:
    """Largest divisor of ``n`` <= ``desired`` that is a multiple of
    ``multiple`` (Mosaic tiling: 8 for sublane/row blocks, 128 for lane
    blocks), else the whole axis as one block."""
    for blk in range(min(desired, n), multiple - 1, -1):
        if n % blk == 0 and blk % multiple == 0:
            return blk
    return n
