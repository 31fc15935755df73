"""Persistent XLA compile cache with a location the caller can place.

Every entry point that compiles (``train/cli.py``, ``lm_pretrain``,
``bert_finetune``, ``serve``, the kernel check under
``chip_smoke.py``) calls :func:`enable_compile_cache` first thing
in its ``__main__`` block.

The cache path is part of JAX's cache key, so it must not move between
runs: never a tempfile, pid or timestamp path. Two cases only:

* ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads it itself at import;
  this module sets NOTHING in code, so an operator (or a machine image
  that keeps a warm cache across jobs) owns the location.
* unset — one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored), shared by every process started from this tree.

:func:`key_on_names` makes the key hold each instruction's metadata too.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def key_on_names() -> None:
    """Key the persistent cache on the programs' metadata as well. JAX strips
    it from the key by default, so a program that differs from a cached one
    only in its names (the train step's part scopes, ``ops/pallas/scope.py::
    part_scope``, which a trace is read by) would load the cached executable
    with the other program's names."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
