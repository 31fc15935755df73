"""A stable name of its own for every Pallas kernel in the device trace, and
one scope for each part of the train step (:func:`part_scope`).

XLA names a Mosaic custom call after the INNERMOST name scope alone, and the
profiler's ``XLA Ops`` events carry that instruction name (plus shapes and
``custom_call_target="tpu_custom_call"``; no ``op_name`` metadata). Without
a scope the flash kernels are all ``%attention._causal_attend.N``: forward,
dq and dkv cannot be told apart. ``pallas_call(name=...)`` and a plain
``jax.named_scope("flash_fwd")`` both REPLACE that name by ``%flash_fwd.N``
(compiled for a described v5e chip, PR 25), which would lose the calling
method that readers of older traces match. :func:`kernel_scope` therefore
joins the kernel's name to the scope it is called from:
``%attention._causal_attend.flash_fwd.N``.
"""

from typing import Optional

import jax
from jax.extend import source_info_util


def caller_scope() -> str:
    """The innermost name scope of the code being traced, '' outside any."""
    scopes = [e.name for e in source_info_util.current_name_stack().stack
              if type(e).__name__ == "Scope"]      # not the jvp/transpose marks
    return scopes[-1] if scopes else ""


def kernel_scope(kernel_name: str, caller: Optional[str] = None):
    """``jax.named_scope`` for one kernel launch: ``<caller's innermost
    scope>.<kernel_name>``, or the kernel's name alone outside any scope.
    A launch that is traced apart from its call site (inside a ``jax.jit`` of
    its own, whose name stack starts empty) passes the ``caller_scope()`` it
    read at the call site. The name is then part of that ``jit``'s cache key:
    a model's layers share one trace of a kernel only where their innermost
    scope has the same name in every layer."""
    if caller is None:
        caller = caller_scope()
    return jax.named_scope(f"{caller}.{kernel_name}" if caller else kernel_name)


# The parts of a train step, each the name of a scope that every family enters
# at its own sites (``part_scope``); ``benchmark/lib/scopes.py`` reads each
# part's device time off the trace by the last ``part.<name>`` in an
# operation's ``op_name``, so a part entered inside another wins there
# (``experts_walk`` inside ``ffn``).
STEP_PARTS = ("embed", "mixer", "ffn", "experts_walk", "head_loss", "optimizer")


def part_scope(name: str):
    """``jax.named_scope("part.<name>")`` for one part of the step. Only the
    ``op_name`` metadata of what is traced under it changes: enter it around
    a module's call, never between a ``kernel_scope`` and its launch, whose
    name is the innermost scope."""
    if name not in STEP_PARTS:
        raise ValueError(f"unknown step part {name!r}; the parts are {STEP_PARTS}")
    return jax.named_scope(f"part.{name}")
