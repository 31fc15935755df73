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

The mixer kernels' forward results carry names of their own as well
(:data:`KERNEL_RESULTS`), for a remat policy to keep.
"""

from typing import Optional

import jax
from jax.ad_checkpoint import checkpoint_name
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


# What each mixer kernel's forward launch gives its backward, one name a result.
# Each custom-VJP forward rule names exactly what its launch returned
# (:func:`name_results`); a remat policy that saves these names keeps them, so
# the backward's rerun of a block does not launch the forward again
# (``models/hybrid_lm.py::HybridLM``). Outside a remat a name is the identity.
KERNEL_RESULTS = {"flash": ("flash_out", "flash_lse"),
                  "kda": ("kda_o", "kda_states"),
                  "ssd": ("ssd_y", "ssd_kept")}
KERNEL_RESULT_NAMES = tuple(name for names in KERNEL_RESULTS.values() for name in names)


def name_results(kernel: str, *results):
    """``results`` of one forward launch of ``kernel``, each under its name in
    :data:`KERNEL_RESULTS`: all of them, since a rerun that lacks one of a
    launch's results launches it again for that one."""
    return tuple(checkpoint_name(r, n) for r, n in zip(results, KERNEL_RESULTS[kernel],
                                                          strict=True))


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
