"""A stable name of its own for every Pallas kernel in the device trace.

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

import jax
from jax.extend import source_info_util


def kernel_scope(kernel_name: str):
    """``jax.named_scope`` for one kernel launch: ``<caller's innermost
    scope>.<kernel_name>``, or the kernel's name alone outside any scope."""
    scopes = [e.name for e in source_info_util.current_name_stack().stack
              if type(e).__name__ == "Scope"]      # not the jvp/transpose marks
    return jax.named_scope(f"{scopes[-1]}.{kernel_name}" if scopes else kernel_name)
