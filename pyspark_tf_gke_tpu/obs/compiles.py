"""JAX's own trace / lower / compile as spans, a counter and a hook.

JAX hands every jaxpr trace, every lowering to MLIR and every backend
compile (or load from the persistent cache: the event is the same) to
``jax.monitoring.record_event_time_span(event, start, end, fun_name=...)``
on the thread that did the work. :func:`install_compile_listener`
registers ONE listener for the process (``Trainer.__init__`` and the entry
points that call ``enable_compile_cache`` do; it is idempotent) which
turns them into

* finished spans ``jax.trace`` / ``jax.lower`` / ``jax.compile`` with
  attribute ``fun``, children of the span current on that thread, in that
  span's recorder (``TraceRecorder.record_span``). A compile that
  ``train.init_state`` or a ``train.epoch`` caused hangs under it; one with
  no program span around it leaves no span (JAX's own small programs,
  ``convert_element_type`` and the like, would otherwise fill the ring);
* ``runtime_jit_compiles_total{fun}`` on the shared registry, whatever the
  parent: which functions this process compiled, and how often. ``fun``
  is the Python function's name, so its values are bounded by the code;
* a call to the function given to :func:`on_compile` on that thread, if
  any: how the trainer tells a recompile of its step from every other
  compile without this module knowing what a step is.

One function has one name: JAX reports ``train_step`` for the trace and
``jit(train_step)`` for lowering and compile; the wrapper is stripped.
JAX is not patched, and nothing here imports it before the install.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
import threading
from typing import Callable, Optional

from pyspark_tf_gke_tpu.obs.metrics import get_registry, platform_families
from pyspark_tf_gke_tpu.obs.trace import recording_parent

SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
COUNTER = "runtime_jit_compiles_total"

_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_installed = False
_install_lock = threading.Lock()
_on_compile: "contextvars.ContextVar[Optional[Callable[[str, float], None]]]" = (
    contextvars.ContextVar("pyspark_tf_gke_tpu_on_compile", default=None))


def fun_label(fun_name) -> str:
    """``jit(train_step)`` -> ``train_step``; anything else as it is."""
    name = str(fun_name) if fun_name else "unknown"
    m = _WRAPPED.match(name)
    return m.group(1) if m else name


@contextlib.contextmanager
def on_compile(callback: Callable[[str, float], None]):
    """For the enclosed block, on this thread: ``callback(fun, seconds)``
    after every backend compile (or cache load)."""
    token = _on_compile.set(callback)
    try:
        yield
    finally:
        _on_compile.reset(token)


_open = threading.local()   # .n: timed blocks of JAX's open on this thread


def _on_start(event: str, value, **_) -> None:
    if event in SPAN_OF_EVENT:
        _open.n = getattr(_open, "n", 0) + 1


def _on_end(event: str, start: float, end: float, fun_name=None, **_) -> None:
    name = SPAN_OF_EVENT.get(event)
    if name is None:
        return
    _open.n = still_open = max(getattr(_open, "n", 1) - 1, 0)
    try:
        _record(name, fun_label(fun_name), start, end, outermost=not still_open)
    except Exception:  # noqa: BLE001 — this runs inside JAX's compile
        pass           # path: observability must never fail a compile


def _record(name: str, fun: str, start: float, end: float, outermost: bool) -> None:
    parent = recording_parent() if outermost else None
    if parent is not None:
        parent.recorder.record_span(name, start, end, parent, {"fun": fun})
    if name != "jax.compile":
        return
    registry = get_registry()
    counter = registry.get(COUNTER) or platform_families(registry)[COUNTER]
    counter.labels(fun).inc()
    callback = _on_compile.get()
    if callback is not None:
        callback(fun, end - start)


def install_compile_listener() -> None:
    """Register the listener with ``jax.monitoring``, once per process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_on_start)
        jax.monitoring.register_event_time_span_listener(_on_end)
        _installed = True
