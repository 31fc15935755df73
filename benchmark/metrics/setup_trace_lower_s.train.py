"""Seconds of set-up the program spent tracing its functions to jaxprs and
lowering them to MLIR: the union of the ``jax.trace`` and ``jax.lower`` spans
(``obs/compiles.py``) under a ``train.*`` span in the program's ring. A
union, so a jit traced inside another is counted once; the benchmark's own
programs have no ``train.*`` ancestor and are not in it."""

from lib import spans as S

SPANS = ("jax.trace", "jax.lower")
UNDER = "train."


def read(ctx):
    return S.union_under(S.ring_of(ctx), SPANS, UNDER)
