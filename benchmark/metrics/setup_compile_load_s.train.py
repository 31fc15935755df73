"""Seconds of set-up the program spent in XLA's backend compile, or in
loading the executable from the persistent cache (JAX reports both as one
event): the union of the ``jax.compile`` spans (``obs/compiles.py``) under a
``train.*`` span in the program's ring."""

from lib import spans as S

SPANS = ("jax.compile",)
UNDER = "train."


def read(ctx):
    return S.union_under(S.ring_of(ctx), SPANS, UNDER)
