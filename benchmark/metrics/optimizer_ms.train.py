"""Device time a train step spends in the optimizer's own launches: the part
scope ``optimizer`` around ``Trainer``'s ``apply_gradients`` (Adam's moments
and update over every leaf, the step count), per ``jit_train_step`` execution
(``lib/scopes.py``). The Adam updates XLA fuses into their gradients'
matmuls are not here but in ``shared_ms.train``: this is the Adam work kept
apart, not all of Adam's."""

from lib import scopes

PROGRAM = ("jit_train_step",)
PARTS = ("optimizer",)


def read(ctx):
    return scopes.part_ms(ctx, PARTS)
