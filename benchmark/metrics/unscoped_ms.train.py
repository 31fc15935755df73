"""Device time a train step spends in operations under no part scope, per
``jit_train_step`` execution: the check on the tracing itself. It holds what
XLA adds without metadata (layout copies, the memory space's async copies and
slices, the broadcasts that zero a loop's carries) outside every part's loop:
an operation belongs to the parts its own and its fused instructions carry
(``lib/scopes.py``). ``None`` for a program without the scopes."""

from lib import scopes

PROGRAM = ("jit_train_step",)


def read(ctx):
    return scopes.part_ms(ctx, (scopes.UNSCOPED,))
