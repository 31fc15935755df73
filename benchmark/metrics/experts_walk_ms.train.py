"""Device time a train step spends in the held experts' walk: the operations
under the part scope ``experts_walk`` (``models/moe.py``, entered in both rules
of ``_held_experts``), per ``jit_train_step`` execution, with XLA's
``ragged_dot`` kernels that run in the walk's loops (``lib/scopes.py``). What
``PERF.md`` section 5 summed by hand as every operation inside the expert
layers' ``%while`` loops."""

from lib import scopes

PROGRAM = ("jit_train_step",)
PARTS = ("experts_walk",)


def read(ctx):
    return scopes.part_ms(ctx, PARTS)
