"""Device time a train step spends in fusions that join the work of two or
more parts, per ``jit_train_step`` execution (``lib/scopes.py``): XLA fuses
each weight's Adam update into the matmul that makes its gradient
(``mixer+optimizer``, ``ffn+optimizer``; the embedding table's with its
one-hot gradient), and the residual sum that ends one sublayer into the norm
that starts the next (``ffn+mixer``, ``embed+mixer``). Such a fusion is one
launch, so no part's reader takes it; ``benchmark/tools/describe_parts.py``
splits this by the parts joined. ``None`` for a program without the scopes."""

from lib import scopes

PROGRAM = ("jit_train_step",)


def read(ctx):
    return scopes.part_ms(ctx, (scopes.SHARED,))
