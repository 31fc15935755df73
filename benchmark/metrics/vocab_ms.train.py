"""Device time a train step spends at the vocabulary: the part scopes
``embed`` (``wte``, ``wpe`` and the embedding's scale) and ``head_loss``
(``ln_final``, ``lm_head`` and the task's loss with ``ops/chunked_ce.py``'s
chunks), per ``jit_train_step`` execution (``lib/scopes.py``). A fusion
that joins this work to another part's, as the embedding table's gradient
joined to its Adam update, is ``shared_ms.train``'s."""

from lib import scopes

PROGRAM = ("jit_train_step",)
PARTS = ("embed", "head_loss")


def read(ctx):
    return scopes.part_ms(ctx, PARTS)
