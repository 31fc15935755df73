"""Device time a train step spends in its FFN sublayers: the part scopes
``ffn`` (``HybridBlock`` and ``CausalLMBlock``: the norm(s) before and after,
the dense MLP, SwiGLU or relu² FFN, or ``HeldExpertsLayer`` with its router and
shared expert, and the residual sum) and ``experts_walk`` inside it, per
``jit_train_step`` execution (``lib/scopes.py``). The weight gradients fused
with their Adam updates, and the residual sum fused into the next norm, are
``shared_ms.train``'s."""

from lib import scopes

PROGRAM = ("jit_train_step",)
PARTS = ("ffn", "experts_walk")


def read(ctx):
    return scopes.part_ms(ctx, PARTS)
