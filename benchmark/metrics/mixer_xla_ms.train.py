"""Device time a train step spends in its mixer sublayers outside the Pallas
launches that have readers of their own: the part scope ``mixer``
(``HybridBlock`` and ``CausalLMBlock``: the norm(s), the projections, the
layout copies, the work around the kernels of GatedAttention, GQA, MLA, KDA
and Mamba-2, and the residual sum), per ``jit_train_step`` execution, less the
launches ``flash_*``, ``kda_*`` and ``ssd_*`` (``lib/scopes.py``). The fused
LayerNorm's launches have no reader and stay in. The residual sum fused into
the next sublayer's norm, and the projections' weight gradients fused with
their Adam updates, are ``shared_ms.train``'s."""

from lib import scopes

PROGRAM = ("jit_train_step",)
PARTS = ("mixer",)
KERNELS = (("flash_", "tpu_custom_call"), ("kda_", "tpu_custom_call"),
           ("ssd_", "tpu_custom_call"))


def read(ctx):
    return scopes.part_ms(ctx, PARTS, leave_out=KERNELS)
