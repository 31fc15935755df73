"""Whole training step's share of the chips' peak: forward + backward FLOPs
per token (lib/flops.py: no recomputed operation, no one-hot embedding
matmul) x tokens trained in the window over window x chips x peak."""

from lib import flops as F


def read(ctx):
    if not ctx.get("steps") or not ctx.get("peaks"):
        return None
    per_token = F.train_flops_token(ctx["cfg"], ctx["traffic"]["seq_len"])
    tokens = ctx["steps"] * ctx["tokens_per_step"]
    return 100.0 * per_token * tokens / (
        ctx["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops"])
