"""Whole training step's share of the chips' peak for the AFMoE cell: forward +
backward FLOPs per token (``lib/flops_afmoe.py``: no recomputed operation, no
one-hot embedding matmul, the held experts' work by the assignments the step's
counters report, a window layer's attention at the keys a row sees) x tokens
trained in the window over window x chips x peak."""

from lib import flops_afmoe as F


def read(ctx):
    counters = ctx.get("counters") or {}
    if not ctx.get("steps") or not ctx.get("peaks") or "moe_held_assignments" not in counters:
        return None
    cfg = ctx["cfg"]
    per_layer_token = counters["moe_held_assignments"] / (
        F.layers_of(cfg, ffn="experts") * ctx["tokens_per_step"])
    per_token = F.train_flops_token(cfg, ctx["traffic"]["seq_len"], per_layer_token)
    tokens = ctx["steps"] * ctx["tokens_per_step"]
    return 100.0 * per_token * tokens / (
        ctx["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops"])
