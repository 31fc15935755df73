"""Routing imbalance over the held experts in the AFMoE cell: the busiest held
expert's assignments in any expert layer (``moe_held_load_max``) over the mean
held expert's (``moe_held_tokens_per_expert.train.afmoe``). 1 is even."""

from lib import flops_afmoe as F


def read(ctx):
    counters = ctx.get("counters") or {}
    if not counters.get("moe_held_assignments") or "moe_held_load_max" not in counters:
        return None
    cfg = ctx["cfg"]
    mean = counters["moe_held_assignments"] / (
        F.layers_of(cfg, ffn="experts") * cfg["num_experts"])
    return counters["moe_held_load_max"] / mean
