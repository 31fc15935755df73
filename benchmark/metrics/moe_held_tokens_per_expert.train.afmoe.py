"""Assignments a held expert sees a step in the AFMoE cell, on average over the
held experts and the expert layers: the program's ``moe_held_assignments``
counter (the window's mean, from the trainer's history) over layers x experts
held (``num_experts`` in this family's file). In the deployment an expert
would see 8 times as many."""

from lib import flops_afmoe as F


def read(ctx):
    counters = ctx.get("counters") or {}
    if "moe_held_assignments" not in counters:
        return None
    cfg = ctx["cfg"]
    return counters["moe_held_assignments"] / (
        F.layers_of(cfg, ffn="experts") * cfg["num_experts"])
