"""Assignments a held expert sees a step in the Nemotron-H cell, on average
over the held experts and the expert layers: the program's
``moe_held_assignments`` counter (the window's mean, from the trainer's
history) over layers x experts held (``n_routed_experts`` in this family's
file). In the deployment an expert would see 16 times as many."""

from lib import flops_nemotron_h as F


def read(ctx):
    counters = ctx.get("counters") or {}
    if "moe_held_assignments" not in counters:
        return None
    cfg = ctx["cfg"]
    return counters["moe_held_assignments"] / (
        F.layers_of(cfg, "experts") * cfg["n_routed_experts"])
