"""Assignments a held expert sees a step, on average over the held experts
and the expert layers: the program's ``moe_held_assignments`` counter (the
window's mean, from the trainer's history) over layers x experts held. In the
deployment an expert would see the whole batch's worth."""

from lib import flops_kimi_linear as F


def read(ctx):
    counters = ctx.get("counters") or {}
    if "moe_held_assignments" not in counters:
        return None
    cfg = ctx["cfg"]
    return counters["moe_held_assignments"] / (F.expert_layers(cfg) * cfg["num_experts"])
