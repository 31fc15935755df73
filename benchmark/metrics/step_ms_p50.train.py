"""Median device duration of the train-step program in the trace. Against
``train_tok_s`` (all work over all time) it shows a stall the median hides."""

import statistics

from lib import trace as T

PROGRAM = ("jit_train_step",)


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"]:
        return None
    mods = T.matching(tr["devices"][0]["modules"], PROGRAM)
    if not mods:
        return None
    return statistics.median([m[2] for m in mods]) / 1e6
