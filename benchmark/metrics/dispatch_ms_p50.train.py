"""Median time the trainer's loop spends handing one step to the device:
the ``train.step_dispatch`` annotations (around the jitted call, which
returns before the device has run it) of the traced ``fit``."""

import statistics

from lib import spans as S

ANNOTATION = "train.step_dispatch"


def read(ctx):
    calls = S.named(S.loop_thread(S.host_of(ctx), ANNOTATION), ANNOTATION)
    if not calls:
        return None
    return statistics.median(e[2] for e in calls) / 1e6
