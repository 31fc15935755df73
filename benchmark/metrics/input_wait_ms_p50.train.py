"""Median time the trainer's loop waits for its next device batch: the
``train.input_wait`` annotations (around ``next(device_batches)``, fed by
``data/pipeline.py::prefetch_to_device``) of the traced ``fit``."""

import statistics

from lib import spans as S

LOOP = "train.step_dispatch"
ANNOTATION = "train.input_wait"


def read(ctx):
    waits = S.named(S.loop_thread(S.host_of(ctx), LOOP), ANNOTATION)
    if not waits:
        return None
    return statistics.median(e[2] for e in waits) / 1e6
