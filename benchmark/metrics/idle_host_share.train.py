"""Of the device's idle time between the first traced train step's start
and the last one's end (the span ``device_idle.train`` uses), the share in
which the host had not yet handed the device its next step: from a gap's
start until the ``train.step_dispatch`` annotation of the step that ends the
gap returns (``lib/spans.py::host_idle_ns``). The rest is the device's own
launch gap, with the next step already queued.

Not read off the annotation that covers a gap's start: on the v5e the loop
leaves ``train.first_step_sync`` 2.5 ms after the device went idle, and once
the runtime's bound on programs in flight holds the loop, it waits in
``train.metrics_accumulate`` and not in a ``*_sync`` (PERF.md section 6, PR 25)."""

from lib import spans as S

PROGRAM = ("jit_train_step",)
DISPATCH = "train.step_dispatch"


def read(ctx):
    tr = ctx.get("trace")
    loop = S.loop_thread(S.host_of(ctx), DISPATCH)
    if not tr or not tr["devices"] or not loop:
        return None
    found = S.host_idle_ns(tr["devices"][0], PROGRAM, loop, DISPATCH)
    if found is None or not found[0]:
        return None
    return 100.0 * found[1] / found[0]
