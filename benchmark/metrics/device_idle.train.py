"""Share of the trainer loop's time in which no operation ran on the device,
on the device's own clock: from the first traced train step's start to the
last one's end, 1 - union of device-op intervals / that span. What is left is
what the loop adds between steps (input wait, dispatch); the profiler's own
start and stop lie outside the span and are not in it."""

from lib import trace as T

PROGRAM = ("jit_train_step",)


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"]:
        return None
    dev = tr["devices"][0]
    mods = T.matching(dev["modules"], PROGRAM)
    if len(mods) < 2:
        return None
    lo = min(m[1] for m in mods)
    hi = max(m[1] + m[2] for m in mods)
    ops = [(n, s, min(d, hi - s)) for n, s, d in (dev["ops"] or mods) if lo <= s < hi]
    return 100.0 * (1.0 - T.union_seconds(ops) / ((hi - lo) / 1e9))
