"""The state-space scan, forward + backward, inside the train step: the least
time the chip could take for the FLOPs and bytes the recurrence needs
(``lib/flops_nemotron_h.py``: 5 P N + 3 P FLOPs a token a head forward; x, B,
C, dt in and y out, and the like backward; no recomputation, none of the
chunked form's extra matmuls) over the time of the ``ssd_*`` kernels in the
trace. ``None`` where the trace holds no such kernel."""

from lib import flops_nemotron_h as F
from lib import trace as T

PROGRAM = ("jit_train_step",)
KERNEL = (("ssd_", "tpu_custom_call"),)


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"] or not ctx.get("peaks"):
        return None
    dev = tr["devices"][0]
    mods = T.matching(dev["modules"], PROGRAM)
    kernels = T.inside(T.matching(dev["ops"], KERNEL), mods)
    if not mods or not kernels:
        return None
    cfg = ctx["cfg"]
    tokens = ctx["tokens_per_step"] // ctx["chips"]
    least = max(F.ssd_flops(cfg, tokens) / ctx["peaks"]["bf16_flops"],
                F.ssd_bytes(cfg, tokens) / ctx["peaks"]["hbm_bytes_s"])
    return 100.0 * least * F.layers_of(cfg, "mamba2") * len(mods) / T.total_seconds(kernels)
