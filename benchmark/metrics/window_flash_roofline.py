"""Windowed flash attention of the sliding layers (32 query heads on 4
key-value heads of 128, a window of 2048 keys), forward + backward, inside the
train step: the least time the chip could take for the FLOPs of the scores a
row sees and the bytes the algorithm needs (``lib/flops_afmoe.py``) over the
time of the three windowed kernels (``window_flash_fwd``, ``window_flash_dq``,
``window_flash_dkv``) in the trace; with remat the forward runs twice and both
runs are in that time. ``None`` where the trace holds no such kernel."""

from lib import flops_afmoe as F
from lib import trace as T

PROGRAM = ("jit_train_step",)
KERNEL = (("window_flash_fwd", "tpu_custom_call"), ("window_flash_dq", "tpu_custom_call"),
          ("window_flash_dkv", "tpu_custom_call"))


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
    rows, seq = ctx["rows"] // ctx["chips"], ctx["traffic"]["seq_len"]
    least = max(F.flash_flops(cfg, rows, seq, cfg["sliding_window"]) / ctx["peaks"]["bf16_flops"],
                F.flash_bytes(cfg, rows, seq) / ctx["peaks"]["hbm_bytes_s"])
    return 100.0 * least * F.layers_of(cfg, attention="sliding") * len(mods) \
        / T.total_seconds(kernels)
