"""Causal flash attention of the AFMoE cell's global layers (32 query heads on
4 key-value heads of 128, no window), forward + backward, inside the train
step, as ``gqa_flash_roofline`` reads Nemotron-H's: the least time the chip
could take for the FLOPs and bytes the algorithm needs (``lib/flops_afmoe.py``)
over the time of the three flash kernels (``flash_fwd``, ``flash_dq``,
``flash_dkv``) in the trace, the windowed launches (``window_flash_*``, the
sliding layers') left out; with remat the forward runs twice and both runs are
in that time. ``None`` where the trace holds no such kernel."""

from lib import flops_afmoe as F
from lib import trace as T

PROGRAM = ("jit_train_step",)
KERNEL = (("flash_fwd", "tpu_custom_call"), ("flash_dq", "tpu_custom_call"),
          ("flash_dkv", "tpu_custom_call"))
NOT_KERNEL = "window_flash_"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"] or not ctx.get("peaks"):
        return None
    dev = tr["devices"][0]
    mods = T.matching(dev["modules"], PROGRAM)
    kernels = [e for e in T.inside(T.matching(dev["ops"], KERNEL), mods)
               if NOT_KERNEL not in e[0]]
    if not mods or not kernels:
        return None
    cfg = ctx["cfg"]
    rows, seq = ctx["rows"] // ctx["chips"], ctx["traffic"]["seq_len"]
    least = max(F.flash_flops(cfg, rows, seq) / ctx["peaks"]["bf16_flops"],
                F.flash_bytes(cfg, rows, seq) / ctx["peaks"]["hbm_bytes_s"])
    return 100.0 * least * F.layers_of(cfg, attention="full") * len(mods) \
        / T.total_seconds(kernels)
