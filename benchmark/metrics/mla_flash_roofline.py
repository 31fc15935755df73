"""Causal flash attention of the MLA layers (keys of 192, values of 128),
forward + backward, inside the train step: the least time the chip could take
for the FLOPs and bytes the algorithm needs (``lib/flops_kimi_linear.py``)
over the time of the three flash kernels (``flash_fwd``, ``flash_dq``,
``flash_dkv``) in the trace; with remat the forward runs twice and both runs
are in that time. ``None`` where the trace holds no such kernel."""

from lib import flops_kimi_linear as F
from lib import weights_kimi_linear as K
from lib import trace as T

PROGRAM = ("jit_train_step",)
KERNEL = (("flash_fwd", "tpu_custom_call"), ("flash_dq", "tpu_custom_call"),
          ("flash_dkv", "tpu_custom_call"))


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
    layers = sum(K.attention_kind(cfg, n) == "mla"
                 for n in range(1, cfg["num_hidden_layers"] + 1))
    least = max(F.mla_flash_flops(cfg, rows, seq) / ctx["peaks"]["bf16_flops"],
                F.mla_flash_bytes(cfg, rows, seq) / ctx["peaks"]["hbm_bytes_s"])
    return 100.0 * least * layers * len(mods) / T.total_seconds(kernels)
