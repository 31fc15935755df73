"""Causal flash attention forward + backward (``ops/pallas/flash_attention.py``
``_fwd_kernel``, ``_dq_kernel``, ``_dkv_kernel``) inside the train step: the
least time the chip could take for the FLOPs and bytes the algorithm needs
(lib/flops.py, from shapes) over the kernels' time in the trace."""

from lib import flops as F
from lib import trace as T

PROGRAM = ("jit_train_step",)
# Mosaic names a kernel by the module method that calls it (lib/trace.py)
KERNEL = (("_causal_attend", "tpu_custom_call"),)


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"]:
        return None
    dev = tr["devices"][0]
    mods = T.matching(dev["modules"], PROGRAM)
    kernels = T.inside(T.matching(dev["ops"], KERNEL), mods)
    if not mods or not kernels:
        return None
    rows = ctx["rows"] // ctx["chips"]          # each chip attends its rows
    seq = ctx["traffic"]["seq_len"]
    least = max(F.flash_attention_flops(ctx["cfg"], rows, seq) / ctx["peaks"]["bf16_flops"],
                F.flash_attention_bytes(ctx["cfg"], rows, seq) / ctx["peaks"]["hbm_bytes_s"])
    least *= ctx["cfg"]["n_layer"] * len(mods)
    return 100.0 * least / T.total_seconds(kernels)
