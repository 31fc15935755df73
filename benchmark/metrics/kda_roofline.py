"""KDA's recurrence, forward + backward, inside the train step: the least
time the chip could take for the FLOPs and bytes the recurrence needs
(``lib/flops_kimi_linear.py``: 6 d_k d_v FLOPs a token a head forward, q, k,
v, g, beta in and o out, no recomputation, none of the chunked form's extra
matmuls) over the time of the ``kda_*`` kernels in the trace. ``None`` where
the trace holds no such kernel."""

from lib import flops_kimi_linear as F
from lib import weights_kimi_linear as K
from lib import trace as T

PROGRAM = ("jit_train_step",)
KERNEL = (("kda_", "tpu_custom_call"),)


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
    layers = sum(K.attention_kind(cfg, n) == "kda"
                 for n in range(1, cfg["num_hidden_layers"] + 1))
    least = max(F.kda_flops(cfg, tokens) / ctx["peaks"]["bf16_flops"],
                F.kda_bytes(cfg, tokens) / ctx["peaks"]["hbm_bytes_s"])
    return 100.0 * least * layers * len(mods) / T.total_seconds(kernels)
