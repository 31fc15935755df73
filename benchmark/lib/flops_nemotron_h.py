"""Operations and bytes the Nemotron-H decoder needs, computed from shapes
(``configs/nemotron-3-nano-30b-a3b.json`` key names), beside ``lib/flops.py``.

What the mathematics on this chip requires, the same whatever implements it:
no recomputed operation, no one-hot embedding matmul (a lookup is a gather),
the head once per token, the held experts' work by the assignments that were
really routed to them (the step's counters), attention at its mean context,
and the state-space scan at the ``5 P N + 3 P`` FLOPs a token a head that one
token's update and read cost (the decay ``P N``, the rank-1 update ``2 P N``,
``h C`` ``2 P N``, ``dt x`` ``P`` and ``D x`` ``2 P``): not the chunked form's
extra matmuls."""

from lib import weights_nemotron_h as N


def _matmul_params(shapes: dict) -> int:
    """Parameters of the 2-D ``kernel`` leaves: one MAC a token each."""
    return sum(s[0] * s[1] for n, s in shapes.items()
               if n.endswith("kernel") and len(s) == 2 and "conv" not in n)


def ssd_flops_token(cfg) -> float:
    """One Mamba-2 layer's recurrence, forward, a token (all heads)."""
    d = N.dims(cfg)
    return (5.0 * d["m_dim"] * d["state"] + 3.0 * d["m_dim"]) * d["m_heads"]


def expert_flops_assignment(cfg) -> float:
    """Forward FLOPs of one token through one routed expert (two matrices)."""
    d = N.dims(cfg)
    return 2.0 * 2 * d["h"] * d["expert_ffn"]


def mixer_forward_flops_token(cfg, kind: str, context: float,
                              held_assignments_token: float) -> float:
    """``held_assignments_token``: assignments to held experts a token, in
    one expert layer (the counters' mean)."""
    d = N.dims(cfg)
    dense = 2.0 * _matmul_params(N.mixer_leaf_shapes(cfg, kind))
    if kind == "mamba2":
        return dense + 2.0 * d["conv"] * d["conv_dim"] + ssd_flops_token(cfg)
    if kind == "gqa":
        return dense + 2.0 * 2.0 * context * d["heads"] * d["head_dim"]     # Q K^T and P V
    # the router and the shared expert, and the routed experts by their load
    return dense + held_assignments_token * expert_flops_assignment(cfg)


def layers_of(cfg, kind: str) -> int:
    return sum(N.kind(cfg, n) == kind for n in range(1, cfg["num_hidden_layers"] + 1))


def forward_flops_token(cfg, context: float, held_assignments_token: float) -> float:
    total = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]                 # the head
    for n in range(1, cfg["num_hidden_layers"] + 1):
        total += mixer_forward_flops_token(cfg, N.kind(cfg, n), context, held_assignments_token)
    return total


def train_flops_token(cfg, seq_len: int, held_assignments_token: float) -> float:
    """Forward + backward per trained token at causal length ``seq_len``
    (mean context (seq_len + 1) / 2); backward costs twice forward."""
    return 3.0 * forward_flops_token(cfg, (seq_len + 1) / 2.0, held_assignments_token)


def ssd_flops(cfg, tokens: int) -> float:
    """One Mamba-2 layer's recurrence, forward + backward, over ``tokens``."""
    return 3.0 * ssd_flops_token(cfg) * tokens


def ssd_bytes(cfg, tokens: int, itemsize: int = 2) -> float:
    """HBM bytes one Mamba-2 layer's recurrence must move a step: forward
    reads x, B, C (``itemsize``) and dt (float32) and writes y; backward reads
    those and dY and writes dx, dB, dC, ddt."""
    d = N.dims(cfg)
    wide, keys = d["inner"], 2 * d["groups"] * d["state"]
    steps = 4.0 * d["m_heads"]
    forward = (2.0 * wide + keys) * itemsize + steps              # x y, B C, dt
    backward = (2.0 * wide + keys) * itemsize + steps             # x dY, B C, dt
    backward += (wide + keys) * itemsize + steps                  # dx, dB dC, ddt
    return (forward + backward) * tokens


def gqa_flash_flops(cfg, batch: int, seq_len: int) -> float:
    """Causal flash attention of one GQA layer, forward + backward, as
    ``lib/flops_kimi_linear.py::mla_flash_flops`` counts MLA's: Q K^T and P V
    forward; recomputed Q K^T, dV, dP, dQ, dK backward; each 2 B H S S D FLOPs
    halved by the mask, H the query heads (a key-value head's products are
    done once a query head whether or not K and V are repeated in memory)."""
    d = N.dims(cfg)
    return float(batch) * d["heads"] * seq_len * seq_len * 7 * d["head_dim"]


def gqa_flash_bytes(cfg, batch: int, seq_len: int, itemsize: int = 2) -> float:
    """Forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and
    writes dQ, dK, dV; K, V and their gradients at the key-value heads (what
    the algorithm needs: the program repeats them to the query heads)."""
    d = N.dims(cfg)
    heads = (2 * d["heads"] + 2 * d["kv_heads"]) + (3 * d["heads"] + 2 * d["kv_heads"]) \
        + (d["heads"] + 2 * d["kv_heads"])
    return float(batch) * heads * seq_len * d["head_dim"] * itemsize
