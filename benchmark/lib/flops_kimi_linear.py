"""Operations and bytes the Kimi-Linear decoder needs, computed from shapes
(``configs/kimi-linear-48b-a3b.json`` key names), beside ``lib/flops.py``.

What the mathematics on this chip requires, the same whatever implements it:
no recomputed operation, no one-hot embedding matmul (a lookup is a gather),
the head once per token, the held experts' work by the assignments that were
really routed to them (the step's counters), and KDA's recurrence at the
6 ``d_k d_v`` FLOPs a token a head that one token's update and read cost
(decay ``d_k d_v``, ``k^T S`` 2, the rank-1 update 2, ``S^T q`` 2, less the
multiply shared with the update): not the chunked form's extra matmuls."""

from lib import weights_kimi_linear as K


def _matmul_params(shapes: dict, skip=()) -> int:
    """Parameters of the 2-D ``kernel`` leaves: one MAC a token each."""
    return sum(s[0] * s[1] for n, s in shapes.items()
               if n.endswith("kernel") and len(s) == 2 and "conv" not in n
               and not n.startswith(tuple(skip)))


def kda_recurrence_flops_token(cfg) -> float:
    d = K.dims(cfg)
    return 6.0 * d["kda_dim"] * d["kda_dim"] * d["kda_heads"]


def attention_forward_flops_token(cfg, kind: str, context: float) -> float:
    d = K.dims(cfg)
    dense = 2.0 * _matmul_params(K.attention_leaf_shapes(cfg, kind))
    if kind == "kda":
        conv = 2.0 * 3 * d["conv"] * d["kda_heads"] * d["kda_dim"]
        return dense + conv + kda_recurrence_flops_token(cfg)
    scores = 2.0 * context * d["heads"] * (d["nope"] + d["rope"])      # Q K^T
    return dense + scores + 2.0 * context * d["heads"] * d["v_dim"]    # + P V


def expert_flops_assignment(cfg) -> float:
    """Forward FLOPs of one token through one routed expert."""
    d = K.dims(cfg)
    return 2.0 * 3 * d["h"] * d["expert_ffn"]


def ffn_forward_flops_token(cfg, kind: str, held_assignments_token: float) -> float:
    """``held_assignments_token``: assignments to held experts a token, in
    one expert layer (the counters' mean)."""
    shapes = K.ffn_leaf_shapes(cfg, kind)
    if kind == "dense":
        return 2.0 * _matmul_params(shapes)
    return (2.0 * _matmul_params(shapes)            # router and shared expert
            + held_assignments_token * expert_flops_assignment(cfg))


def expert_layers(cfg) -> int:
    return sum(K.ffn_kind(cfg, n) == "experts"
               for n in range(1, cfg["num_hidden_layers"] + 1))


def forward_flops_token(cfg, context: float, held_assignments_token: float) -> float:
    total = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]                 # the head
    for n in range(1, cfg["num_hidden_layers"] + 1):
        total += attention_forward_flops_token(cfg, K.attention_kind(cfg, n), context)
        total += ffn_forward_flops_token(cfg, K.ffn_kind(cfg, n), held_assignments_token)
    return total


def train_flops_token(cfg, seq_len: int, held_assignments_token: float) -> float:
    """Forward + backward per trained token at causal length ``seq_len``
    (mean context (seq_len + 1) / 2); backward costs twice forward."""
    return 3.0 * forward_flops_token(cfg, (seq_len + 1) / 2.0, held_assignments_token)


def kda_flops(cfg, tokens: int) -> float:
    """One KDA layer's recurrence, forward + backward, over ``tokens``."""
    return 3.0 * kda_recurrence_flops_token(cfg) * tokens


def kda_bytes(cfg, tokens: int, itemsize: int = 2) -> float:
    """HBM bytes one KDA layer's recurrence must move a step: forward reads
    q, k, v (``itemsize``), g and beta (float32) and writes o; backward reads
    those and dO and writes dq, dk, dv, dg, dbeta."""
    d = K.dims(cfg)
    wide = d["kda_heads"] * d["kda_dim"]
    gates = 4.0 * (wide + d["kda_heads"])
    forward = 4.0 * wide * itemsize + gates                 # q k v o, g beta
    backward = 4.0 * wide * itemsize + gates                # q k v dO, g beta
    backward += 3.0 * wide * itemsize + gates               # dq dk dv, dg dbeta
    return (forward + backward) * tokens


def mla_flash_flops(cfg, batch: int, seq_len: int) -> float:
    """Causal flash attention of one MLA layer, forward + backward: Q K^T and
    P V forward; recomputed Q K^T, dV, dP, dQ, dK backward; each
    2 B H S S width FLOPs halved by the mask, width the keys' (Q K^T, dQ,
    dK) or the values' (P V, dV, dP)."""
    d = K.dims(cfg)
    wk, wv = d["nope"] + d["rope"], d["v_dim"]
    widths = (wk + wv) + (wk + wv + wv + wk + wk)
    return float(batch) * d["heads"] * seq_len * seq_len * widths


def mla_flash_bytes(cfg, batch: int, seq_len: int, itemsize: int = 2) -> float:
    """Forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and
    writes dQ, dK, dV."""
    d = K.dims(cfg)
    wk, wv = d["nope"] + d["rope"], d["v_dim"]
    widths = (2 * wk + 2 * wv) + (2 * wk + 3 * wv) + (2 * wk + wv)
    return float(batch) * d["heads"] * seq_len * widths * itemsize
