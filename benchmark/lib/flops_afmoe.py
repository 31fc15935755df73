"""Operations and bytes the AFMoE decoder needs, computed from shapes
(``configs/trinity-mini.json`` key names), beside ``lib/flops.py``.

What the mathematics on this chip requires, the same whatever implements it:
no recomputed operation, no one-hot embedding matmul (a lookup is a gather),
the head once per token, the held experts' work by the assignments that were
really routed to them (the step's counters), and attention at the keys a row
really sees: all before it on a global layer, ``sliding_window`` at most on a
window layer."""

from lib import weights_afmoe as A


def _matmul_params(shapes: dict) -> int:
    """Parameters of the 2-D ``kernel`` leaves: one MAC a token each."""
    return sum(s[0] * s[1] for n, s in shapes.items()
               if n.endswith("kernel") and len(s) == 2)


def visible_scores(seq_len: int, window=None) -> int:
    """Scores a head computes of one row of the batch: key ``j`` for query
    ``i`` where ``j <= i`` and, with a window, ``i - j < window``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layers_of(cfg, attention=None, ffn=None) -> int:
    """Layers of that attention kind (``sliding`` / ``full``) or FFN kind."""
    return sum((attention is None or A.attention_kind(cfg, n) == attention)
               and (ffn is None or A.ffn_kind(cfg, n) == ffn)
               for n in range(cfg["num_hidden_layers"]))


def expert_flops_assignment(cfg) -> float:
    """Forward FLOPs of one token through one routed expert (three matrices)."""
    d = A.dims(cfg)
    return 2.0 * 3 * d["h"] * d["expert_ffn"]


def attention_forward_flops_token(cfg, kind: str, seq_len: int) -> float:
    """The projections, and Q K^T and P V at the row's mean visible keys."""
    d = A.dims(cfg)
    context = visible_scores(seq_len, d["window"] if kind == "sliding" else None) / seq_len
    return 2.0 * _matmul_params(A.attention_leaf_shapes(cfg)) \
        + 2.0 * 2.0 * context * d["heads"] * d["head_dim"]


def ffn_forward_flops_token(cfg, kind: str, held_assignments_token: float) -> float:
    """``held_assignments_token``: assignments to held experts a token, in
    one expert layer (the counters' mean). The router and the shared expert
    are dense; the routed experts go by their load."""
    dense = 2.0 * _matmul_params(A.ffn_leaf_shapes(cfg, kind))
    if kind == "dense":
        return dense
    return dense + held_assignments_token * expert_flops_assignment(cfg)


def forward_flops_token(cfg, seq_len: int, held_assignments_token: float) -> float:
    total = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]                 # the head
    for n in range(cfg["num_hidden_layers"]):
        total += attention_forward_flops_token(cfg, A.attention_kind(cfg, n), seq_len)
        total += ffn_forward_flops_token(cfg, A.ffn_kind(cfg, n), held_assignments_token)
    return total


def train_flops_token(cfg, seq_len: int, held_assignments_token: float) -> float:
    """Forward + backward per trained token; backward costs twice forward."""
    return 3.0 * forward_flops_token(cfg, seq_len, held_assignments_token)


def flash_flops(cfg, batch: int, seq_len: int, window=None) -> float:
    """Flash attention of one layer, forward + backward, as
    ``lib/flops_nemotron_h.py::gqa_flash_flops`` counts the causal launches: Q
    K^T and P V forward; recomputed Q K^T, dV, dP, dQ, dK backward; 2 D FLOPs a
    visible score each, H the query heads (a key-value head's products are done
    once a query head whether or not K and V are repeated in memory). With
    ``window`` the visible scores are the window's (:func:`visible_scores`),
    which stand where ``S x S / 2`` stands there."""
    d = A.dims(cfg)
    return float(batch) * d["heads"] * visible_scores(seq_len, window) * 2 * 7 * d["head_dim"]


def flash_bytes(cfg, batch: int, seq_len: int, itemsize: int = 2) -> float:
    """Forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and
    writes dQ, dK, dV; K, V and their gradients at the key-value heads (what
    the algorithm needs: the program repeats them to the query heads). The
    same with or without a window."""
    d = A.dims(cfg)
    heads = (2 * d["heads"] + 2 * d["kv_heads"]) + (3 * d["heads"] + 2 * d["kv_heads"]) \
        + (d["heads"] + 2 * d["kv_heads"])
    return float(batch) * heads * seq_len * d["head_dim"] * itemsize
