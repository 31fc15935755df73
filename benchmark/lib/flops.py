"""Operations and bytes the algorithm needs, computed from shapes.

Everything here counts what the mathematics requires: no recomputed
operation, no one-hot embedding matmul (an embedding lookup is a gather), the
LM head once per token that needs logits. ``cfg`` is a configuration file's
dict (GPT-2 key names)."""


def _dims(cfg):
    h = cfg["n_embd"]
    ffn = cfg.get("n_inner") or 4 * h
    return h, cfg["n_layer"], cfg["n_head"], ffn, cfg["vocab_size"]


def matmul_params_per_layer(cfg) -> int:
    h, _, _, ffn, _ = _dims(cfg)
    return 4 * h * h + 2 * h * ffn


def param_count(cfg, tied_head: bool = False) -> int:
    """Every parameter the program holds (biases and norms included)."""
    h, layers, _, ffn, vocab = _dims(cfg)
    per_layer = (4 * h * h + 4 * h) + (2 * h * ffn + ffn + h) + 4 * h
    head = 0 if tied_head else h * vocab + vocab
    return vocab * h + cfg["n_positions"] * h + layers * per_layer + 2 * h + head


def forward_flops_token(cfg, context: float, with_head: bool = True) -> float:
    """Forward FLOPs of one token that attends over ``context`` keys."""
    h, layers, _, _, vocab = _dims(cfg)
    dense = 2.0 * layers * matmul_params_per_layer(cfg)
    attn = 4.0 * layers * h * context          # QK^T and PV, 2 FLOPs per MAC
    head = 2.0 * h * vocab if with_head else 0.0
    return dense + attn + head


def train_flops_token(cfg, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token at causal length
    ``seq_len``: the mean context of a causal row is (seq_len + 1) / 2, and
    backward costs twice forward."""
    return 3.0 * forward_flops_token(cfg, (seq_len + 1) / 2.0)


def flash_attention_flops(cfg, batch: int, seq_len: int) -> float:
    """Causal flash attention of one layer, forward + backward: 2 matmuls
    forward (QK^T, PV) and 5 backward (recomputed QK^T, dV, dP, dQ, dK), each
    2*B*H*S*S*D FLOPs, halved by the causal mask."""
    h = cfg["n_embd"]
    per_matmul = 2.0 * batch * seq_len * seq_len * h / 2.0
    return 7.0 * per_matmul


def flash_attention_bytes(cfg, batch: int, seq_len: int, itemsize: int = 2) -> float:
    """HBM bytes one layer's kernels must move: forward reads Q, K, V and
    writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    h = cfg["n_embd"]
    return 12.0 * batch * seq_len * h * itemsize
