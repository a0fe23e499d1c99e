"""Operations and bytes the ``mimo_v2`` forward requires, from shapes and
from what the program counted — never from what a kernel computed.

Matmul FLOPs are 2 per multiply-add.  A token at position ``p`` attends
``p + 1`` cached positions in a global layer and ``min(p + 1,
sliding_window)`` in a window layer; scores cost ``2 * heads * d_qk`` and the
weighted values ``2 * heads * d_v`` FLOPs a cached position.  The routed
experts' part is not a function of shapes: it is what the run routed to the
experts held here, ``moe_assignments_local_total`` over the window, each one
SwiGLU of width ``moe_intermediate_size``.  That counter counts padded rows
too (a decode row with no request, the tail of a prompt's last chunk): the
program routes them like any other, and ``dispatch_token_utilization.serve``
says how many there were.
"""

from __future__ import annotations


def dense_flops_per_token(cfg: dict) -> float:
    """Every product a token takes whatever its position and routing:
    attention projections, the dense FFN layers, the routers, the head."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    total = 2.0 * h * cfg["vocab_size"]
    for window, routed in zip(cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]):
        n_kv = cfg["swa_num_key_value_heads"] if window else cfg["num_key_value_heads"]
        total += 2.0 * h * (n * dk + n_kv * dk + n_kv * dv) + 2.0 * n * dv * h
        total += 2.0 * h * cfg["n_routed_experts"] if routed else 2.0 * 3 * h * cfg["intermediate_size"]
    return total


def attention_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Scores and weighted values of the tokens at positions ``start..stop-1``."""
    per_position = 2.0 * cfg["num_attention_heads"] * (cfg["head_dim"] + cfg["v_head_dim"])
    w = cfg["sliding_window"]
    n_window = sum(cfg["hybrid_layer_pattern"])
    n_global = cfg["num_hidden_layers"] - n_window
    ctx_global = (start + 1 + stop) * (stop - start) // 2  # sum of p + 1
    ctx_window = sum(min(p + 1, w) for p in range(start, min(stop, w))) + w * max(0, stop - max(start, w))
    return per_position * (n_global * ctx_global + n_window * ctx_window)


def serve_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Forward FLOPs of positions ``start..stop-1`` but for the routed experts."""
    return dense_flops_per_token(cfg) * (stop - start) + attention_flops_span(cfg, start, stop)


def expert_flops(cfg: dict, assignments_local: float) -> float:
    """The held experts' FLOPs for that many (token, expert) assignments."""
    return assignments_local * 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg: dict, experts_hit: float, itemsize: int = 2) -> float:
    """Weight bytes a step must read for that many distinct experts."""
    return experts_hit * 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize
