"""Operations and bytes the ``afmoe`` forward requires, from shapes and from
what the program counted — never from what a kernel computed.

Matmul FLOPs are 2 per multiply-add.  A token at position ``p`` attends
``p + 1`` cached positions in a full layer and ``min(p + 1, sliding_window)``
in a sliding one; scores and weighted values cost ``2 * heads * head_dim``
FLOPs each a cached position.  The dense part is every product a token takes
whatever its routing: the four attention projections (the gate is as wide as
q), the output projection, the dense FFN of the leading layers, the routers,
the **shared expert** of every routed layer, the head.  The routed experts'
part is not a function of shapes: it is what the run routed to the experts
held here, ``moe_assignments_local_total`` over the window, each one SwiGLU of
width ``moe_intermediate_size``.  That counter counts padded rows too (a
decode row with no request, the tail of a prompt's last chunk): the program
routes them like any other, and ``dispatch_token_utilization.serve`` says how
many there were.

The family brings no kernel of its own: its decode attention is
``paged_decode_attention`` (bytes: the ``decode_step`` span's
``kv_bytes_global`` and ``kv_bytes_window``, models/step.CacheSpec.read_bytes)
and its experts' products are ``jax.lax.ragged_dot`` (bytes:
:func:`expert_bytes` of the distinct experts hit).
"""

from __future__ import annotations


def _routed(cfg: dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


def _n_window(cfg: dict) -> int:
    return sum(kind == "sliding_attention" for kind in cfg["layer_types"])


def dense_flops_per_token(cfg: dict) -> float:
    h, n, n_kv, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    f = cfg["moe_intermediate_size"]
    total = 2.0 * h * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        total += 2.0 * h * (2 * n * d + 2 * n_kv * d) + 2.0 * n * d * h
        if _routed(cfg, i):
            total += 2.0 * h * cfg["num_experts"] + 2.0 * 3 * h * f * cfg.get("num_shared_experts", 0)
        else:
            total += 2.0 * 3 * h * cfg["intermediate_size"]
    return total


def attention_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Scores and weighted values of the tokens at positions ``start..stop-1``."""
    per_position = 2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    w = cfg["sliding_window"]
    n_window = _n_window(cfg)
    n_full = cfg["num_hidden_layers"] - n_window
    ctx_full = (start + 1 + stop) * (stop - start) // 2  # sum of p + 1
    inside = max(0, min(stop, w) - start)  # positions below the window: p + 1 each
    ctx_window = (2 * start + 1 + inside) * inside // 2 + w * max(0, stop - max(start, w))
    return per_position * (n_full * ctx_full + n_window * ctx_window)


def serve_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Forward FLOPs of positions ``start..stop-1`` but for the routed experts."""
    return dense_flops_per_token(cfg) * (stop - start) + attention_flops_span(cfg, start, stop)


def expert_flops(cfg: dict, assignments_local: float) -> float:
    """The held experts' FLOPs for that many (token, expert) assignments."""
    return assignments_local * 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg: dict, experts_hit: float, itemsize: int = 2) -> float:
    """Weight bytes a step must read for that many distinct experts."""
    return experts_hit * 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def kv_read_bytes(cfg: dict, position: int, itemsize: int = 2) -> dict:
    """K/V bytes a decode at ``position`` must read, by cache kind (what the
    program's ``decode_step`` span says as ``kv_bytes_global`` and
    ``kv_bytes_window``, summed over its rows)."""
    per_layer = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    n_window = _n_window(cfg)
    return {
        "global": (cfg["num_hidden_layers"] - n_window) * (position + 1) * per_layer,
        "window": n_window * min(position + 1, cfg["sliding_window"]) * per_layer,
    }
