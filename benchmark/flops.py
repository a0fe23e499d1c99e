"""Operations the algorithm requires, from shapes.  Recomputed operations
(remat) never count, and a frozen kernel takes no weight gradient.

Matmul FLOPs are 2 per multiply-add.  Causal attention over a sequence of
``s`` tokens costs, per layer, ``QK^T`` and ``PV`` at half the square:
``2 * s * h`` FLOPs a token in the forward pass.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> dict:
    """Parameters of the matmul kernels: ``body`` (attention + MLP linears of
    all layers) and ``head`` (the output projection)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    L, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    return {"body": L * (4 * h * h + 2 * h * f), "head": h * v}


def lora_params(cfg: dict, r: int) -> int:
    """LoRA factors on qkv, dense, h_to_4h and 4h_to_h of every layer."""
    h, f, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    return L * r * ((h + 3 * h) + (h + h) + 2 * (h + f))


def train_flops_per_token(cfg: dict, seq: int, lora_r: int = 0) -> float:
    """Forward and backward of one trained token.  With ``lora_r > 0`` the
    body kernels are frozen (ReLoRA): activation gradients pass through them,
    weight gradients go to the LoRA factors and the output head only."""
    mm = matmul_params(cfg)
    n_mm = mm["body"] + mm["head"]
    n_lora = lora_params(cfg, lora_r) if lora_r else 0
    attn = 2 * cfg["num_hidden_layers"] * seq * cfg["hidden_size"]
    forward = 2 * (n_mm + n_lora) + attn
    n_weight_grad = (n_lora + mm["head"]) if lora_r else n_mm
    backward = 2 * (n_mm + n_lora) + 2 * n_weight_grad + 2 * attn
    return float(forward + backward)


def serve_flops_per_token(cfg: dict, position: int) -> float:
    """Forward of one token (prompt or output) that attends to ``position + 1``
    cached positions, merged LoRA-free weights."""
    mm = matmul_params(cfg)
    attn = 4 * cfg["num_hidden_layers"] * cfg["hidden_size"] * (position + 1)
    return float(2 * (mm["body"] + mm["head"]) + attn)


def serve_flops_span(cfg: dict, start: int, stop: int) -> float:
    """Sum of :func:`serve_flops_per_token` over positions ``start..stop-1``."""
    mm = matmul_params(cfg)
    n = stop - start
    ctx = (start + 1 + stop) * n // 2  # sum of (p + 1) for p in [start, stop)
    return float(2 * (mm["body"] + mm["head"]) * n + 4 * cfg["num_hidden_layers"] * cfg["hidden_size"] * ctx)


def flash_attention_train(cfg: dict, batch: int, seq: int) -> dict:
    """Causal flash attention over all layers of one update, forward and
    backward: FLOPs and HBM bytes the algorithm needs.  Forward: ``QK^T`` and
    ``PV``.  Backward: ``QK^T`` again (the scores are never stored), ``dP``,
    ``dV``, ``dQ``, ``dK``.  Each product is ``2 * s * s * head_dim`` a head,
    halved by the causal mask.  Bytes: bf16 q, k, v, o in the forward pass;
    q, k, v, o, do in and dq, dk, dv out in the backward pass.  The forward
    pass that full remat runs a second time is not required work."""
    h, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    product = 2 * batch * seq * seq * h / 2
    tensor_bytes = 2 * batch * seq * h
    return {"flops": float(L * 7 * product), "bytes": float(L * (4 + 8) * tensor_bytes)}


def scaled(work: dict, times: float) -> dict:
    return {k: v * times for k, v in work.items()}
