"""Shape-aware dispatch for the LoRA composite ``x @ W + ((x @ A) @ B) * s``.

There are three ways to execute the composite, and the right one depends on
the (M, K, N, r) shape — *Run LoRA Run* (2312.03415) territory:

- **fused** — the single-``pallas_call`` kernel from
  :mod:`relora_tpu.ops.pallas_lora_matmul`: every operand read from HBM
  exactly once, rank-r intermediate VMEM-resident, one launch.  Wins for
  training-sized M on TPU; needs M and N to tile and a real Mosaic backend
  (the interpreter is a correctness tool, ~1000x slower than XLA on CPU).
- **ordered** — the unfused ``x@W + ((x@A)@B)*s`` reference with the cheap
  left-to-right association (models/lora.py's historical path).  Always
  available; the fallback for shapes that don't tile and for dropout-active
  branches (where the LoRA input differs from the base input).
- **merged** — ``x @ (W + s·(A@B))``: fold the rank-r delta into the base
  weight and run one matmul.  For decode-sized M (batch × 1 tokens) the
  composite is launch/bandwidth-bound, not FLOPs-bound, so paying the
  2·K·r·N delta FLOPs to drop down to a single effective matmul wins —
  this is the arm serve/engine.py's decode forward selects.

:func:`choose_arm` ranks the arms with a bytes/FLOPs roofline plus a
per-launch overhead term — ``t(arm) = max(bytes/BW, flops/peak) +
launches·t_launch`` — over static python ints only (``lru_cache``-d; no
tracing, no retraces).  :func:`lora_matmul` is the execution entry point
used by models/lora.py and the serve engine; forcing ``arm=`` bypasses the
model (how CPU tests pin each arm).  :func:`plan_blocks` is the one home
for kernel block planning, subsuming the probe loops previously inlined in
``LoRALinear._int8_matmul``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from relora_tpu.ops.pallas_lora_matmul import (
    fused_lora_matmul,
    fused_lora_matmul_int8,
    grouped_lora_matmul,
    grouped_lora_reference,
)
from relora_tpu.ops.quant import dequantize_int8

__all__ = [
    "ARMS",
    "GROUPED_ARMS",
    "plan_blocks",
    "estimate_arm_times",
    "estimate_grouped_arm_times",
    "choose_arm",
    "choose_grouped_arm",
    "lora_matmul",
    "lora_matmul_grouped",
]

ARMS: Tuple[str, ...] = ("fused", "ordered", "merged")

#: Execution arms for the *multi-tenant* composite, where each activation row
#: references its own adapter slot (serve/adapters.py).  Disjoint from
#: :data:`ARMS` on purpose: the single-adapter arms cannot express a mixed
#: batch, and the grouped arms need the stacked-factor operands.
GROUPED_ARMS: Tuple[str, ...] = ("grouped", "gathered", "looped")

#: Pallas block-size candidates, largest first.  The minor (lane) dimension
#: stays a multiple of 128 for Mosaic tiling; the sublane dimension may
#: shrink to 8 so decode-sized M still tiles.
BLOCK_M_CANDIDATES: Tuple[int, ...] = (256, 128, 64, 32, 16, 8)
BLOCK_N_CANDIDATES: Tuple[int, ...] = (256, 128)

# Roofline constants for TPU v5e (single core).  Only the *ratios* matter for
# arm ranking, so these double for the CPU path without harm: the model picks
# the same winner anywhere the launch/bandwidth/FLOP balance is TPU-like.
HBM_BW_BYTES = 819e9  # HBM bandwidth, bytes/s
PEAK_FLOPS = 197e12  # bf16 MXU peak, FLOP/s
LAUNCH_OVERHEAD_S = 3e-6  # per dispatched op (launch + scheduling)


def plan_blocks(M: int, N: int) -> Optional[Tuple[int, int]]:
    """Largest (block_m, block_n) candidates that tile (M, N); ``None`` if
    either axis has no candidate divisor (the caller must fall back to an
    unfused arm).  The one home for kernel block planning — subsumes the
    probe loops previously inlined in ``LoRALinear._int8_matmul``."""
    bm = next((c for c in BLOCK_M_CANDIDATES if M % c == 0), None)
    bn = next((c for c in BLOCK_N_CANDIDATES if N % c == 0), None)
    if bm is None or bn is None:
        return None
    return bm, bn


@functools.lru_cache(maxsize=4096)
def estimate_arm_times(
    M: int,
    K: int,
    N: int,
    r: int,
    act_bytes: int = 2,
    base_bytes: int = 2,
    weights_static: bool = False,
) -> Dict[str, float]:
    """Modeled seconds per arm for one composite of shape (M, K, N, r).

    ``act_bytes`` is the activation/LoRA dtype width (2 for bf16), and
    ``base_bytes`` the stored base-weight width (1 for int8).
    ``weights_static`` says W/A/B are constant across many calls (serving:
    the merged ``W + s·A@B`` is built once and amortizes to nothing), as
    opposed to training, where W changes every step and merged pays the
    full delta + materialization each call.  The model is deliberately
    coarse — a roofline ``max(bytes/BW, flops/peak)`` plus a launch term —
    because arm ranking only needs the right *order*: decode-M with static
    weights → merged, mid-M training → fused, very large M → merged wins
    on FLOPs alone once ``M > K·N/(K+N)`` (Run LoRA Run's crossover).
    """

    def roofline(nbytes: float, flops: float, launches: int) -> float:
        return max(nbytes / HBM_BW_BYTES, flops / PEAK_FLOPS) + launches * LAUNCH_OVERHEAD_S

    base_flops = 2.0 * M * K * N
    lora_flops = 2.0 * M * r * (K + N)
    w_bytes = float(K * N * base_bytes)
    factor_bytes = float((K * r + r * N) * act_bytes)

    # ordered: x@W, x@A, z@B, add — the base result and the full-width LoRA
    # output both round-trip through HBM, and the add re-reads both.
    ordered = roofline(
        w_bytes
        + factor_bytes
        + (2 * M * K + 2 * M * r + 3 * M * N) * act_bytes,
        base_flops + lora_flops,
        4,
    )

    # fused: every operand read once, y (+ tiny z) written once, one launch.
    fused = roofline(
        w_bytes + factor_bytes + (M * K + M * N + M * r) * act_bytes,
        base_flops + lora_flops,
        1,
    )

    # merged: one matmul against w_eff = W + s·(A@B).
    if weights_static:
        # w_eff is built once outside the step and reused: per-call cost is a
        # bare dense matmul (w_eff is act-width even over a quantized base).
        merged = roofline(
            float(K * N * act_bytes) + (M * K + M * N) * act_bytes, base_flops, 1
        )
    else:
        # Rebuilt per call: pay the 2·K·r·N delta FLOPs plus the w_eff HBM
        # round trip (a matmul output cannot fuse into a matmul operand).
        merged_bytes = (
            w_bytes + factor_bytes + (M * K + M * N) * act_bytes
            + 2.0 * K * N * act_bytes
        )
        merged_launches = 2
        if base_bytes < act_bytes:
            merged_launches += 1  # separate dequant pass feeding the add
        merged = roofline(merged_bytes, base_flops + 2.0 * K * r * N, merged_launches)

    return {"fused": fused, "ordered": ordered, "merged": merged}


@functools.lru_cache(maxsize=4096)
def estimate_grouped_arm_times(
    M: int,
    K: int,
    N: int,
    r: int,
    num_adapters: int = 1,
    act_bytes: int = 2,
    base_bytes: int = 2,
) -> Dict[str, float]:
    """Modeled seconds per *grouped* arm for a mixed-tenant batch of M rows
    touching ``num_adapters`` distinct adapter slots (G).

    - ``grouped`` — the scalar-prefetch kernel: W and the activations stream
      once, and the factor traffic is ``G·(K·r + r·N)`` — **bytes scale with
      the distinct adapters touched, not the batch** (the LoRAFusion
      property this arm exists for).  One launch.
    - ``gathered`` — XLA gather + batched einsum: materializes a per-row
      ``A[idx]``/``B[idx]`` copy in HBM, so factor traffic scales with M
      (read G slabs, write M gathered slabs, read them back).  The
      correctness fallback off-TPU and over int8 bases.
    - ``looped`` — split the batch per adapter and run the single-adapter
      fused kernel G times: G launches, W re-read every launch.
    """
    G = max(1, min(num_adapters, M))

    def roofline(nbytes: float, flops: float, launches: int) -> float:
        return max(nbytes / HBM_BW_BYTES, flops / PEAK_FLOPS) + launches * LAUNCH_OVERHEAD_S

    base_flops = 2.0 * M * K * N
    lora_flops = 2.0 * M * r * (K + N)
    w_bytes = float(K * N * base_bytes)
    slab_bytes = float((K * r + r * N) * act_bytes)
    act_io = (M * K + M * N) * act_bytes

    grouped = roofline(w_bytes + G * slab_bytes + act_io, base_flops + lora_flops, 1)
    gathered = roofline(
        w_bytes + (G + 2.0 * M) * slab_bytes + act_io + 2 * M * N * act_bytes,
        base_flops + lora_flops,
        4,
    )
    looped = roofline(
        G * (w_bytes + slab_bytes) + act_io, base_flops + lora_flops, G
    )
    return {"grouped": grouped, "gathered": gathered, "looped": looped}


@functools.lru_cache(maxsize=4096)
def choose_grouped_arm(
    M: int,
    K: int,
    N: int,
    r: int,
    num_adapters: int = 1,
    act_bytes: int = 2,
    base_bytes: int = 2,
    grouped_available: bool = True,
    allow: Tuple[str, ...] = GROUPED_ARMS,
) -> str:
    """Pick the cheapest grouped arm under the roofline model.

    ``grouped_available=False`` (non-TPU backend, int8 base, or an N with no
    lane-tile divisor) strikes both kernel arms — ``gathered`` is the
    always-available reference.  Pure python over static ints (lru_cache'd;
    no retraces), mirroring :func:`choose_arm`.
    """
    times = estimate_grouped_arm_times(M, K, N, r, num_adapters, act_bytes, base_bytes)
    candidates = [arm for arm in allow if arm in GROUPED_ARMS]
    if not grouped_available or not any(N % c == 0 for c in BLOCK_N_CANDIDATES):
        candidates = [a for a in candidates if a not in ("grouped", "looped")]
    if not candidates:
        return "gathered"
    return min(candidates, key=lambda arm: times[arm])


@functools.lru_cache(maxsize=4096)
def choose_arm(
    M: int,
    K: int,
    N: int,
    r: int,
    act_bytes: int = 2,
    base_bytes: int = 2,
    fused_available: bool = True,
    weights_static: bool = False,
    allow: Tuple[str, ...] = ARMS,
) -> str:
    """Pick the cheapest arm for (M, K, N, r) under the roofline model.

    ``fused_available=False`` (non-TPU backend, or caller opted out) and
    untileable shapes both strike the fused arm; ``allow`` restricts the
    candidate set (tests use it to pin a specific arm's path).  Pure python
    over static ints — safe to call at trace time without retrace risk.
    """
    times = estimate_arm_times(M, K, N, r, act_bytes, base_bytes, weights_static)
    candidates = [arm for arm in allow if arm in ARMS]
    if not fused_available or plan_blocks(M, N) is None:
        candidates = [arm for arm in candidates if arm != "fused"]
    if not candidates:
        return "ordered"
    return min(candidates, key=lambda arm: times[arm])


def _dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def lora_matmul(
    x: jax.Array,
    base: Union[jax.Array, Tuple[jax.Array, jax.Array]],
    a: jax.Array,
    b: jax.Array,
    scale=1.0,
    *,
    arm: str = "auto",
    dtype=None,
    interpret: Optional[bool] = None,
    weights_static: bool = False,
) -> jax.Array:
    """Execute ``x @ W + ((x @ A) @ B) * scale`` via the chosen arm.

    ``base`` is either the dense ``W`` (K, N) or an int8 pair
    ``(q, qscale)`` from :func:`relora_tpu.ops.quant.quantize_int8`.
    ``scale`` may be a python float or a traced scalar (trainable-scaling
    ``tanh(lora_s)``).  ``dtype`` is the compute dtype for the unfused
    arms' matmul operands (defaults to ``x.dtype``; the fused kernel always
    accumulates f32 internally).  ``arm="auto"`` consults
    :func:`choose_arm`; any explicit arm name bypasses the cost model.
    ``weights_static=True`` (serving) tells the model the merged weight
    amortizes across calls — see :func:`estimate_arm_times`.
    The frozen base never receives a gradient through the fused arm — pass
    ``stop_gradient`` on the base (as models/lora.py does) so every arm
    agrees that its cotangent is zero.
    """
    if arm not in ARMS and arm != "auto":
        raise ValueError(f"unknown arm {arm!r}; expected one of {ARMS + ('auto',)}")
    quantized = isinstance(base, tuple)
    if quantized:
        q, qscale = base
        K, N = q.shape
        base_bytes = 1
    else:
        K, N = base.shape
        base_bytes = _dtype_bytes(base.dtype)
    dtype = dtype or x.dtype
    M = 1
    for d in x.shape[:-1]:
        M *= d
    r = a.shape[1]

    if arm == "auto":
        # The Pallas interpreter is a correctness tool, not a fast path:
        # never auto-select fused off-TPU.  On the TPU the fused arm is a
        # candidate only for forward-only calls (weights_static, serving):
        # its backward kernels hold whole-N blocks in VMEM and the chip's
        # compiler refuses them at FFN width (tests/test_tpu_compile.py,
        # ROADMAP A4), and a call cannot see whether it will be differentiated.
        fused_ok = jax.default_backend() == "tpu" and weights_static
        arm = choose_arm(
            M, K, N, r, _dtype_bytes(dtype), base_bytes,
            fused_available=fused_ok, weights_static=weights_static,
        )

    if arm == "fused":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        planned = plan_blocks(M, N)
        if planned is None:
            # choose_arm strikes untileable shapes, so only a forced arm
            # gets here: say so instead of quietly running another arm
            raise ValueError(
                f"arm='fused' was forced but (M={M}, N={N}) has no block plan "
                f"(M must divide by one of {BLOCK_M_CANDIDATES}, N by one of "
                f"{BLOCK_N_CANDIDATES})"
            )
        bm, bn = planned
        kwargs = dict(block_m=bm, block_n=bn, interpret=interpret, out_dtype=dtype)
        if quantized:
            return fused_lora_matmul_int8(
                x.astype(dtype), q, qscale, a.astype(dtype), b.astype(dtype),
                scale, **kwargs,
            )
        return fused_lora_matmul(
            x.astype(dtype), base.astype(dtype), a.astype(dtype),
            b.astype(dtype), scale, **kwargs,
        )

    w = dequantize_int8(q, qscale, dtype) if quantized else base.astype(dtype)
    xd = x.astype(dtype)
    if arm == "merged":
        delta = jnp.matmul(a.astype(dtype), b.astype(dtype)) * scale
        return jnp.matmul(xd, (w + delta.astype(dtype)))
    # ordered — mirrors models/lora.py's historical base + branch association
    z = jnp.matmul(jnp.matmul(xd, a.astype(dtype)), b.astype(dtype))
    return jnp.matmul(xd, w) + z * scale


def lora_matmul_grouped(
    x: jax.Array,
    base: Union[jax.Array, Tuple[jax.Array, jax.Array]],
    a_stack: jax.Array,
    b_stack: jax.Array,
    scale_stack: jax.Array,
    adapter_idx: jax.Array,
    *,
    arm: str = "auto",
    dtype=None,
    interpret: Optional[bool] = None,
    num_adapters: Optional[int] = None,
) -> jax.Array:
    """Execute the mixed-tenant composite
    ``y[m] = x[m] @ W + ((x[m] @ A[idx[m]]) @ B[idx[m]]) * s[idx[m]]``.

    ``a_stack``/``b_stack`` are the (num_slots, K, r)/(num_slots, r, N) HBM
    adapter stacks (serve/adapters.py owns their contents), ``scale_stack``
    the (num_slots,) per-slot scales, ``adapter_idx`` the (M,) int32 row ->
    slot map.  ``num_adapters`` is the static distinct-adapter count for the
    cost model (defaults to min(num_slots, M) — the worst case).  Int8 bases
    always take the ``gathered`` reference (the grouped kernel is dense-base
    only).  Inference-only: no VJP.
    """
    if arm not in GROUPED_ARMS and arm != "auto":
        raise ValueError(
            f"unknown grouped arm {arm!r}; expected one of {GROUPED_ARMS + ('auto',)}"
        )
    quantized = isinstance(base, tuple)
    if quantized:
        q, qscale = base
        K, N = q.shape
        base_bytes = 1
    else:
        K, N = base.shape
        base_bytes = _dtype_bytes(base.dtype)
    dtype = dtype or x.dtype
    M = 1
    for d in x.shape[:-1]:
        M *= d
    S, _, r = a_stack.shape
    if num_adapters is None:
        num_adapters = min(S, M)

    if arm == "auto":
        # the grouped kernel puts one activation row in a program — a (1, K)
        # block the chip's compiler refuses (tests/test_tpu_compile.py,
        # ROADMAP A4) — so no backend has it as a candidate until it is
        # rebuilt; forcing arm="grouped" on a TPU raises the compiler's error
        arm = choose_grouped_arm(
            M, K, N, r, num_adapters, _dtype_bytes(dtype), base_bytes,
            grouped_available=False,
        )

    if arm in ("grouped", "looped") and not quantized:
        # "looped" exists only as a cost-model rival; execution-wise the
        # grouped kernel dominates it whenever either is legal.
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return grouped_lora_matmul(
            x.astype(dtype), base.astype(dtype), a_stack.astype(dtype),
            b_stack.astype(dtype), scale_stack, adapter_idx,
            interpret=interpret, out_dtype=dtype,
        )
    w = dequantize_int8(q, qscale, dtype) if quantized else base.astype(dtype)
    return grouped_lora_reference(
        x.astype(dtype), w, a_stack.astype(dtype), b_stack.astype(dtype),
        scale_stack, adapter_idx,
    ).astype(dtype)
