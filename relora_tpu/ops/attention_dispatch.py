"""Shape-aware dispatch for cached/paged attention.

The serving stack has three ways to attend a query against the KV cache,
and the right one depends on ``(B, S, S_kv, heads, page_size)`` the same
way the LoRA composite depends on (M, K, N, r) — *Run LoRA Run* roofline
territory, in the :mod:`relora_tpu.ops.lora_dispatch` mold:

- **naive** — :func:`relora_tpu.ops.attention.paged_cached_attention` /
  ``cached_attention``: gather (paged) then masked einsum softmax einsum.
  Always available, any S, the differential oracle.  Pays HBM for the
  gathered cache copy *and* the ``(B, heads, S, S_kv)`` score matrix.
- **flash** — the Pallas flash kernel via ``dot_product_attention``:
  O(seq) memory for the pure causal self-attention case (prefill from
  scratch, S == S_kv, 128-aligned).  Not applicable to cache-visibility
  masking, so it never serves the paged pool — it is modeled here so one
  cost table ranks every attention arm the repo has.
- **paged_decode** — :func:`relora_tpu.ops.attention.paged_decode_attention`:
  small-S decode straight out of the page pool through the block table —
  S == 1 plain decode or the speculative-decoding ``(B, K+1)`` verify
  window (``PAGED_DECODE_MAX_S`` bounds it; long chunked-prefill shapes
  stay naive) — one launch, no gathered copy, no score matrix, optional
  in-VMEM int8 dequant.  TPU-only for auto (the interpreter is a
  correctness tool).

:func:`choose_arm` ranks arms with the same ``t(arm) = max(bytes/BW,
flops/peak) + launches·t_launch`` roofline over static python ints
(``lru_cache``-d — no tracing, no retraces).  :func:`paged_attention` is
the execution entry used by the model cache-write path; forcing ``arm=``
bypasses the cost model (how CPU tests pin each arm).

:func:`choose_training_arm` is the *training/prefill* half of the same
table: pure causal self-attention (S == S_kv) as the model forward runs it
under autodiff, where the cost of an arm is forward **plus backward** —
the backward pays ~2× the forward matmul FLOPs, re-materializes whatever
the remat policy dropped, and (for the score-materializing arms) moves the
``S × S`` matrix through HBM several more times.  ``dot_product_attention``'s
``impl="auto"`` resolves through it, which is what retired the
``RELORA_TPU_PALLAS_MIN_SEQ`` sequence-length threshold.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from relora_tpu.ops.attention import (
    flash_block_size,
    packed_paged_attention,
    paged_cached_attention,
    paged_decode_attention,
)

# Shared roofline constants (see lora_dispatch for provenance: only ratios
# matter for ranking, so v5e numbers rank correctly on CPU too).
from relora_tpu.ops.lora_dispatch import (
    HBM_BW_BYTES,
    LAUNCH_OVERHEAD_S,
    PEAK_FLOPS,
)
from relora_tpu.utils.logging import get_logger, info_once

logger = get_logger(__name__)

__all__ = [
    "ARMS",
    "TRAIN_ARMS",
    "estimate_arm_times",
    "estimate_training_arm_times",
    "choose_arm",
    "choose_training_arm",
    "paged_attention",
    "packed_attention",
]

ARMS: Tuple[str, ...] = ("naive", "flash", "paged_decode", "packed")

#: largest query length the fused paged kernel serves: covers plain decode
#: (S=1) and every speculative verify window (K+1 for K <= 15) while the
#: per-row VMEM state (N*S rows of online-softmax scratch) stays small;
#: chunked prefill at the default chunk_size=64 keeps the naive arm
PAGED_DECODE_MAX_S = 16

#: arms a training forward can execute (attention.dot_product_attention
#: impls; "flash" maps to impl="pallas" there)
TRAIN_ARMS: Tuple[str, ...] = ("naive", "xla", "flash")

_F32 = 4  # score/softmax math is f32 in every arm


@functools.lru_cache(maxsize=4096)
def estimate_arm_times(
    B: int,
    S: int,
    S_kv: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    page_size: int,
    kv_bytes: int = 2,
    act_bytes: int = 4,
) -> Dict[str, float]:
    """Modeled seconds per arm for one attention of the given shape.

    ``kv_bytes`` is the *stored* cache width (2 for bf16 pools, 1 for int8
    codes), ``act_bytes`` the activation width of q/out.  The model is
    deliberately coarse — decode attention is bandwidth-bound, so what
    matters is how many times each arm moves the ``S_kv`` cache tokens and
    the ``S × S_kv`` score matrix through HBM:

    - naive: pool read + gathered-copy write + gathered-copy read (3× the
      cache bytes; the paged gather materializes), scores written and
      re-read twice (logits→softmax→probs) at f32, ~6 dispatched ops.
    - flash: q/k/v/out each moved once, no score matrix, one launch.
    - paged_decode: pool + scales moved once, q/out once, no gathered copy,
      no score matrix, one launch.
    """

    def roofline(nbytes: float, flops: float, launches: int) -> float:
        return max(nbytes / HBM_BW_BYTES, flops / PEAK_FLOPS) + launches * LAUNCH_OVERHEAD_S

    qo_bytes = 2.0 * B * S * heads * head_dim * act_bytes  # q read + out write
    cache_bytes = 2.0 * B * S_kv * kv_heads * head_dim * kv_bytes  # K and V
    scale_bytes = 2.0 * B * (S_kv / max(page_size, 1)) * kv_heads * _F32
    score_bytes = float(B) * heads * S * S_kv * _F32
    flops = 4.0 * B * S * S_kv * heads * head_dim  # QK^T + PV

    gathered_f32 = 2.0 * B * S_kv * kv_heads * head_dim * _F32
    dequant_extra = gathered_f32 if kv_bytes == 1 else 0.0
    naive = roofline(
        qo_bytes
        + cache_bytes  # pool read (gather source)
        + 2.0 * gathered_f32  # gathered copy written then re-read (f32 math)
        + dequant_extra  # int8: separate dequant pass writes f32 copy again
        + 4.0 * score_bytes,  # logits w+r, probs w+r
        flops,
        6,
    )

    flash = roofline(qo_bytes + cache_bytes, flops, 1)

    paged_decode = roofline(qo_bytes + cache_bytes + scale_bytes, flops, 1)

    # packed mixed-batch: per-token page streaming — identical HBM traffic
    # shape to paged_decode at (B=T packed tokens, S=1), one launch for the
    # whole mixed batch instead of one per entry kind (the win the dispatch
    # count in serve metrics measures, not this table)
    packed = roofline(qo_bytes + cache_bytes + scale_bytes, flops, 1)

    return {
        "naive": naive,
        "flash": flash,
        "paged_decode": paged_decode,
        "packed": packed,
    }


@functools.lru_cache(maxsize=4096)
def choose_arm(
    B: int,
    S: int,
    S_kv: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    page_size: int,
    kv_bytes: int = 2,
    fused_available: bool = True,
    allow: Tuple[str, ...] = ARMS,
) -> str:
    """Pick the cheapest *applicable* arm under the roofline model.

    Applicability is structural, not modeled: ``paged_decode`` serves
    small-S queries only (``S <= PAGED_DECODE_MAX_S`` — single-token decode
    and the speculative verify window; its per-row VMEM softmax state
    scales with heads×S); ``flash`` only for pure causal self-attention
    with 128-aligned lengths (S == S_kv, tileable) — the cache-visibility
    mask of chunked prefill is not expressible in it.
    ``fused_available=False`` (non-TPU backend, or caller opt-out) strikes
    both Pallas arms; ``allow`` restricts the candidate set (tests pin
    arms with it).  Pure python over static ints — trace-safe.
    """
    times = estimate_arm_times(
        B, S, S_kv, heads, kv_heads, head_dim, page_size, kv_bytes
    )
    candidates = [arm for arm in allow if arm in ARMS]
    if S > PAGED_DECODE_MAX_S or not fused_available:
        candidates = [a for a in candidates if a != "paged_decode"]
    # the packed arm reads per-token row/position maps: callers rank it with
    # (B = packed tokens, S = 1); any other query shape cannot address it
    if S != 1 or not fused_available:
        candidates = [a for a in candidates if a != "packed"]
    if S != S_kv or flash_block_size(S, S_kv) is None or not fused_available:
        candidates = [a for a in candidates if a != "flash"]
    if not candidates:
        return "naive"
    return min(candidates, key=lambda arm: times[arm])


@functools.lru_cache(maxsize=4096)
def estimate_training_arm_times(
    B: int,
    S: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    act_bytes: int = 2,
    with_backward: bool = True,
) -> Dict[str, float]:
    """Modeled seconds per arm for one *training* causal self-attention
    (S == S_kv), forward + backward.

    The decode table (:func:`estimate_arm_times`) ranks bandwidth-bound
    single-token shapes; training shapes are compute-heavy and pay the
    backward too, which shifts the balance:

    - matmul FLOPs: 4·B·S²·h·d forward; the backward's dq/dk/dv matmuls
      are ~2× that, and under the remat policies we train with (``dots`` /
      ``dots_narrow`` recompute batched dots) the probs are recomputed once
      more — modeled as a 3.5× forward multiplier for every arm.  The flash
      kernel's grid skips fully-masked causal blocks, so its effective
      FLOPs are ~half the dense count; XLA/naive compute the full square.
    - HBM: every arm moves q/k/v/out once forward and ~2× more backward
      (reads + grads).  The score-materializing arms additionally stream
      the ``B·h·S²`` matrix — twice forward (probs write + PV read) and
      ~twice backward for ``xla`` at activation width, double that and at
      f32 for ``naive`` (logits→softmax→probs each written and re-read).
      ``flash`` keeps scores in VMEM, forward and backward.
    - launches: naive is ~6 fused ops forward + ~8 backward; the XLA fused
      path ~2 + 4; flash is 1 forward + 2 backward kernels (dq, dkv).
    """

    def roofline(nbytes: float, flops: float, launches: int) -> float:
        return max(nbytes / HBM_BW_BYTES, flops / PEAK_FLOPS) + launches * LAUNCH_OVERHEAD_S

    bwd_flops_mult = 3.5 if with_backward else 1.0
    bwd_io_mult = 3.0 if with_backward else 1.0

    io_bytes = (
        2.0 * B * S * heads * head_dim * act_bytes  # q + out
        + 2.0 * B * S * kv_heads * head_dim * act_bytes  # k + v
    )
    score_bytes = float(B) * heads * S * S  # × itemsize below
    flops_full = 4.0 * B * S * S * heads * head_dim
    flops_causal = flops_full / 2.0

    naive = roofline(
        bwd_io_mult * io_bytes * 2  # f32 math: inputs upcast
        + (8.0 if with_backward else 4.0) * score_bytes * _F32,
        bwd_flops_mult * flops_full,
        14 if with_backward else 6,
    )
    xla = roofline(
        bwd_io_mult * io_bytes + (4.0 if with_backward else 2.0) * score_bytes * act_bytes,
        bwd_flops_mult * flops_full,
        6 if with_backward else 2,
    )
    flash = roofline(
        bwd_io_mult * io_bytes,
        bwd_flops_mult * flops_causal,
        3 if with_backward else 1,
    )
    return {"naive": naive, "xla": xla, "flash": flash}


@functools.lru_cache(maxsize=4096)
def choose_training_arm(
    B: int,
    S: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    act_bytes: int = 2,
    with_backward: bool = True,
    fused_available: bool = True,
    allow: Tuple[str, ...] = TRAIN_ARMS,
) -> str:
    """Cheapest applicable arm for a training/prefill causal self-attention.

    Applicability mirrors :func:`choose_arm`: ``flash`` needs the Pallas
    kernel (TPU, 128-aligned tileable S — :func:`flash_block_size`);
    ``fused_available=False`` strikes it.  ``xla`` and ``naive`` always
    apply.  Pure python over static trace-time ints, so the per-shape
    choice is free and can never retrace.
    """
    times = estimate_training_arm_times(
        B, S, heads, kv_heads, head_dim, act_bytes, with_backward
    )
    candidates = [arm for arm in allow if arm in TRAIN_ARMS]
    if not fused_available or flash_block_size(S, S) is None:
        candidates = [a for a in candidates if a != "flash"]
    if not candidates:
        return "xla"
    return min(candidates, key=lambda arm: times[arm])


def _note_traced_arm(entry: str, arm: str, q_shape, kv_dtype, interpret: bool) -> None:
    """Say once which arm a serving program was traced with (trace time only,
    not in the compiled step), so an operator — and chip_smoke.py — can read
    from the server's log whether decode runs the compiled Pallas kernel, the
    interpreter or the naive arm."""
    info_once(
        logger,
        f"{entry} traced: arm={arm} q_shape={tuple(q_shape)} "
        f"kv_dtype={jnp.dtype(kv_dtype)} interpret={interpret}",
    )


def paged_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    arm: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Attend ``q`` against the page pool via the chosen arm.

    The execution entry point used by the model cache-write path
    (models/llama.attend_with_paged_cache).  ``arm="auto"`` consults
    :func:`choose_arm` with the static trace-time shapes; long chunked
    prefill resolves to the naive arm, while single-token decode and the
    small-S speculative verify window (T <= PAGED_DECODE_MAX_S) take the
    fused kernel on TPU.  Explicit ``arm=`` bypasses the model; the flash
    arm is not servable from a pool and is rejected here.
    """
    if arm not in ("auto", "naive", "paged_decode"):
        raise ValueError(
            f"unknown/unservable arm {arm!r}; expected auto|naive|paged_decode"
        )
    B, T, N, H = q.shape
    _, page_size, n_kv, _ = pool_k.shape
    S_kv = block_tables.shape[1] * page_size
    if arm == "auto":
        fused_ok = jax.default_backend() == "tpu"
        arm = choose_arm(
            B, T, S_kv, N, n_kv, H, page_size,
            jnp.dtype(pool_k.dtype).itemsize,
            fused_available=fused_ok, allow=("naive", "paged_decode"),
        )
    if arm == "paged_decode":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        _note_traced_arm("paged_attention", arm, q.shape, pool_k.dtype, interpret)
        return paged_decode_attention(
            q, pool_k, pool_v, block_tables, positions,
            k_scale=k_scale, v_scale=v_scale, scale=scale, window=window, sink=sink,
            interpret=interpret,
        )
    _note_traced_arm("paged_attention", arm, q.shape, pool_k.dtype, False)
    return paged_cached_attention(
        q, pool_k, pool_v, block_tables, positions,
        k_scale=k_scale, v_scale=v_scale, scale=scale, window=window, sink=sink,
    )


def packed_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    block_tables: jax.Array,
    row_map: jax.Array,
    positions: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    arm: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Attend a token-major packed mixed batch against the page pool.

    ``q`` is ``(1, T, N, H)`` — T packed tokens from a mix of decode rows,
    speculative verify windows, and prefill chunks — with ``row_map`` ``(T,)``
    selecting each token's row of ``block_tables`` ``(R, W)`` and
    ``positions`` ``(T,)`` its absolute position.  On TPU the fused
    :func:`relora_tpu.ops.attention.packed_paged_attention` kernel serves it
    in one launch; elsewhere (or with ``arm="naive"``) each token attends
    through its own gathered table as a batch row of
    :func:`relora_tpu.ops.attention.paged_cached_attention` — same masked
    einsum math as the sequential decode path, which is what the
    packed-vs-sequential token-parity tests lean on.
    """
    if arm not in ("auto", "naive", "packed"):
        raise ValueError(f"unknown/unservable arm {arm!r}; expected auto|naive|packed")
    B, T, N, H = q.shape
    if B != 1:
        raise ValueError(f"packed attention is token-major: expected B=1, got {B}")
    _, page_size, n_kv, _ = pool_k.shape
    S_kv = block_tables.shape[1] * page_size
    rm = row_map.reshape(T)
    pos = positions.reshape(T)
    if arm == "auto":
        fused_ok = jax.default_backend() == "tpu"
        arm = choose_arm(
            T, 1, S_kv, N, n_kv, H, page_size,
            jnp.dtype(pool_k.dtype).itemsize,
            fused_available=fused_ok, allow=("naive", "packed"),
        )
    if arm == "packed":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        _note_traced_arm("packed_attention", arm, q.shape, pool_k.dtype, interpret)
        return packed_paged_attention(
            q, pool_k, pool_v, block_tables, rm, pos,
            k_scale=k_scale, v_scale=v_scale, scale=scale, interpret=interpret,
        )
    _note_traced_arm("packed_attention", arm, q.shape, pool_k.dtype, False)
    # naive: tokens become batch rows, each with its own table — (T, 1, N, H)
    # queries against (T, W) per-token tables, then back to token-major
    token_tables = jnp.take(block_tables, rm.astype(jnp.int32), axis=0)
    out = paged_cached_attention(
        q.reshape(T, 1, N, H), pool_k, pool_v, token_tables, pos.reshape(T, 1),
        k_scale=k_scale, v_scale=v_scale, scale=scale,
    )
    return out.reshape(1, T, N, H)
