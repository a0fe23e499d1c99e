"""Pallas TPU kernels: the fused LoRA composite ``x @ W + ((x @ A) @ B) * s``.

ReLoRA keeps a LoRA branch on *every* linear layer for the entire pretraining
run, so this composite is the hottest computation in the stack.  Executed as
three separate ``jnp.matmul``s plus an add (models/lora.py's unfused
reference), the rank-r intermediate ``z = x @ A`` and the full-width LoRA
output ``z @ B`` each round-trip through HBM on every layer.  These kernels
compute the whole composite in one ``pallas_call``: the base tile, the LoRA
factors and the rank-r intermediate are all staged through VMEM, and only the
final ``y`` tile is written back — the LoRAFusion (2510.00206) recipe.

Layout: ``y[M, N] = x[M, K] @ W[K, N] + ((x[M, K] @ A[K, r]) @ B[r, N]) * s``
with f32 accumulation throughout.  Grid is (M/bm, N/bn); each program reads a
(bm, K) activation stripe, a (K, bn) base stripe, the full (K, r) A and a
(r, bn) B stripe.  ``z`` is additionally emitted as a (M, r) secondary output
(one small write, reused by the backward so it is never recomputed).

Two base flavors share the structure:

- :func:`fused_lora_matmul` — dense (f32/bf16) frozen base;
- :func:`fused_lora_matmul_int8` — int8 frozen base, ``dequantize_int8``
  folded into the same kernel (the weight side reads 1 byte/element from HBM,
  like ops/pallas_quant_matmul, but without a second disjoint LoRA path).

Both carry a proper ``custom_vjp``: the backward produces ``dx`` (fused
base + LoRA chain kernel), ``dA``/``dB`` (one accumulating kernel over M
tiles) and ``ds`` — and **nothing for the frozen W**: its cotangent is
symbolically zero (callers pass ``stop_gradient(W)``; ReLoRA never trains the
base between merges).  The int8 variant gives ``scale`` (the quantization
scales) their true gradient and ``q`` a float0 zero, mirroring
ops/pallas_quant_matmul.

``interpret=True`` runs the same kernel bodies on CPU for differential
testing; the TPU path is selected by the dispatcher (ops/lora_dispatch) once
validated per-chip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fused_lora_matmul",
    "fused_lora_matmul_int8",
    "grouped_lora_matmul",
    "grouped_lora_reference",
]

_F32 = jnp.float32


def _largest_divisor(n: int, candidates: Tuple[int, ...] = (256, 128, 64, 32, 16, 8)) -> int:
    """Largest candidate block evenly dividing ``n`` (``n`` itself if none —
    a single-tile grid axis is always legal)."""
    for c in candidates:
        if n % c == 0:
            return c
    return n


# ---------------------------------------------------------------------------
# forward kernels
# ---------------------------------------------------------------------------


def _fused_lora_kernel(x_ref, w_ref, a_ref, b_ref, s_ref, out_ref, z_ref):
    x = x_ref[:].astype(_F32)
    z = jax.lax.dot_general(
        x, a_ref[:].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    z_ref[:] = z  # rank-r intermediate: VMEM-resident; one (bm, r) write
    base = jax.lax.dot_general(
        x, w_ref[:].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    branch = jax.lax.dot_general(
        z, b_ref[:].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    out_ref[:] = (base + branch * s_ref[0, 0]).astype(out_ref.dtype)


def _fused_lora_int8_kernel(x_ref, q_ref, qs_ref, a_ref, b_ref, s_ref, out_ref, z_ref):
    x = x_ref[:].astype(_F32)
    z = jax.lax.dot_general(
        x, a_ref[:].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    z_ref[:] = z
    w = q_ref[:].astype(_F32) * qs_ref[:]  # dequant in VMEM, 1 byte/elem from HBM
    base = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())), preferred_element_type=_F32)
    branch = jax.lax.dot_general(
        z, b_ref[:].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    out_ref[:] = (base + branch * s_ref[0, 0]).astype(out_ref.dtype)


def _forward(bm, bn, interpret, out_dtype, x2, base_operands, a, b, s):
    """Shared pallas_call plumbing; ``base_operands`` is (w,) or (q, qscale).
    Returns (y, z) with z in f32 for the backward."""
    M, K = x2.shape
    r = a.shape[1]
    int8 = len(base_operands) == 2
    N = base_operands[0].shape[1]
    kernel = _fused_lora_int8_kernel if int8 else _fused_lora_kernel
    base_specs = [pl.BlockSpec((K, bn), lambda i, j: (0, j))]
    if int8:
        base_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    y, z = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            *base_specs,
            pl.BlockSpec((K, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            # every j-program writes the same z stripe; last write wins
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), out_dtype),
            jax.ShapeDtypeStruct((M, r), _F32),
        ],
        interpret=interpret,
        name="lora_matmul_fwd",
    )(x2, *base_operands, a, b, s)
    return y, z


# ---------------------------------------------------------------------------
# grouped-adapter forward (multi-tenant serving; no VJP — inference only)
# ---------------------------------------------------------------------------


def _grouped_lora_kernel(idx_ref, x_ref, w_ref, a_ref, b_ref, s_ref, out_ref):
    """One program = one activation row x one N stripe.  The scalar-prefetch
    ``idx_ref`` steered the BlockSpec index maps, so ``a_ref``/``b_ref``/
    ``s_ref`` already hold *this row's* adapter slab — the kernel body is the
    plain fused composite; no gather runs here."""
    del idx_ref  # consumed by the index maps
    x = x_ref[:].astype(_F32)  # (1, K)
    base = jax.lax.dot_general(
        x, w_ref[:].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    z = jax.lax.dot_general(
        x, a_ref[0].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    branch = jax.lax.dot_general(
        z, b_ref[0].astype(_F32), (((1,), (0,)), ((), ())), preferred_element_type=_F32
    )
    out_ref[:] = (base + branch * s_ref[0, 0]).astype(out_ref.dtype)


def _grouped_forward(bn, interpret, out_dtype, idx, x2, w, a_stack, b_stack, s_stack):
    M, K = x2.shape
    S, _, r = a_stack.shape
    N = w.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M, N // bn),
        in_specs=[
            pl.BlockSpec((1, K), lambda m, j, idx: (m, 0)),
            pl.BlockSpec((K, bn), lambda m, j, idx: (0, j)),
            # the block-table mold (ops/attention.paged_decode_attention):
            # the prefetched per-row slot index selects which HBM adapter
            # slab the DMA engine streams — no gathered A/B copy in HBM
            pl.BlockSpec((1, K, r), lambda m, j, idx: (idx[m], 0, 0)),
            pl.BlockSpec((1, r, bn), lambda m, j, idx: (idx[m], 0, j)),
            pl.BlockSpec((1, 1), lambda m, j, idx: (idx[m], 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda m, j, idx: (m, j)),
    )
    return pl.pallas_call(
        _grouped_lora_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="grouped_lora_matmul",
    )(idx, x2, w, a_stack, b_stack, s_stack)


def grouped_lora_reference(x, w, a_stack, b_stack, scale_stack, adapter_idx):
    """Pure-jnp grouped composite: gathers ``A[idx]``/``B[idx]`` per row and
    contracts batched.  The differential oracle for the kernel, and the
    execution path for bases the grouped kernel does not handle (int8,
    off-TPU without interpret)."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K).astype(_F32)
    idx = adapter_idx.reshape(-1)
    a = jnp.take(a_stack, idx, axis=0).astype(_F32)  # (M, K, r)
    b = jnp.take(b_stack, idx, axis=0).astype(_F32)  # (M, r, N)
    s = jnp.take(scale_stack.reshape(-1).astype(_F32), idx, axis=0)  # (M,)
    base = jnp.matmul(x2, w.astype(_F32))
    z = jnp.einsum("mk,mkr->mr", x2, a)
    branch = jnp.einsum("mr,mrn->mn", z, b)
    y = base + branch * s[:, None]
    return y.astype(x.dtype).reshape(*lead, w.shape[1])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret", "out_dtype"))
def grouped_lora_matmul(
    x: jax.Array,
    w: jax.Array,
    a_stack: jax.Array,
    b_stack: jax.Array,
    scale_stack: jax.Array,
    adapter_idx: jax.Array,
    *,
    block_n: Optional[int] = None,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``y[m] = x[m] @ W + ((x[m] @ A[idx[m]]) @ B[idx[m]]) * s[idx[m]]`` for
    a mixed-tenant batch in one ``pallas_call``.

    ``x``: (..., K) activations whose leading dims flatten to M rows;
    ``w``: (K, N) shared frozen base; ``a_stack``: (num_slots, K, r);
    ``b_stack``: (num_slots, r, N); ``scale_stack``: (num_slots,) f32;
    ``adapter_idx``: (M,) int32 row -> slot map fed through scalar prefetch
    (the ``paged_decode_attention`` block-table mold), so only the *distinct*
    adapters a batch touches are ever streamed from HBM.  Grid is
    (M, N/block_n); inference-only — no VJP is defined.
    """
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    S, Ka, r = a_stack.shape
    if Ka != K or w.shape[0] != K:
        raise ValueError(f"contraction mismatch: x K={K}, base {w.shape}, A {a_stack.shape}")
    if b_stack.shape != (S, r, N):
        raise ValueError(
            f"B stack {b_stack.shape} does not match A stack {a_stack.shape} / base N={N}"
        )
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bn = block_n or _largest_divisor(N, (512, 256, 128))
    if N % bn:
        raise ValueError(f"N={N} must tile by block_n={bn}")
    idx = adapter_idx.reshape(-1).astype(jnp.int32)
    if idx.shape[0] != M:
        raise ValueError(
            f"adapter_idx has {idx.shape[0]} rows but x flattens to M={M} "
            "(expand per-batch indices to per-row before the kernel)"
        )
    s = scale_stack.reshape(-1, 1).astype(_F32)
    y = _grouped_forward(bn, interpret, out_dtype, idx, x2, w, a_stack, b_stack, s)
    return y.reshape(*lead, N)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dx_kernel(g_ref, w_ref, a_ref, b_ref, s_ref, dx_ref):
    """dx = g @ W.T + s * (g @ B.T) @ A.T — base and LoRA chain in one pass,
    the rank-r cotangent u = g @ B.T never leaving VMEM."""
    g = g_ref[:].astype(_F32)  # (bm, N)
    u = jax.lax.dot_general(
        g, b_ref[:].astype(_F32), (((1,), (1,)), ((), ())), preferred_element_type=_F32
    )  # (bm, r)
    dx = jax.lax.dot_general(
        g, w_ref[:].astype(_F32), (((1,), (1,)), ((), ())), preferred_element_type=_F32
    )
    dx = dx + s_ref[0, 0] * jax.lax.dot_general(
        u, a_ref[:].astype(_F32), (((1,), (1,)), ((), ())), preferred_element_type=_F32
    )
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _bwd_dx_int8_kernel(g_ref, q_ref, qs_ref, a_ref, b_ref, s_ref, dx_ref):
    g = g_ref[:].astype(_F32)
    w = q_ref[:].astype(_F32) * qs_ref[:]  # (bk, N), dequant in VMEM
    u = jax.lax.dot_general(
        g, b_ref[:].astype(_F32), (((1,), (1,)), ((), ())), preferred_element_type=_F32
    )
    dx = jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())), preferred_element_type=_F32)
    dx = dx + s_ref[0, 0] * jax.lax.dot_general(
        u, a_ref[:].astype(_F32), (((1,), (1,)), ((), ())), preferred_element_type=_F32
    )
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _bwd_dab_kernel(g_ref, x_ref, z_ref, b_ref, s_ref, da_ref, db_ref):
    """dA = s * x.T @ (g @ B.T), dB = s * z.T @ g — both accumulated across
    the sequential M-tile grid into VMEM-resident (K, r)/(r, N) outputs."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        da_ref[:] = jnp.zeros(da_ref.shape, da_ref.dtype)
        db_ref[:] = jnp.zeros(db_ref.shape, db_ref.dtype)

    g = g_ref[:].astype(_F32)  # (bm, N)
    x = x_ref[:].astype(_F32)  # (bm, K)
    z = z_ref[:]  # (bm, r), saved f32 residual
    s = s_ref[0, 0]
    u = jax.lax.dot_general(
        g, b_ref[:].astype(_F32), (((1,), (1,)), ((), ())), preferred_element_type=_F32
    )  # (bm, r)
    da_ref[:] = da_ref[:] + s * jax.lax.dot_general(
        x, u, (((0,), (0,)), ((), ())), preferred_element_type=_F32
    )
    db_ref[:] = db_ref[:] + s * jax.lax.dot_general(
        z, g, (((0,), (0,)), ((), ())), preferred_element_type=_F32
    )


def _backward_dx(bm, interpret, g, base_operands, a, b, s, x_dtype):
    M, N = g.shape
    K = a.shape[0]
    r = a.shape[1]
    bk = _largest_divisor(K)
    int8 = len(base_operands) == 2
    kernel = _bwd_dx_int8_kernel if int8 else _bwd_dx_kernel
    base_specs = [pl.BlockSpec((bk, N), lambda i, k: (k, 0))]
    if int8:
        base_specs.append(pl.BlockSpec((1, N), lambda i, k: (0, 0)))
    return pl.pallas_call(
        kernel,
        grid=(M // bm, K // bk),
        in_specs=[
            pl.BlockSpec((bm, N), lambda i, k: (i, 0)),
            *base_specs,
            pl.BlockSpec((bk, r), lambda i, k: (k, 0)),
            pl.BlockSpec((r, N), lambda i, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
        out_shape=jax.ShapeDtypeStruct((M, K), x_dtype),
        interpret=interpret,
        name="lora_matmul_bwd_dx",
    )(g, *base_operands, a, b, s)


def _backward_dab(bm, interpret, g, x2, z, b, s):
    M, N = g.shape
    K = x2.shape[1]
    r = z.shape[1]
    da, db = pl.pallas_call(
        _bwd_dab_kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((bm, r), lambda i: (i, 0)),
            pl.BlockSpec((r, N), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((K, r), lambda i: (0, 0)),
            pl.BlockSpec((r, N), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, r), _F32),
            jax.ShapeDtypeStruct((r, N), _F32),
        ],
        interpret=interpret,
        name="lora_matmul_bwd_dab",
    )(g, x2, z, b, s)
    return da, db


# ---------------------------------------------------------------------------
# custom VJPs (dense and int8 base)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fused_vjp(bm, bn, interpret, out_dtype, x2, w, a, b, s):
    return _forward(bm, bn, interpret, out_dtype, x2, (w,), a, b, s)[0]


def _fused_fwd(bm, bn, interpret, out_dtype, x2, w, a, b, s):
    y, z = _forward(bm, bn, interpret, out_dtype, x2, (w,), a, b, s)
    return y, (x2, w, a, b, s, z)


def _fused_bwd(bm, bn, interpret, out_dtype, res, g):
    x2, w, a, b, s, z = res
    g32 = g.astype(_F32)
    dx = _backward_dx(bm, interpret, g32, (w,), a, b, s, x2.dtype)
    da, db = _backward_dab(bm, interpret, g32, x2, z, b, s)
    # ds = sum(g ⊙ (z @ B)); one extra matmul, DCE'd when s is a constant
    ds = jnp.sum(
        g32 * jnp.matmul(z, b.astype(_F32)), dtype=_F32
    ).reshape(1, 1)
    # W is the frozen base: its cotangent is symbolically zero by contract
    # (callers pass stop_gradient(W); ReLoRA only updates W at merges)
    dw = jnp.zeros_like(w)
    return dx, dw, da.astype(a.dtype), db.astype(b.dtype), ds


_fused_vjp.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fused_int8_vjp(bm, bn, interpret, out_dtype, x2, q, qscale, a, b, s):
    return _forward(bm, bn, interpret, out_dtype, x2, (q, qscale), a, b, s)[0]


def _fused_int8_fwd(bm, bn, interpret, out_dtype, x2, q, qscale, a, b, s):
    y, z = _forward(bm, bn, interpret, out_dtype, x2, (q, qscale), a, b, s)
    return y, (x2, q, qscale, a, b, s, z)


def _fused_int8_bwd(bm, bn, interpret, out_dtype, res, g):
    x2, q, qscale, a, b, s, z = res
    g32 = g.astype(_F32)
    dx = _backward_dx(bm, interpret, g32, (q, qscale), a, b, s, x2.dtype)
    da, db = _backward_dab(bm, interpret, g32, x2, z, b, s)
    ds = jnp.sum(g32 * jnp.matmul(z, b.astype(_F32)), dtype=_F32).reshape(1, 1)
    # true gradient for the quantization scales (parity: pallas_quant_matmul):
    # d/dqscale[n] = sum_m g[m,n] * (x @ q)[m,n]
    xq = jnp.matmul(x2.astype(_F32), q.astype(_F32))
    dqscale = jnp.sum(g32 * xq, axis=0, keepdims=True).astype(qscale.dtype)
    dq = np.zeros(q.shape, jax.dtypes.float0)
    return dx, dq, dqscale, da.astype(a.dtype), db.astype(b.dtype), ds


_fused_int8_vjp.defvjp(_fused_int8_fwd, _fused_int8_bwd)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def _prepare(x, K_weight, a, b, block_m, block_n, N):
    lead = x.shape[:-1]
    K = x.shape[-1]
    if K != K_weight:
        raise ValueError(f"contraction mismatch: x K={K} vs base K={K_weight}")
    if a.shape[0] != K or b.shape[0] != a.shape[1] or b.shape[1] != N:
        raise ValueError(
            f"LoRA factor shapes {a.shape} x {b.shape} do not match base ({K}, {N})"
        )
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    if block_m is None or block_n is None:
        from relora_tpu.ops.lora_dispatch import plan_blocks

        planned = plan_blocks(M, N)
        if planned is None:
            raise ValueError(
                f"M={M}, N={N} do not tile (pick explicit block_m/block_n or "
                "route through ops.lora_dispatch, which falls back unfused)"
            )
        block_m, block_n = planned
    bm = min(block_m, M)
    bn = min(block_n, N)
    if M % bm or N % bn:
        raise ValueError(f"M={M}, N={N} must tile by ({bm}, {bn})")
    return x2, lead, M, bm, bn


def _as_scale(s) -> jax.Array:
    return jnp.asarray(s, _F32).reshape(1, 1)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret", "out_dtype")
)
def fused_lora_matmul(
    x: jax.Array,
    w: jax.Array,
    a: jax.Array,
    b: jax.Array,
    scale=1.0,
    *,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``x @ W + ((x @ A) @ B) * scale`` in one fused Pallas kernel.

    ``x``: (..., K) activations; ``w``: (K, N) frozen base; ``a``: (K, r);
    ``b``: (r, N); ``scale``: python float or traced scalar (e.g. the
    trainable-scaling ``tanh(lora_s)``).  M (= prod of leading dims) and N
    must tile by block_m/block_n (``None`` auto-plans via
    lora_dispatch.plan_blocks).  Differentiable in x/a/b/scale; the frozen
    ``w`` gets a symbolically-zero cotangent — pass ``stop_gradient(w)``.
    """
    out_dtype = out_dtype or x.dtype
    x2, lead, M, bm, bn = _prepare(x, w.shape[0], a, b, block_m, block_n, w.shape[1])
    y = _fused_vjp(bm, bn, interpret, out_dtype, x2, w, a, b, _as_scale(scale))
    return y.reshape(*lead, w.shape[1])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret", "out_dtype")
)
def fused_lora_matmul_int8(
    x: jax.Array,
    q: jax.Array,
    qscale: jax.Array,
    a: jax.Array,
    b: jax.Array,
    scale=1.0,
    *,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``x @ (q · qscale) + ((x @ A) @ B) * scale`` with the int8 dequant
    folded into the same kernel: the weight side reads 1 byte/element from
    HBM and the rank-r intermediate never leaves VMEM.  ``q``: (K, N) int8;
    ``qscale``: (1, N) f32.  Differentiable in x/a/b/scale (+ the true
    qscale gradient, parity with ops.pallas_quant_matmul); ``q`` is int8 and
    gets a float0 zero."""
    out_dtype = out_dtype or x.dtype
    x2, lead, M, bm, bn = _prepare(x, q.shape[0], a, b, block_m, block_n, q.shape[1])
    y = _fused_int8_vjp(
        bm, bn, interpret, out_dtype, x2, q, qscale, a, b, _as_scale(scale)
    )
    return y.reshape(*lead, q.shape[1])
