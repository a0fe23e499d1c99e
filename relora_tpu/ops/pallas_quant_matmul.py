"""Pallas TPU kernel: matmul against an int8 frozen base, dequantizing
inside the tile loop.

The point of int8 base storage (ops/quant.py) is HBM: with a plain
``dequantize → matmul``, XLA may materialize the dequantized kernel, moving
f32/bf16 bytes through HBM anyway.  This kernel keeps the weight int8 all the
way into VMEM and dequantizes per tile right before the MXU dot — the weight
side of the matmul reads 1 byte/element from HBM, a 4× traffic cut vs f32.

Layout: ``y[M, N] = x[M, K] @ (q[K, N] · scale[1, N])`` with f32
accumulation.  Grid is (M/bm, N/bn); each program reads an (bm, K) activation
stripe and a (K, bn) int8 weight stripe.  Block sizes respect the v5e tiling
constraints (last dim 128, second-to-last a multiple of 8).

``interpret=True`` runs the same kernel on CPU for differential testing; the
TPU path is opt-in (RELORA_TPU_PALLAS_QUANT=1) until validated per-chip.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# (M, K, N) shapes already warned about the unfused backward — the log should
# fire once per shape at trace time, not on every step (same pattern as
# models/lora._NF4_FALLBACK_WARNED)
_BWD_FALLBACK_WARNED: set = set()


def _dequant_matmul_kernel(x_ref, q_ref, scale_ref, out_ref):
    x = x_ref[:]
    w = q_ref[:].astype(jnp.float32) * scale_ref[:]  # dequant in VMEM
    out_ref[:] = jax.lax.dot_general(
        x.astype(jnp.float32),
        w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


def _pallas_forward(bm, bn, interpret, out_dtype, x2, q, scale):
    M, K = x2.shape
    N = q.shape[1]
    return pl.pallas_call(
        _dequant_matmul_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="quant_matmul",
    )(x2, q, scale)


# pallas_call has no transpose rule, so the kernel gets an explicit VJP.
# Only the forward benefits from keeping the weight int8 into VMEM; the
# backward runs the plain dequantize-then-matmul (XLA fuses it) — dx is a
# bandwidth-bound (M,N)@(N,K) contraction where the weight side is read once
# anyway.  q is int8 (tangent dtype float0); scale gets its true gradient so
# jax.grad stays correct even though the frozen base never trains.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _dequant_matmul_vjp(bm, bn, interpret, out_dtype, x2, q, scale):
    return _pallas_forward(bm, bn, interpret, out_dtype, x2, q, scale)


def _dequant_matmul_fwd(bm, bn, interpret, out_dtype, x2, q, scale):
    return _pallas_forward(bm, bn, interpret, out_dtype, x2, q, scale), (x2, q, scale)


def _dequant_matmul_bwd(bm, bn, interpret, out_dtype, res, g):
    x2, q, scale = res
    key = (x2.shape[0], q.shape[0], q.shape[1])
    if key not in _BWD_FALLBACK_WARNED:
        # once per shape at trace time: the backward is NOT the fused int8
        # kernel — it dequantizes and runs plain matmuls, so per-kernel
        # benchmarks must not attribute the f32-traffic backward cost to the
        # pallas forward (fused fwd+bwd lives in ops/pallas_lora_matmul)
        _BWD_FALLBACK_WARNED.add(key)
        logging.getLogger(__name__).info(
            "dequant_matmul backward for (M=%d, K=%d, N=%d) takes the "
            "dequantize-then-matmul fallback (pallas forward only)",
            *key,
        )
    g32 = g.astype(jnp.float32)
    w = q.astype(jnp.float32) * scale  # (K, N)
    dx = jnp.matmul(g32, w.T).astype(x2.dtype)
    # d/dscale[n] sum_m g[m,n] * (x @ q)[m,n]
    xq = jnp.matmul(x2.astype(jnp.float32), q.astype(jnp.float32))
    dscale = jnp.sum(g32 * xq, axis=0, keepdims=True).astype(scale.dtype)
    dq = np.zeros(q.shape, jax.dtypes.float0)
    return dx, dq, dscale


_dequant_matmul_vjp.defvjp(_dequant_matmul_fwd, _dequant_matmul_bwd)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret", "out_dtype"))
def dequant_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``x @ (q * scale)`` with the dequant fused into the kernel.

    ``x``: (..., M, K) activations; ``q``: (K, N) int8; ``scale``: (1, N) f32.
    M and N must tile by block_m/block_n (pad upstream if not).
    Differentiable: custom VJP routes the backward through the plain
    dequantize-then-matmul path (pallas_call itself has no transpose rule).
    """
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-2] if x.ndim > 2 else ()
    x2 = x.reshape(-1, x.shape[-1]) if x.ndim != 2 else x
    M, K = x2.shape
    Kq, N = q.shape
    if K != Kq:
        raise ValueError(f"contraction mismatch: x K={K} vs q K={Kq}")
    bm = min(block_m, M)
    bn = min(block_n, N)
    if M % bm or N % bn:
        raise ValueError(f"M={M}, N={N} must tile by ({bm}, {bn})")

    out = _dequant_matmul_vjp(bm, bn, interpret, out_dtype, x2, q, scale)
    if x.ndim != 2:
        out = out.reshape(*lead, x.shape[-2], N)
    return out
