"""Causal attention with selectable backends.

The reference calls ``F.scaled_dot_product_attention(..., is_causal=True)``
and deliberately ignores the padding mask (modeling_llama.py:221-224,
modeling_pythia.py:262-270).  Here the same contract — causal, no padding
mask — is served by three interchangeable implementations:

- ``xla``     — ``jax.nn.dot_product_attention``: XLA fuses this into an
  efficient (flash-like) kernel on TPU; the safe default everywhere.
- ``pallas``  — the Pallas TPU flash-attention kernel
  (jax.experimental.pallas.ops.tpu.flash_attention) for long sequences;
  requires TPU and MXU-friendly head dims.
- ``naive``   — explicit softmax(QKᵀ)V in f32, the differential-testing
  oracle.

All take/return ``(batch, seq, heads, head_dim)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from relora_tpu.utils.logging import get_logger, info_once

logger = get_logger(__name__)


def _expand_grouped_kv(q, k, v):
    """Materialize grouped K/V up to the full query head count (for impls
    that need equal head counts), validating divisibility at the boundary."""
    n, n_kv = q.shape[2], k.shape[2]
    if n == n_kv:
        return k, v
    if n % n_kv:
        raise ValueError(f"num_heads={n} must divide by kv_heads={n_kv}")
    rep = n // n_kv
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _grouped_equal_heads_call(q, k, v, equal_heads_fn) -> jax.Array:
    """Apply an equal-head-count attention kernel to grouped-query inputs
    WITHOUT materializing expanded K/V: one call per group slice, every
    slice reading the same K/V buffers.  ``g`` is a small static int, so the
    unrolled loop adds g-1 kernel launches, not g× K/V HBM."""
    n, n_kv = q.shape[2], k.shape[2]
    if n == n_kv:
        return equal_heads_fn(q, k, v)
    if n % n_kv:
        raise ValueError(f"num_heads={n} must divide by kv_heads={n_kv}")
    g = n // n_kv
    B, S, _, H = q.shape
    qg = q.reshape(B, S, n_kv, g, H)
    outs = [equal_heads_fn(qg[:, :, :, j, :], k, v) for j in range(g)]
    return jnp.stack(outs, axis=3).reshape(B, S, n, H)


def _naive_attention(q, k, v, *, causal: bool, scale: float) -> jax.Array:
    B, S, N, H = q.shape
    n_kv = k.shape[2]
    qg = q.astype(jnp.float32).reshape(B, S, n_kv, N // n_kv, H)
    logits = (
        jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32)) * scale
    )
    if causal:
        mask = jnp.tril(jnp.ones((S, k.shape[1]), dtype=bool))
        logits = jnp.where(mask[None, None, None, :, :], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, N, H).astype(q.dtype)


def flash_block_size(S: int, S_kv: int) -> Optional[int]:
    """Tile size for the pallas flash kernel, or None when the lengths are
    sub-tile / non-128-aligned and the kernel can't apply.  The kernel's
    _verify_block requires exact divisibility (e.g. S=768 with block 512 is
    rejected), so this picks the largest of 512/256/128 dividing both."""
    if S < 128 or S_kv < 128 or S % 128 or S_kv % 128:
        return None
    return next(b for b in (512, 256, 128) if S % b == 0 and S_kv % b == 0)


def _pallas_attention(q, k, v, *, causal: bool, scale: float) -> jax.Array:
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    blk = flash_block_size(q.shape[1], k.shape[1])
    if blk is None:
        # e.g. the (1, 8) param-init trace: XLA's fused path is fine at
        # these sizes
        return jax.nn.dot_product_attention(
            q, k, v, scale=scale, is_causal=causal
        )
    sizes = BlockSizes(
        block_q=blk,
        block_k_major=blk,
        block_k=blk,
        block_b=1,
        block_q_major_dkv=blk,
        block_k_major_dkv=blk,
        block_k_dkv=blk,
        block_q_dkv=blk,
        block_k_major_dq=blk,
        block_k_dq=blk,
        block_q_dq=blk,
    )

    def equal_heads(qq, kk, vv):
        # the pallas kernel wants (batch, heads, seq, head_dim)
        qt, kt, vt = (x.swapaxes(1, 2) for x in (qq, kk, vv))
        out = flash_attention(
            qt, kt, vt, causal=causal, sm_scale=scale, block_sizes=sizes
        )
        return out.swapaxes(1, 2)

    def local(qq, kk, vv):
        return _grouped_equal_heads_call(qq, kk, vv, equal_heads)

    mesh = _multi_device_mesh()
    if mesh is None:
        return local(q, k, v)
    # GSPMD cannot partition a Mosaic kernel ("wrap the call in a shard_map"):
    # run it per shard over the layout the activations already have — batch
    # over data x fsdp, heads over tensor
    if not flash_partitionable(q.shape[0], q.shape[2], k.shape[2]):
        raise ValueError(
            f"pallas attention under mesh {dict(mesh.shape)} needs batch "
            f"{q.shape[0]} divisible by data*fsdp and heads {q.shape[2]}/"
            f"{k.shape[2]} by tensor"
        )
    from relora_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS, TENSOR_AXIS

    spec = jax.sharding.PartitionSpec((DATA_AXIS, FSDP_AXIS), None, TENSOR_AXIS, None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def _multi_device_mesh():
    """The current mesh when it spans more than one device, else None."""
    from relora_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    return mesh if mesh is not None and mesh.size > 1 else None


def flash_partitionable(batch: int, heads: int, kv_heads: int) -> bool:
    """Whether the pallas flash arm can run under the current mesh: always on
    one device; on a mesh only where the per-shard split of
    :func:`_pallas_attention` is exact (e.g. not the batch-1 init trace)."""
    mesh = _multi_device_mesh()
    if mesh is None:
        return True
    n_batch = mesh.shape["data"] * mesh.shape["fsdp"]
    n_t = mesh.shape["tensor"]
    return (
        mesh.shape["sequence"] == 1
        and batch % n_batch == 0
        and heads % n_t == 0
        and kv_heads % n_t == 0
    )


def cached_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,
    *,
    scale: Optional[float] = None,
    key_positions: Optional[jax.Array] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """Masked decode attention against a fixed-capacity KV cache.

    ``q`` is ``(B, T, N, H)`` — T is 1 for single-token decode, up to S for
    prefill — holding queries at absolute positions ``positions`` ``(B, T)``
    (or ``(1, T)``, broadcast over batch).  ``k``/``v`` are the cache buffers
    ``(B, C, N_kv, H)`` with capacity C (V's head size may differ from K's);
    entry ``j`` of the cache is visible
    to the query at position ``p`` iff ``j <= p``, which is simultaneously
    the causal mask (prefill), the length mask that hides not-yet-written
    (or stale, from an evicted slot) cache tail entries (decode), and the
    pad mask for right-padded prompts.

    ``key_positions`` ``(B, C)`` says which position each entry holds where
    that is not its index (a ring: :func:`ring_key_positions`); a negative
    one is never visible.  ``window`` hides entries at ``p - window`` and
    before.  ``sink`` ``(N,)`` is one more score per query head that joins
    the softmax's denominator and carries no value.

    Math in f32 like the ``naive`` oracle: decode is memory-bound — the
    arithmetic is negligible next to streaming the cache from HBM — so
    there is no reason to give up softmax accuracy.  Grouped-query K/V
    attends without materializing the head expansion.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, T, N, H = q.shape
    C, n_kv = k.shape[1], k.shape[2]
    if N % n_kv:
        raise ValueError(f"num_heads={N} must divide by kv_heads={n_kv}")
    qg = q.astype(jnp.float32).reshape(B, T, n_kv, N // n_kv, H)
    logits = jnp.einsum("btkgh,bskh->bkgts", qg, k.astype(jnp.float32)) * scale
    if key_positions is None:
        visible = jnp.arange(C)[None, None, :] <= positions[..., None]  # (B|1, T, C)
    else:
        kp = key_positions[:, None, :]
        visible = (kp <= positions[..., None]) & (kp >= 0)
    if window is not None:
        kp = jnp.arange(C)[None, None, :] if key_positions is None else key_positions[:, None, :]
        visible = visible & (kp > positions[..., None] - window)
    logits = jnp.where(
        visible[:, None, None, :, :], logits, jnp.finfo(jnp.float32).min
    )
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        # the sink is a column of the softmax that no value stands behind
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, n_kv, N // n_kv, 1, 1), (*logits.shape[:-1], 1)
        )
        probs = jax.nn.softmax(jnp.concatenate([logits, col], axis=-1), axis=-1)[..., :-1]
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, T, N, v.shape[-1]).astype(q.dtype)


def ring_key_positions(positions: jax.Array, table_width: int, page_size: int) -> jax.Array:
    """The position each gathered entry of a ring table holds, ``(B, W*ps)``:
    entry ``e`` of the table holds the newest logical page ``<=`` the row's
    last written one that is ``e`` modulo the width — negative (never
    visible) where the row has not reached that entry yet.  For a table no
    row has wrapped (a paged one) these are the entries' own indices where
    written and negative past them."""
    newest = jnp.max(positions, axis=-1, keepdims=True) // page_size  # (B, 1)
    entry = jnp.arange(table_width)[None, :]
    page = newest - (newest - entry) % table_width  # (B, W)
    return (page[:, :, None] * page_size + jnp.arange(page_size)[None, None, :]).reshape(
        positions.shape[0], table_width * page_size
    )


def dequantize_gathered_pages(
    kv: jax.Array, scales: jax.Array, block_tables: jax.Array
) -> jax.Array:
    """Dequantize a :func:`gather_kv_pages` result of int8 codes back to f32.

    ``kv`` is the gathered ``(B, W * page_size, n_kv, H)`` int8 view,
    ``scales`` the per-``(page, kv_head)`` f32 scales ``(num_pages, n_kv)``
    (see ops/quant.quantize_kv_page), gathered here through the same
    ``block_tables`` so each token row picks up its page's scale.  Null /
    unwritten pages carry zero codes, so whatever scale they gather
    dequantizes to exactly 0.0 — masked off downstream either way.
    """
    B, S, n_kv, H = kv.shape
    W = block_tables.shape[1]
    ps = S // W
    s = jnp.take(scales, block_tables, axis=0)  # (B, W, n_kv)
    s = jnp.broadcast_to(s[:, :, None, :], (B, W, ps, n_kv)).reshape(B, S, n_kv)
    return kv.astype(jnp.float32) * s[..., None]


def gather_kv_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Gather a per-row contiguous K/V view out of a shared page pool.

    ``pool`` is ``(num_pages, page_size, N_kv, H)`` — one buffer shared by
    every request — and ``block_tables`` is ``(B, W)`` mapping each row's
    logical page index (``position // page_size``) to a pool page.  Returns
    ``(B, W * page_size, N_kv, H)`` in logical token order.  Padded table
    entries point at the null page (paging.NULL_PAGE); whatever garbage
    lives there is masked off downstream by the ``j <= position``
    visibility rule, exactly like unwritten tail entries of the contiguous
    cache.
    """
    pages = jnp.take(pool, block_tables, axis=0)  # (B, W, page_size, N_kv, H)
    B, W, ps = pages.shape[:3]
    return pages.reshape(B, W * ps, pages.shape[3], pages.shape[4])


def paged_cached_attention(
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
) -> jax.Array:
    """``cached_attention`` against a paged K/V pool.

    The gather reconstructs each row's logical cache at full table width
    ``W * page_size`` — with ``W = cache_size / page_size`` that is exactly
    the contiguous path's contraction length ``C``, and masked entries get
    softmax probability exactly 0.0 (their f32-min logits underflow the
    shifted exp), so the result is bitwise-identical to attending the
    contiguous cache.  That equality is what lets the paged scheduler pin
    token parity against the contiguous engine.  Width-bucketing the gather
    to the pages actually used (a read-bandwidth win for short requests in
    a long-capacity pool) is future work and would trade that bitwise
    guarantee for an allclose one.

    With ``k_scale``/``v_scale`` (per-``(page, kv_head)`` f32, from
    ops/quant.quantize_kv_page) the pool holds int8 codes; the gathered view
    is dequantized to f32 before attending.  This is the differential
    oracle for the fused :func:`paged_decode_attention` kernel — same math,
    but it materializes both the gathered cache and the score matrix in HBM.

    With ``window`` the table may be a ring (logical page ``p`` in entry
    ``p % W``): each gathered entry's position comes from
    :func:`ring_key_positions`, and only the last ``window`` are visible.
    """
    k = gather_kv_pages(pool_k, block_tables)
    v = gather_kv_pages(pool_v, block_tables)
    if k_scale is not None:
        k = dequantize_gathered_pages(k, k_scale, block_tables)
    if v_scale is not None:
        v = dequantize_gathered_pages(v, v_scale, block_tables)
    key_positions = None
    if window is not None:
        B = q.shape[0]
        key_positions = ring_key_positions(
            jnp.broadcast_to(positions, (B, positions.shape[-1])), block_tables.shape[1], pool_k.shape[1]
        )
    return cached_attention(
        q, k, v, positions, scale=scale, key_positions=key_positions, window=window, sink=sink
    )


# ---------------------------------------------------------------------------
# Fused paged-decode kernel: pool -> output in one launch, no HBM gather
# ---------------------------------------------------------------------------


#: tokens of one row that a step of the paged-decode kernel attends: the
#: step's pages are one (tokens, n_kv, H) operand of the score product
_DECODE_STEP_TOKENS = 128
#: VMEM the K and V buffers of a step may hold, two buffers each
_DECODE_STEP_VMEM_BYTES = 4 * 1024 * 1024


def decode_pages_per_step(
    page_size: int, n_kv: int, head_dim: int, itemsize: int, table_width: int
) -> int:
    """Table entries ``P`` one step of :func:`paged_decode_attention` walks:
    as many pages as make ``_DECODE_STEP_TOKENS`` tokens, fewer where K and V
    buffers of that many pages, two each and padded to the dtype's
    ``(sublane, 128)`` tile, would pass ``_DECODE_STEP_VMEM_BYTES``, and never
    more than the table has."""
    sublanes = 8 * (4 // itemsize)
    page_bytes = (
        page_size * -(-n_kv // sublanes) * sublanes * -(-head_dim // 128) * 128 * itemsize
    )
    by_vmem = _DECODE_STEP_VMEM_BYTES // (4 * page_bytes)
    return max(1, min(table_width, _DECODE_STEP_TOKENS // page_size, by_vmem))


def _as_column(row):
    """``(1, n)`` -> ``(n, 1)`` through the diagonal of its sublane
    broadcast: a select and a lane reduction, where Mosaic may refuse the
    transpose of so small a tile."""
    n = row.shape[1]
    diag = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (n, n), 1
    )
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


def _online_softmax(s, visible, m_prev, l_prev, axis):
    """One flash-style update over the tokens along ``axis``: returns the
    unnormalized probabilities, the rescale ``alpha`` of what was
    accumulated before, and the new running max and denominator (each of
    size 1 along ``axis``)."""
    s = jnp.where(visible, s, -1e30)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=axis, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # mask p itself, not just the logits: if every token of a step is
    # hidden from a query, exp(-1e30 - m) could still round to nonzero garbage
    p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
    return p, alpha, m_new, l_prev * alpha + jnp.sum(p, axis=axis, keepdims=True)


def _page_scale_columns(scale_ref, step, pages: int, page_size: int):
    """The step's ``(page, kv_head)`` scales as ``(T, n_kv, 1)``: each page's
    ``(1, n_kv)`` row turned to a column and repeated over its tokens."""
    cols = [
        _as_column(scale_ref[0, pl.ds(step * pages + e, 1), :]) for e in range(pages)
    ]
    return jnp.concatenate(
        [jnp.broadcast_to(c[None], (page_size, *c.shape)) for c in cols], axis=0
    )


def _paged_decode_kernel(
    # scalar-prefetch operands (SMEM)
    bt_ref,  # (B, W) int32 block tables
    pos_ref,  # (B, S) int32 per-query-token positions
    low_ref,  # (B, S) int32 the lowest position each query token can see
    span_ref,  # (B, 2) int32 first and last logical page any query of the row can see
    # inputs: the row's queries in VMEM, the pools left in HBM
    q_ref,  # (1, N*S, Hk) head-major (row = head*S + s)
    k_hbm,  # (num_pages, ps, n_kv, Hk)
    v_hbm,  # (num_pages, ps, n_kv, Hv)
    *refs,  # if quantized: the scales of the row's table entries, k then v,
    #         (1, steps*P, n_kv) f32 in VMEM; if there is a sink: its score
    #         per query row (N*S, 1) f32; then the output (1, N*S, Hv) and
    #         the scratch
    sm_scale: float,
    page_size: int,
    n_kv: int,
    q_len: int,
    pages: int,
    quantized: bool,
    has_sink: bool,
):
    P, S, ps = pages, q_len, page_size
    ks_ref = vs_ref = sink_ref = None
    if quantized:
        ks_ref, vs_ref, *refs = refs
    if has_sink:
        sink_ref, *refs = refs
    o_ref, k_buf, v_buf, sem, slot_ref, acc_ref, m_ref, l_ref = refs
    # k_buf, v_buf: (2, P*ps, n_kv, H), a step's pages, two buffers;
    # sem: DMA (2, 2) by (k|v, buffer); slot_ref: SMEM (1,), the buffer that
    # holds this row's first step; acc (N*S, Hv), m, l (N*S, 1): f32 state
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    T = P * ps
    gS = q_ref.shape[1] // n_kv
    W = bt_ref.shape[1]

    def step_copies(row, step, slot, *, wait=False):
        """Start, or wait for, the copies of the pages ``step`` of ``row``
        walks, into buffer ``slot``.  A logical page outside the row's live
        span is not copied: its tokens are hidden by their position, whatever
        the buffer still holds there.  Logical page ``p`` is entry ``p % W``
        of the table: itself in a table as wide as the cache, the ring's
        entry in a window layer's."""
        for e in range(P):
            entry = step * P + e

            @pl.when((entry >= span_ref[row, 0]) & (entry <= span_ref[row, 1]))
            def _():
                page = bt_ref[row, entry % W]
                for side, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                    copy = pltpu.make_async_copy(
                        hbm.at[page], buf.at[slot, pl.ds(e * ps, ps)], sem.at[side, slot]
                    )
                    copy.wait() if wait else copy.start()

    @pl.when(b == 0)
    def _first_row():
        # what a buffer holds where no page was copied is multiplied by
        # p = 0: it has to be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        step_copies(0, span_ref[0, 0] // P, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    if has_sink:
        # the sink is a score with no value behind it: the softmax starts
        # from it, at a denominator of exp(sink - sink)
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.ones_like(l_ref)
    else:
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend_every_head_at_once(step, slot):
        # g*S == 1: a score is the dot of a head's one query with each key of
        # that head, so on the (T, n_kv, H) buffer the scores of every head
        # are one broadcast multiply and one reduction over H, and p.V one
        # broadcast multiply and one reduction over tokens.  Scores keep the
        # reduced lane as (T, n_kv, 1), the layout p needs to scale V rows.
        tok = step * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1, 1), 0)
        visible = (tok <= pos_ref[b, 0]) & (tok >= low_ref[b, 0])
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (n_kv, H)
        k = k_buf[slot].astype(jnp.float32)
        v = v_buf[slot].astype(jnp.float32)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)  # (T, n_kv, 1)
        if quantized:
            # codes . q times the page's scale is (codes * scale) . q
            s = s * _page_scale_columns(ks_ref, step, P, ps)
        p, alpha, m_new, l_new = _online_softmax(
            s, visible, m_ref[...][None], l_ref[...][None], axis=0
        )
        m_ref[...] = m_new[0]
        l_ref[...] = l_new[0]
        if quantized:
            p = p * _page_scale_columns(vs_ref, step, P, ps)
        acc_ref[...] = acc_ref[...] * alpha[0] + jnp.sum(p * v, axis=0)

    def attend_head_by_head(step, slot):
        # g*S > 1 (grouped queries, the verify window): per kv head a
        # (g*S, H) x (H, T) product over the step's T tokens
        g = gS // S
        tok = step * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        # row i*S + s of a head's block is query token s: g*S scalar SMEM
        # reads build the position column
        def column(ref):
            return jnp.concatenate(
                [ref[b, s].reshape(1, 1) for _ in range(g) for s in range(S)], axis=0
            )

        visible = (tok <= column(pos_ref)) & (tok >= column(low_ref))  # (gS, T)
        for j in range(n_kv):
            rows = slice(j * gS, (j + 1) * gS)

            def head_tokens(buf, scale_ref):
                if scale_ref is None:
                    return buf[slot, :, j, :].astype(jnp.float32)  # (T, H)
                return jnp.concatenate(
                    [
                        buf[slot, e * ps : (e + 1) * ps, j, :].astype(jnp.float32)
                        * scale_ref[0, step * P + e, j]
                        for e in range(P)
                    ],
                    axis=0,
                )

            kj = head_tokens(k_buf, ks_ref)
            vj = head_tokens(v_buf, vs_ref)
            qj = q_ref[0, rows, :].astype(jnp.float32)
            s = (
                jax.lax.dot_general(
                    qj, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                * sm_scale
            )  # (gS, T)
            p, alpha, m_new, l_new = _online_softmax(
                s, visible, m_ref[rows, :], l_ref[rows, :], axis=1
            )
            m_ref[rows, :] = m_new
            l_ref[rows, :] = l_new
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
                p, vj, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )

    attend = attend_every_head_at_once if gS == 1 else attend_head_by_head
    # only the steps that hold a page some query of the row can see: a step
    # outside them would contribute alpha = 1, p = 0, so it is neither
    # fetched nor run
    step0 = span_ref[b, 0] // P
    n_steps = span_ref[b, 1] // P - step0 + 1
    slot0 = slot_ref[0]

    def walk(i, _):
        step = step0 + i
        slot = (slot0 + i) % 2

        # fetch ahead into the other buffer: this row's next step, or the
        # next row's first
        @pl.when(i + 1 < n_steps)
        def _():
            step_copies(b, step + 1, 1 - slot)

        @pl.when((i + 1 == n_steps) & (b + 1 < n_rows))
        def _():
            step_copies(b + 1, span_ref[jnp.minimum(b + 1, n_rows - 1), 0] // P, 1 - slot)

        step_copies(b, step, slot, wait=True)
        attend(step, slot)

    jax.lax.fori_loop(0, n_steps, walk, None)
    slot_ref[0] = (slot0 + n_steps) % 2
    o_ref[0, :, :] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_decode_attention(
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
    interpret: bool = False,
) -> jax.Array:
    """Fused small-S decode/verify attention straight out of the page pool.

    One Pallas launch over grid ``(B,)``, a row a grid step.  The pools stay
    in HBM; the block table, the positions and each row's last live table
    entry ride in as scalar-prefetch operands, and the kernel walks only the
    table entries some query of the row can see — ``last // P + 1`` steps of
    ``P`` entries (:func:`decode_pages_per_step`: ``P * page_size`` is 128
    tokens unless the buffers would not fit), not the table's whole width.
    A step's pages are copied by ``P`` async copies for K and ``P`` for V
    into one of two VMEM buffers while the step before is attended, and a
    row's last step fetches the next row's first, so the copies hide behind
    the arithmetic across rows too.  Neither the gathered
    ``(B, W*ps, n_kv, H)`` cache copy of :func:`paged_cached_attention` nor
    the ``(B, N, S, S_kv)`` score matrix ever exists in HBM, and a table
    entry past a row's position costs nothing: it is neither copied nor
    attended (it would contribute ``alpha = 1``, ``p = 0``).  What a buffer
    holds where a step copied no page is hidden by the ``j <= position``
    mask like the tail of the row's own last page.

    Scores stay in VMEM as flash-style online-softmax state (running max
    ``m``, denominator ``l``, numerator ``acc``, f32, carried across a row's
    steps).  With ``g * S == 1`` (multi-head decode) every kv head is
    attended in one operation on the step's ``(tokens, n_kv, H)`` buffer: a
    broadcast multiply by the row's ``(n_kv, H)`` queries and a reduction
    over ``H``, then ``p * V`` reduced over tokens, with ``m`` and ``l`` as
    ``(n_kv, 1)`` columns.  With ``g * S > 1`` (grouped queries, the verify
    window) each kv head takes a ``(g*S, H) x (H, tokens)`` product.

    With ``k_scale``/``v_scale`` the pool is int8: the codes are copied (1
    byte per element from HBM), the scales of the row's own table entries are
    gathered outside the kernel (``(B, W, n_kv)`` f32) and ride in VMEM, and
    a page is dequantized by its ``(page, kv_head)`` scale after the copy.

    ``q`` is ``(B, S, N, H)`` for a *small* static S — 1 for plain decode,
    ``K+1`` for the speculative-decoding verify window (the dispatcher caps
    the fused arm at small S; long chunked prefill keeps the naive arm).
    Queries lay out head-major ``(B, N*S, H)`` inside the kernel so each
    kv-head group stays one contiguous row block, and per-token positions
    ride in as SMEM scalars to build the ``j <= position`` visibility mask
    per query row.

    The kernel adapts to what it is handed.  V pages may have another head
    size than K pages (the output has V's).  With ``window`` a query sees
    only positions ``p - window + 1 .. p``: the walk starts at the first
    logical page any query of the row can see, and logical page ``e`` is read
    from table entry ``e % W`` — so a window layer's table may be a ring
    of a few pages per row (models/step.py), and a table as wide as the cache
    reads as before.  ``sink`` ``(N,)`` is a score per query head that joins
    the softmax's denominator with no value behind it: the online softmax
    starts from ``m = sink, l = 1`` where it otherwise starts from nothing.

    ``positions`` is ``(B,)``/``(B, 1)`` (broadcast — every query at the
    same position) or ``(B, S)`` per-token.  Returns ``(B, S, N, Hv)`` in
    ``q.dtype``; math is f32 like every decode path here.  Off-TPU use
    ``interpret=True`` (differential tests); numerics match the naive arm
    to f32 tolerance, not bitwise — online softmax sums in a different
    order.
    """
    B, T, N, H = q.shape
    _, page_size, n_kv, _ = pool_k.shape
    Hv = pool_v.shape[-1]
    W = block_tables.shape[1]
    if N % n_kv:
        raise ValueError(f"num_heads={N} must divide by kv_heads={n_kv}")
    if scale is None:
        scale = H**-0.5
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if quantized and window is not None:
        raise ValueError("an int8 pool has no ring: its scales ride by table entry, not by logical page")

    # head-major rows: (B, S, N, H) -> (B, N, S, H) -> (B, N*S, H); row
    # n*S + s holds query token s of head n, so kv-head j's group block is
    # the contiguous slice [j*g*S, (j+1)*g*S)
    q3 = q.transpose(0, 2, 1, 3).reshape(B, N * T, H)
    bt = block_tables.astype(jnp.int32)
    pos = jnp.broadcast_to(positions.reshape(B, -1)[:, :1], (B, T)) if (
        positions.size == B
    ) else positions.reshape(B, T)
    pos = pos.astype(jnp.int32)
    if window is None:
        low = jnp.zeros_like(pos)
        last = jnp.clip(jnp.max(pos, axis=1) // page_size, 0, W - 1)
    else:
        # a ring has no last entry to clip to: a row past its cache is a row
        # whose table is null throughout
        low = jnp.maximum(pos - (window - 1), 0)
        last = jnp.maximum(jnp.max(pos, axis=1), 0) // page_size
    span = jnp.stack([jnp.min(low, axis=1) // page_size, last], axis=1)

    P = decode_pages_per_step(
        page_size, n_kv, max(H, Hv), jnp.dtype(pool_k.dtype).itemsize, W
    )

    def row_block(rows, cols):
        return pl.BlockSpec((1, rows, cols), lambda b, *_: (b, 0, 0))

    in_specs = [row_block(N * T, H)] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands = [q3, pool_k, pool_v]
    if quantized:
        # a (1, n_kv) slab of a (num_pages, n_kv) array is no tile a copy can
        # address, and a row walks at most W pages: their scales are gathered
        # here, (B, W, n_kv) padded to whole steps, and ride in VMEM by row
        whole_steps = -(-W // P) * P
        for s in (k_scale, v_scale):
            of_row = jnp.take(s.astype(jnp.float32), bt, axis=0)
            operands.append(jnp.pad(of_row, ((0, 0), (0, whole_steps - W), (0, 0))))
            in_specs.append(row_block(whole_steps, n_kv))
    if sink is not None:
        # row n*S + s of the kernel's state is query token s of head n
        operands.append(jnp.repeat(sink.astype(jnp.float32), T).reshape(N * T, 1))
        in_specs.append(pl.BlockSpec((N * T, 1), lambda b, *_: (0, 0)))

    def page_buf(pool):
        return pltpu.VMEM((2, P * page_size, n_kv, pool.shape[-1]), pool.dtype)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=float(scale),
        page_size=page_size,
        n_kv=n_kv,
        q_len=T,
        pages=P,
        quantized=quantized,
        has_sink=sink is not None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=row_block(N * T, Hv),
        scratch_shapes=[
            page_buf(pool_k),
            page_buf(pool_v),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((N * T, Hv), jnp.float32),
            pltpu.VMEM((N * T, 1), jnp.float32),
            pltpu.VMEM((N * T, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N * T, Hv), q.dtype),
        interpret=interpret,
        # a window layer's launches carry a name of their own, so that a trace
        # tells the two cache kinds' launches apart (the prefix is shared)
        name="paged_decode_attention" if window is None else "paged_decode_attention_window",
    )(bt, pos, low, span, *operands)
    return out.reshape(B, N, T, Hv).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Packed mixed-batch kernel: per-token row/position maps over the pool
# ---------------------------------------------------------------------------


def _packed_paged_kernel(
    # scalar-prefetch operands (SMEM)
    rm_ref,  # (T,) int32 packed token -> block-table row
    bt_ref,  # (R, W) int32 block tables, one row per slot (+ null row)
    pos_ref,  # (T,) int32 per-packed-token absolute positions
    # VMEM inputs
    q_ref,  # (1, N, H) this packed token's query, head-major
    k_ref,  # (1, ps, n_kv, H) pool page selected by bt[rm[t], w]
    v_ref,  # (1, ps, n_kv, H)
    ks_ref,  # (1, 1, n_kv) f32 page scales (ones when unquantized)
    vs_ref,  # (1, 1, n_kv)
    # VMEM output
    o_ref,  # (1, N, H)
    # VMEM scratch, carried across the W grid steps of one token
    acc_ref,  # (N, H) f32 running numerator
    m_ref,  # (N, 1) f32 running max
    l_ref,  # (N, 1) f32 running denominator
    *,
    sm_scale: float,
    page_size: int,
    n_kv: int,
    quantized: bool,
):
    t = pl.program_id(0)
    w = pl.program_id(1)
    n_pages = pl.num_programs(1)
    g = q_ref.shape[1] // n_kv

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute token index of each slot in this page; (1, ps) because TPU
    # requires >=2D iota.  Visibility is j <= position of THIS packed token —
    # the only coupling between packed tokens is that none exists: each grid
    # row walks its own table's pages and masks by its own position, so a
    # row's output cannot depend on what else shares the dispatch.
    idx = w * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    visible = jnp.broadcast_to(idx <= pos_ref[t], (g, page_size))

    for j in range(n_kv):
        kj = k_ref[0, :, j, :].astype(jnp.float32)  # (ps, H)
        vj = v_ref[0, :, j, :].astype(jnp.float32)
        if quantized:
            kj = kj * ks_ref[0, 0, j]
            vj = vj * vs_ref[0, 0, j]
        qj = q_ref[0, j * g : (j + 1) * g, :].astype(jnp.float32)  # (g, H)
        s = (
            jax.lax.dot_general(
                qj, kj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * sm_scale
        )  # (g, ps)
        s = jnp.where(visible, s, -1e30)

        m_prev = m_ref[j * g : (j + 1) * g, :]  # (g, 1)
        l_prev = l_ref[j * g : (j + 1) * g, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)  # (g, 1)
        # mask p itself, not just the logits: if every slot of a page is
        # hidden, exp(-1e30 - m) could still round to nonzero garbage
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)  # (g, ps)
        m_ref[j * g : (j + 1) * g, :] = m_new
        l_ref[j * g : (j + 1) * g, :] = l_prev * alpha + jnp.sum(
            p, axis=1, keepdims=True
        )
        acc_ref[j * g : (j + 1) * g, :] = acc_ref[
            j * g : (j + 1) * g, :
        ] * alpha + jax.lax.dot_general(
            p, vj, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(w == n_pages - 1)
    def _emit():
        # a fully-masked token (pad rows at the null position with an
        # all-null table still see page 0 unmasked at pos=cache_size, so l
        # stays positive) — but guard the division anyway: garbage rows must
        # stay finite so they cannot poison reductions downstream
        o_ref[0, :, :] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def packed_paged_attention(
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
    interpret: bool = False,
) -> jax.Array:
    """Fused attention for a *packed* mixed batch straight out of the pool.

    Generalizes :func:`paged_decode_attention` from per-row fixed small S to
    per-row **variable** token counts: ``q`` is ``(1, T, N, H)`` token-major —
    T packed tokens that may belong to different requests (1 per plain decode
    row, K+1 per speculative verify window, a whole prompt chunk per
    prefilling row) — and two scalar-prefetch maps say whose cache each token
    reads: ``row_map`` ``(T,)`` picks the token's row of ``block_tables``
    ``(R, W)`` and ``positions`` ``(T,)`` is its absolute position for the
    ``j <= position`` visibility mask.

    Grid is ``(T, W)``: grid row ``t`` walks exactly the pages
    ``bt[row_map[t], :]`` with online-softmax state private to the token, so
    cross-row leakage is impossible by construction — a token cannot even
    address another request's pages, let alone attend them unmasked.  Pad
    tokens point ``row_map`` at an all-null table row and sit at the null
    position; their output is garbage-but-finite and never gathered.

    The scheduler sizes T to a warmed token-budget bucket, so one compiled
    shape per bucket serves every admission mix.  Returns ``(1, T, N, H)``
    in ``q.dtype``; math is f32.  Off-TPU use ``interpret=True``.
    """
    B, T, N, H = q.shape
    if B != 1:
        raise ValueError(f"packed attention is token-major: expected B=1, got {B}")
    num_pages, page_size, n_kv, _ = pool_k.shape
    W = block_tables.shape[1]
    if N % n_kv:
        raise ValueError(f"num_heads={N} must divide by kv_heads={n_kv}")
    if scale is None:
        scale = H**-0.5
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if quantized:
        ks = k_scale.astype(jnp.float32).reshape(num_pages, 1, n_kv)
        vs = v_scale.astype(jnp.float32).reshape(num_pages, 1, n_kv)
    else:
        ks = jnp.ones((num_pages, 1, n_kv), jnp.float32)
        vs = ks

    # token-major rows: (1, T, N, H) -> (T, N, H); within a token the N axis
    # is head-major, so kv-head j's group block is the slice [j*g, (j+1)*g)
    q3 = q.reshape(T, N, H)
    bt = block_tables.astype(jnp.int32)
    rm = row_map.reshape(T).astype(jnp.int32)
    pos = positions.reshape(T).astype(jnp.int32)

    kernel = functools.partial(
        _packed_paged_kernel,
        sm_scale=float(scale),
        page_size=page_size,
        n_kv=n_kv,
        quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T, W),
        in_specs=[
            pl.BlockSpec((1, N, H), lambda t, w, rm, bt, pos: (t, 0, 0)),
            pl.BlockSpec(
                (1, page_size, n_kv, H),
                lambda t, w, rm, bt, pos: (bt[rm[t], w], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page_size, n_kv, H),
                lambda t, w, rm, bt, pos: (bt[rm[t], w], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, n_kv), lambda t, w, rm, bt, pos: (bt[rm[t], w], 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, n_kv), lambda t, w, rm, bt, pos: (bt[rm[t], w], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, N, H), lambda t, w, rm, bt, pos: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((N, 1), jnp.float32),
            pltpu.VMEM((N, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N, H), q.dtype),
        interpret=interpret,
        name="packed_paged_attention",
    )(rm, bt, pos, q3, pool_k, pool_v, ks, vs)
    return out.reshape(1, T, N, H)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    impl: str = "auto",
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal SDPA over ``(B, S, N, H)`` tensors.

    ``impl='auto'`` resolves per shape through the roofline dispatcher
    (:func:`relora_tpu.ops.attention_dispatch.choose_training_arm`): forward
    + backward cost modeled for naive/xla/flash over the static trace-time
    ``(B, S, heads, head_dim)``, the flash arm struck off-TPU or at
    non-tileable lengths.  Forcing ``impl=`` bypasses the cost model — all
    arms are numerically interchangeable (pinned by
    tests/test_attention_dispatch.py), so dispatch never changes results.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if impl == "auto":
        if q.shape[1] != k.shape[1]:
            impl = "xla"  # cross-attention shape: not in the training table
        else:
            from relora_tpu.ops.attention_dispatch import choose_training_arm

            arm = choose_training_arm(
                q.shape[0],
                q.shape[1],
                q.shape[2],
                k.shape[2],
                q.shape[3],
                act_bytes=jnp.dtype(q.dtype).itemsize,
                fused_available=jax.default_backend() == "tpu"
                and flash_partitionable(q.shape[0], q.shape[2], k.shape[2]),
            )
            impl = "pallas" if arm == "flash" else arm
            # trace time only: a run's log says which attention its step compiled
            info_once(logger, f"dot_product_attention traced: auto -> {impl} q_shape={q.shape}")
    if impl == "xla":
        return jax.nn.dot_product_attention(q, k, v, scale=scale, is_causal=causal)
    if impl == "pallas":
        return _pallas_attention(q, k, v, causal=causal, scale=scale)
    if impl in ("ring", "ring_zigzag", "ulysses"):
        # context parallelism: S sharded over the mesh's sequence axis
        from relora_tpu.parallel.mesh import current_mesh

        mesh = current_mesh()
        if mesh is None:
            raise RuntimeError(
                f"attention impl {impl!r} needs a mesh: call "
                "relora_tpu.parallel.mesh.set_current_mesh(mesh) first"
            )
        if impl == "ring":
            from relora_tpu.parallel.ring_attention import ring_attention

            return ring_attention(q, k, v, mesh, causal=causal, scale=scale)
        if impl == "ring_zigzag":
            # inputs travel in the persistent zigzag layout (the train step
            # permutes tokens/positions/labels consistently)
            from relora_tpu.parallel.ring_attention import ring_attention_zigzag

            if not causal:
                raise ValueError("zigzag layout only applies to causal attention")
            return ring_attention_zigzag(q, k, v, mesh, scale=scale, inputs_permuted=True)
        from relora_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, mesh, causal=causal, scale=scale)
    if impl == "naive":
        return _naive_attention(q, k, v, causal=causal, scale=scale)
    raise ValueError(f"Unknown attention impl {impl!r}")
