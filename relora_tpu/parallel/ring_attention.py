"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

A capability the reference does not have (SURVEY.md §5.7 — max trained
context 2048, plain SDPA): long sequences are sharded over the ``sequence``
mesh axis; each device keeps its resident query block and streams K/V blocks
around the ring with ``ppermute`` over ICI, folding each block into a
streaming-softmax (flash-style m/l/o) accumulator.  Communication overlaps
compute block-by-block, and the result is numerically exact (not an
approximation) — verified against single-device attention in tests.

The fold is flash-tiled *within* each resident block too: scores for at most
``tile`` keys exist at a time, so per-device score memory is
O(S_loc · tile), not O(S_loc²) — at the long contexts ring attention exists
for, the dense per-block buffer would dominate HBM.

Grouped-query attention is native: K/V may carry ``n_kv < n`` heads (any
divisor).  The grouped heads ride the ring un-repeated — ICI traffic and K/V
block memory shrink by ``n/n_kv`` — and the score einsum contracts against
the shared head directly instead of a materialized repeat.

Causality is handled at block granularity: a K/V block strictly in the
future of the resident query block contributes nothing, the diagonal block
applies the intra-block causal mask, and past blocks attend densely.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from relora_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS

_NEG_INF = -1e30  # finite sentinel: keeps exp()/where math NaN-free

# per-block key-tile width; scores live as (B, n_kv, G, Q, TILE) f32
DEFAULT_TILE = 512


def _pick_tile(S: int, tile: int) -> int:
    """Largest divisor of S that is <= tile (S and tile are trace-time ints)."""
    t = min(tile, S)
    while S % t:
        t -= 1
    return t


def _group_q(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, Q, N, H) -> (B, Q, n_kv, G, H) f32, query head n = kv·G + g."""
    B, Q, N, H = q.shape
    if N % n_kv:
        raise ValueError(f"num_heads={N} must divide by kv heads={n_kv}")
    return q.astype(jnp.float32).reshape(B, Q, n_kv, N // n_kv, H)


def _flash_fold_block(carry, qg, q_pos, k_blk, v_blk, k_pos, *, scale, tile):
    """Fold one K/V block into flash (o, l, m) accumulators, streaming over
    key tiles so only (…, Q, tile) scores are live.

    qg: (B, Q, n_kv, G, H) f32 grouped queries; k_blk/v_blk: (B, S, n_kv, H);
    k_pos: (S,) global key positions, or None for non-causal.
    carry: o (B, n_kv, G, Q, H), l/m (B, n_kv, G, Q) — all f32.
    """
    S = k_blk.shape[1]
    T = _pick_tile(S, tile)

    def tfold(t, carry):
        o, l, m = carry
        kt = jax.lax.dynamic_slice_in_dim(k_blk, t * T, T, axis=1).astype(jnp.float32)
        vt = jax.lax.dynamic_slice_in_dim(v_blk, t * T, T, axis=1).astype(jnp.float32)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, kt) * scale
        if k_pos is not None:
            kp = jax.lax.dynamic_slice_in_dim(k_pos, t * T, T, axis=0)
            visible = kp[None, :] <= q_pos[:, None]
            scores = jnp.where(visible[None, None, None], scores, _NEG_INF)
        blk_max = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        p = jnp.exp(scores - m_new[..., None])
        # rows with no visible keys yet: m_new stays at the sentinel and the
        # exp() above evaluated exp(0)=1 on masked lanes — zero them out
        p = jnp.where(scores <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum("bkgqs,bskh->bkgqh", p, vt)
        return o, l, m_new

    return jax.lax.fori_loop(0, S // T, tfold, carry)


def _flash_finish(o, l, q_dtype):
    """(B, n_kv, G, Q, H) accumulators -> (B, Q, N, H) output."""
    out = o / jnp.maximum(l[..., None], 1e-30)
    B, K, G, Q, H = out.shape
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Q, K * G, H).astype(q_dtype)


def _ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool,
    scale: float,
    tile: int,
) -> jax.Array:
    """Per-device body (runs under shard_map).  q: (B, S_local, N, H);
    k/v: (B, S_local, n_kv, H) with n_kv | N."""
    ring = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    B, S, N, H = q.shape
    n_kv = k.shape[2]
    G = N // n_kv

    qg = _group_q(q, n_kv)
    q_pos = me * S + jnp.arange(S)

    acc0 = (
        jnp.zeros((B, n_kv, G, S, H), jnp.float32),
        jnp.zeros((B, n_kv, G, S), jnp.float32),
        jnp.full((B, n_kv, G, S), _NEG_INF, jnp.float32),
    )

    def fold(i, carry):
        o, l, m, k_blk, v_blk = carry
        # which global block is resident after i rotations (blocks travel
        # to the next-higher index each step, so we see me, me-1, ...)
        src = (me - i) % ring
        k_pos = src * S + jnp.arange(S) if causal else None
        o, l, m = _flash_fold_block(
            (o, l, m), qg, q_pos, k_blk, v_blk, k_pos, scale=scale, tile=tile
        )
        k_blk, v_blk = jax.lax.ppermute(
            (k_blk, v_blk),
            axis_name,
            perm=[(j, (j + 1) % ring) for j in range(ring)],
        )
        return o, l, m, k_blk, v_blk

    o, l, m, _, _ = jax.lax.fori_loop(0, ring, fold, (*acc0, k, v))
    return _flash_finish(o, l, q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    seq_axis: str = SEQUENCE_AXIS,
    tile: int = DEFAULT_TILE,
) -> jax.Array:
    """Causal attention over (B, S, N, H) arrays whose S dim is sharded on
    ``seq_axis``; K/V may carry fewer (grouped) heads.  Composable with jit:
    shard_map slots into the surrounding GSPMD program."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P((DATA_AXIS, FSDP_AXIS), seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local,
            axis_name=seq_axis,
            causal=causal,
            scale=scale,
            tile=tile,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # the streaming accumulators start replicated-typed and become
        # device-varying after the first fold; skip the static vma check
        check_vma=False,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Zigzag layout: causal load balancing
#
# With contiguous sequence shards, causal ring attention wastes half its
# FLOPs: device 0's queries can only ever see block 0, yet every device
# computes (and masks away) every rotation.  The zigzag layout splits the
# sequence into 2·ring chunks and gives device d chunks (d, 2·ring-1-d) —
# one early + one late — so each device's *useful* work is the same, and
# per-(query-chunk, key-chunk) `lax.cond`s skip the provably-invisible
# pairs.  Total computed chunk pairs drop from 4·ring² to ~2·ring² + ring.
#
# The kernel expects inputs already permuted by `zigzag_permutation` along S
# (persist the permuted layout across the model for free gains — RoPE uses
# true positions, so only the loss's token adjacency needs care — or use the
# convenience wrapper below, which permutes/unpermutes around the call).
# ---------------------------------------------------------------------------


def zigzag_permutation(seq_len: int, ring: int):
    """perm[i] = original index of permuted position i (gather indices)."""
    import numpy as np

    if seq_len % (2 * ring):
        raise ValueError(f"seq_len={seq_len} must divide by 2*ring={2*ring}")
    C = seq_len // (2 * ring)
    order = []
    for d in range(ring):
        order.extend(range(d * C, (d + 1) * C))
        order.extend(range((2 * ring - 1 - d) * C, (2 * ring - d) * C))
    return np.asarray(order)


def zigzag_inverse(seq_len: int, ring: int):
    import numpy as np

    perm = zigzag_permutation(seq_len, ring)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len)
    return inv


def _zz_positions(block: jax.Array, ring: int, C: int):
    """(early_pos, late_pos) for the device holding zigzag block ``block``."""
    early = block * C + jnp.arange(C)
    late = (2 * ring - 1 - block) * C + jnp.arange(C)
    return early, late


def _ring_attention_zigzag_local(q, k, v, *, axis_name: str, scale: float, tile: int):
    """Per-device body for zigzag layout.  q: (B, 2C, N, H) local;
    k/v: (B, 2C, n_kv, H) grouped."""
    ring = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    B, S2, N, H = q.shape
    C = S2 // 2
    n_kv = k.shape[2]
    G = N // n_kv

    qE = _group_q(q[:, :C], n_kv)
    qL = _group_q(q[:, C:], n_kv)
    myE_pos, myL_pos = _zz_positions(me, ring, C)

    def acc0():
        return (
            jnp.zeros((B, n_kv, G, C, H), jnp.float32),
            jnp.zeros((B, n_kv, G, C), jnp.float32),
            jnp.full((B, n_kv, G, C), _NEG_INF, jnp.float32),
        )

    def fold(i, carry):
        accE, accL, k_blk, v_blk = carry
        src = (me - i) % ring
        srcE_pos, srcL_pos = _zz_positions(src, ring, C)
        kE, vE = k_blk[:, :C], v_blk[:, :C]
        kL, vL = k_blk[:, C:], v_blk[:, C:]

        # chunk-level visibility: chunk a sees chunk b iff b's start <= a's
        # end; chunk index order IS position order, so compare block ids.
        # qE chunk id = me, qL id = 2*ring-1-me; kE id = src, kL id = 2*ring-1-src.
        qE_id, qL_id = me, 2 * ring - 1 - me
        kE_id, kL_id = src, 2 * ring - 1 - src

        def maybe(acc, pred, qc, q_pos, kc, vc, k_pos):
            return jax.lax.cond(
                pred,
                lambda c: _flash_fold_block(
                    c, qc, q_pos, kc, vc, k_pos, scale=scale, tile=tile
                ),
                lambda c: c,
                acc,
            )

        accE = maybe(accE, kE_id <= qE_id, qE, myE_pos, kE, vE, srcE_pos)
        accE = maybe(accE, kL_id <= qE_id, qE, myE_pos, kL, vL, srcL_pos)
        accL = maybe(accL, kE_id <= qL_id, qL, myL_pos, kE, vE, srcE_pos)
        accL = maybe(accL, kL_id <= qL_id, qL, myL_pos, kL, vL, srcL_pos)

        k_blk, v_blk = jax.lax.ppermute(
            (k_blk, v_blk), axis_name, perm=[(j, (j + 1) % ring) for j in range(ring)]
        )
        return accE, accL, k_blk, v_blk

    accE, accL, _, _ = jax.lax.fori_loop(0, ring, fold, (acc0(), acc0(), k, v))
    outE = _flash_finish(*accE[:2], q.dtype)
    outL = _flash_finish(*accL[:2], q.dtype)
    return jnp.concatenate([outE, outL], axis=1)


def ring_attention_zigzag(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    scale: Optional[float] = None,
    seq_axis: str = SEQUENCE_AXIS,
    inputs_permuted: bool = False,
    tile: int = DEFAULT_TILE,
) -> jax.Array:
    """Causal ring attention with zigzag load balancing (K/V may be grouped).

    With ``inputs_permuted=False`` the wrapper gathers into the zigzag layout
    and scatters back around the kernel (convenient, but pays two reshards);
    persist the permuted layout end-to-end and pass ``inputs_permuted=True``
    for the full benefit.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    ring = mesh.shape[seq_axis]
    S = q.shape[1]
    spec = P((DATA_AXIS, FSDP_AXIS), seq_axis, None, None)

    if not inputs_permuted:
        perm = jnp.asarray(zigzag_permutation(S, ring))
        inv = jnp.asarray(zigzag_inverse(S, ring))
        q, k, v = (x[:, perm] for x in (q, k, v))

    fn = jax.shard_map(
        functools.partial(
            _ring_attention_zigzag_local, axis_name=seq_axis, scale=scale, tile=tile
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    out = fn(q, k, v)
    if not inputs_permuted:
        out = out[:, inv]
    return out
