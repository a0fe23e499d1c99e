"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The second context-parallel mode (the task's "ring attention or all-to-all
sequence parallelism"): activations arrive sequence-sharded ``(B, S/sp, N,
H)``; an all-to-all over the ``sequence`` axis re-partitions them to
head-sharded ``(B, S, N/sp, H)``, each device runs ordinary full attention
over its head subset with the complete sequence, and a reverse all-to-all
restores sequence sharding.

Trade-off vs ring attention (parallel/ring_attention.py): Ulysses moves
2×(B·S·N·H) elements per call through two all-to-alls but then attends with
one dense kernel (better MXU utilization, no block-level load imbalance);
the ring streams K/V with sp ppermutes and never materializes the full
sequence on any device (lower peak memory, better for extreme S).  Requires
``num_heads % sp == 0``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from relora_tpu.ops.attention import dot_product_attention
from relora_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale: float, inner_impl: str):
    # (B, S/sp, N, H) -> (B, S, N/sp, H): concat seq shards, split heads
    def to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = dot_product_attention(qh, kh, vh, causal=causal, impl=inner_impl, scale=scale)
    return to_seq(out)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    seq_axis: str = SEQUENCE_AXIS,
    inner_impl: str = "xla",
) -> jax.Array:
    """Causal attention over (B, S, N, H) with S sharded on ``seq_axis``.
    ``num_heads`` must divide by the axis size.  Grouped K/V stay grouped
    when ``n_kv`` also divides by the axis size (the all-to-all then moves
    ``n_kv/N`` of the K/V bytes); otherwise they are expanded first.
    """
    sp = mesh.shape[seq_axis]
    if q.shape[2] % sp != 0:
        raise ValueError(f"num_heads={q.shape[2]} must divide by sequence axis size {sp}")
    if k.shape[2] != q.shape[2] and k.shape[2] % sp != 0:
        from relora_tpu.ops.attention import _expand_grouped_kv

        k, v = _expand_grouped_kv(q, k, v)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P((DATA_AXIS, FSDP_AXIS), seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ulysses_local,
            axis_name=seq_axis,
            causal=causal,
            scale=scale,
            inner_impl=inner_impl,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
