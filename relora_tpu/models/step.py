"""The seam between the serving engine and a model family: what one forward
step is told (:class:`StepContext`) and what cache its layers keep
(:class:`CacheSpec`).

A family with one kind of layer got by on keywords (``positions``,
``block_tables``, ``adapter_idx``, ``row_map`` down every ``__call__`` of
models/llama.py and models/pythia.py).  A family whose layers keep unlike
state takes one :class:`StepContext` instead, with a block table per cache
kind, and says with one :class:`CacheSpec` per kind what the engine has to
hold for it; the engine, the allocator and the scheduler's byte accounting
read the specs and never a family's name.

Two kinds exist:

- ``"paged"`` — K/V pages reached through a request's block table, allocated
  at admission and freed at retirement (serve/paging.PageAllocator); the pool
  holds ``num_pages`` pages (page 0 the null page) and a row's table has
  ``cache_size // page_size`` entries.
- ``"ring"`` — the last ``window`` tokens of a sliding-window layer: every
  decode slot owns ``table_width`` pages for good, logical page ``p`` lives in
  entry ``p % table_width`` of the slot's table, and nothing is allocated or
  freed per request.  The ring is wide enough that writing a whole prefill
  chunk never overwrites a token a query of that chunk still attends.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import flax
import jax
import numpy as np

from relora_tpu.config.model import ModelConfig

PAGED, RING = "paged", "ring"


@flax.struct.dataclass
class StepContext:
    """What a forward step needs beside the token ids.  ``tables`` maps a
    cache kind to that kind's block tables ``(rows, table_width)``."""

    positions: Optional[jax.Array] = None
    tables: Optional[Dict[str, jax.Array]] = None
    row_map: Optional[jax.Array] = None
    adapter_idx: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """The cache of the layers of one kind: ``layers`` pools, each a K leaf
    ``(num_pages, page_size, kv_heads, k_dim + k_pad)`` and a V leaf with
    ``v_dim``.  ``k_pad`` zero features follow each K head where ``k_dim`` is
    no whole number of 128-lane tiles: the chip stores such a row padded
    anyway, and Mosaic cannot copy a page out of a pool whose rows are not
    whole tiles."""

    kind: str
    layers: int
    kv_heads: int
    k_dim: int
    v_dim: int
    itemsize: int
    page_size: int
    num_pages: int
    table_width: int
    window: int = 0  # ring: tokens a query attends, itself included
    k_pad: int = 0

    @property
    def bytes_per_token(self) -> int:
        """K and V bytes of one cached position over the kind's layers: what
        a decode must read of it (the padding is nobody's to need)."""
        return self.layers * self.kv_heads * (self.k_dim + self.v_dim) * self.itemsize

    @property
    def pool_bytes(self) -> int:
        """Resident bytes of the kind's pools, padding and null page included."""
        row = self.kv_heads * (self.k_dim + self.k_pad + self.v_dim) * self.itemsize
        return self.layers * self.num_pages * self.page_size * row

    def read_bytes(self, position: int) -> int:
        """K/V bytes a decode at ``position`` must read from this kind."""
        tokens = position + 1
        return (min(tokens, self.window) if self.window else tokens) * self.bytes_per_token


def ring_pages(window: int, chunk: int, page_size: int) -> int:
    """Pages of a slot's ring: the window and one chunk, and a page more for
    a chunk that starts inside a page."""
    return -(-(window + chunk) // page_size) + 1


def cache_specs(
    cfg: ModelConfig, *, page_size: int, num_pages: int, cache_size: int,
    chunk_size: int, max_batch: int, itemsize: int,
) -> Tuple[CacheSpec, ...]:
    """The cache kinds of a configuration's layers, read off its layer kinds
    (``layer_window``) and head sizes.  ``max_batch`` sizes the rings (0:
    there are no slots to size them by yet)."""
    common = dict(itemsize=itemsize, page_size=page_size)
    width = cache_size // page_size
    n_ring = sum(cfg.layer_window)
    d_k, d_v = cfg.head_dim, cfg.v_head_dim or cfg.head_dim
    if cfg.qk_head_dim:
        # a configuration that states its K and V head sizes keeps each layer's
        # pools apart, K rows padded to whole lane tiles (CacheSpec.k_pad)
        common["k_pad"] = -d_k % 128
    specs = [
        CacheSpec(PAGED, cfg.num_hidden_layers - n_ring, cfg.kv_heads, d_k, d_v,
                  num_pages=num_pages, table_width=width, **common)
    ]
    if n_ring:
        r = ring_pages(cfg.sliding_window, chunk_size, page_size)
        specs.append(
            CacheSpec(RING, n_ring, cfg.window_kv_heads, d_k, d_v,
                      num_pages=1 + max_batch * r, table_width=r, window=cfg.sliding_window, **common)
        )
    return tuple(specs)


def ring_tables(spec: CacheSpec, slots: np.ndarray, live: np.ndarray) -> np.ndarray:
    """The ring pages of ``slots`` ``(rows,)``: slot ``s`` owns pages
    ``1 + s * width .. (s + 1) * width`` of the ring pools; a row that is not
    ``live`` gets the null page throughout, as in its paged table."""
    own = 1 + slots[:, None] * spec.table_width + np.arange(spec.table_width, dtype=np.int32)
    return np.where(live[:, None], own, 0).astype(np.int32)


def check_refused(family: str, refuses: Tuple[str, ...], asked: Dict[str, object]) -> None:
    """A family names what the serving stack cannot do for it yet
    (``refuses`` on its model class); asking for one is an error by its name."""
    for feature in refuses:
        if asked.get(feature):
            raise ValueError(f"the {family} family cannot do {feature} yet (ROADMAP.md R4)")
