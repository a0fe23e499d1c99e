"""Jitted prefill/decode step functions over the cache-aware model forwards.

The models gained a ``decode=True`` mode (models/llama.py, models/pythia.py):
attention keeps per-layer K/V buffers of fixed capacity in the flax ``cache``
variable collection, writes the current chunk at its absolute positions, and
attends with the ``j <= position`` visibility mask (ops/attention.py:
cached_attention).  This module wraps that into an inference engine:

- ``prefill(ids, lengths)`` — run the whole (right-padded) prompt batch in one
  forward, returning full logits and a populated cache.  Pad tokens write
  garbage K/V beyond each row's length, but an entry at index ``j`` only
  becomes visible to queries at positions ``>= j`` — and the decode loop
  overwrites index ``j`` at the step that reaches position ``j``, before it
  ever attends.  So right-padding needs no separate pad mask.
- ``decode(cache, token, pos)`` — one token per row against the cache, cache
  buffers donated so XLA updates them in place (no per-step reallocation).
- ``insert(dcache, pcache, slot)`` — copy a freshly prefilled single-row cache
  into slot ``slot`` of the persistent decode cache (continuous batching
  admission).  ``slot`` is traced, so admissions never retrace.

Prompt lengths are bucketed to powers of two (``bucket_length``) to bound the
number of prefill compilations.

Paged mode (``page_size``/``num_pages`` set): the contiguous per-slot cache is
replaced by one shared page pool (serve/paging.py) and two entry points —
``prefill_chunk(ids, start, pool, block_table)`` writes one fixed-size prompt
chunk straight into the pool through the request's block table (no insert
copy), and ``decode_paged(pool, token, pos, block_tables)`` decodes every slot
through its table.  Both compile exactly once: prompt length appears in no
compiled shape, and cache HBM scales with ``num_pages``, not
``max_batch × cache_size``.  The pool is made here (``init_pool``, from the
model family's ``pool_shapes``), never by the model.  Under ``nn.scan`` the
contiguous cache stacks on the leading layers axis as a scanned input and
output of the layer loop; the pool's leaves have the same leading axis but
ride the loop whole, as a carry, each layer addressing its own pages in
place (models/llama.attend_with_paged_cache) — so a step aliases the donated
pool to its result and holds neither a copy of it nor a layer's slice.

Shardings: with a mesh, params shard per the model's logical annotations
(parallel/mesh.py LOGICAL_RULES), cache buffers shard their batch axis over
``data``×``fsdp``, and K/V heads — contiguous cache and page pool alike —
shard over ``tensor`` when divisible, matching the ``kv`` logical axis of
the k/v projection kernels.  Sharding the pool by head drops per-chip pool
bytes by the tp degree, and the engine returns that HBM as proportionally
more pages (``num_pages`` is the per-chip page budget).  Without a mesh the
same code runs single-host (CPU tests, dev boxes).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from relora_tpu.config.model import ModelConfig
from relora_tpu.core.relora import LoraSpec
from relora_tpu.models import step as model_step
from relora_tpu.models.step import PAGED, RING, StepContext
from relora_tpu.obs import memory as obs_memory
from relora_tpu.obs.compile import CompileWatcher
from relora_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS, TENSOR_AXIS, param_shardings
from relora_tpu.serve.paging import NULL_PAGE
from relora_tpu.serve.sampling import SamplingParams, sample, sample_rows

PyTree = Any

# leaves are (B, capacity, kv_heads, head_dim), plus a leading scan-layers
# axis when the model scans; the batch axis is always ndim-4
_CACHE_RANK = 4


def _cache_batch_axis(leaf) -> int:
    return leaf.ndim - _CACHE_RANK


def bucket_length(n: int, minimum: int = 16) -> int:
    """Round a prompt length up to the next power of two (>= minimum) so
    prefill compiles once per bucket, not once per prompt length."""
    if n < 1:
        raise ValueError(f"prompt length must be >= 1, got {n}")
    return max(minimum, 1 << (n - 1).bit_length())


def _chunk_positions(start: int, rows: int, length: int) -> np.ndarray:
    """Absolute positions ``start .. start+length-1`` for every row of a
    prefill chunk, built on the host: a transfer, where ``jnp`` arithmetic on
    concrete values is an XLA program per operation."""
    return np.broadcast_to(np.arange(start, start + length, dtype=np.int32), (rows, length))


# multi-tenant slot writes: stacked lora_a/lora_b leaves are
# (…, num_slots, in, r) / (…, num_slots, r, out) — the slot axis sits at
# ndim-3 (a leading scan-layers axis may precede it); the per-slot scale
# lora_s is (…, num_slots) with the slot axis last
_LORA_FACTOR_LEAVES = ("lora_a", "lora_b")


def _factor_slot_axis(stacked) -> int:
    return stacked.ndim - 3


def _set_adapter_slot(stacked, block, slot):
    axis = _factor_slot_axis(stacked)
    block = jnp.expand_dims(jnp.asarray(block).astype(stacked.dtype), axis)
    starts = [0] * stacked.ndim
    starts[axis] = slot
    return jax.lax.dynamic_update_slice(stacked, block, tuple(starts))


def _set_adapter_scale(s_leaf, scale, slot):
    shape = list(s_leaf.shape)
    shape[-1] = 1
    block = jnp.full(tuple(shape), scale, s_leaf.dtype)
    starts = [0] * s_leaf.ndim
    starts[-1] = slot
    return jax.lax.dynamic_update_slice(s_leaf, block, tuple(starts))


def _write_adapter_slot_tree(params, factors, scale, slot):
    """Pure slot overwrite: returns ``params`` with adapter ``slot``'s
    lora_a/lora_b slabs replaced by ``factors`` (zeros where the adapter has
    no factor for a module) and its lora_s entry set to ``scale``.  ``slot``
    and ``scale`` are traced — one compile serves every load/evict/swap."""
    out = {}
    for key, value in params.items():
        f = factors.get(key) if isinstance(factors, dict) else None
        if isinstance(value, dict):
            out[key] = _write_adapter_slot_tree(
                value, f if isinstance(f, dict) else {}, scale, slot
            )
        elif key in _LORA_FACTOR_LEAVES:
            if f is None:
                axis = _factor_slot_axis(value)
                f = jnp.zeros(value.shape[:axis] + value.shape[axis + 1 :], value.dtype)
            out[key] = _set_adapter_slot(value, f, slot)
        elif key == "lora_s":
            out[key] = _set_adapter_scale(value, scale, slot)
        else:
            out[key] = value
    return out


def _reload_params_tree(params, fresh):
    """Pure full-tree weight swap: returns ``params`` with every leaf present
    in ``fresh`` replaced.  Leaves ``fresh`` omits (the multi-tenant adapter
    slabs, which a checkpoint reload must never clobber) pass through from
    the live tree.  The live tree is donated, so the swap reuses its HBM
    buffers instead of doubling resident params mid-serve."""
    out = {}
    for key, value in params.items():
        f = fresh.get(key) if isinstance(fresh, dict) else None
        if isinstance(value, dict):
            out[key] = _reload_params_tree(value, f if isinstance(f, dict) else {})
        elif f is None:
            out[key] = value
        else:
            out[key] = f
    return out


def _dict_path(path) -> Tuple[str, ...]:
    """The dict keys along a pytree path (a boxed leaf's own keys left out)."""
    return tuple(k.key for k in path if isinstance(k, jax.tree_util.DictKey))


def _pages_axis(ndim: int) -> int:
    """Pages axis of a pool leaf: code leaves are ``(..., num_pages,
    page_size, kv_heads, head_dim)`` (axis ndim-4), int8 scale leaves are
    ``(..., num_pages, kv_heads)`` (axis ndim-2) — a leading layers axis
    when scanned shifts both the same way."""
    return ndim - 4 if ndim >= 4 else ndim - 2


def build_decode_model(
    model_cfg: ModelConfig,
    *,
    cache_size: int,
    dtype=jnp.float32,
    scan_layers: bool = True,
    attention_impl: str = "auto",
    lora: Optional[LoraSpec] = None,
    page_size: int = 0,
    num_pages: int = 0,
    kv_dtype: str = "bf16",
    adapter_slots: int = 0,
):
    """The serving twin of train.trainer.build_model: same family dispatch,
    decode cache enabled, no remat.  ``lora=None`` (the default) serves a
    merged, LoRA-free param tree; passing the checkpoint's ``LoraSpec``
    serves the factors unmerged (quantized bases that can't absorb the
    delta, or adapter hot-swap).  An unmerged spec is rewritten for decode:
    ``weights_static`` tells ops/lora_dispatch's cost model that W/A/B are
    constant across steps, and ``fused=False`` is promoted to ``"auto"`` so
    the decode forward actually routes through the dispatcher — which picks
    the merged ``x @ (W + s·A@B)`` arm at decode-sized M.

    ``adapter_slots > 0`` switches every LoRA leaf to the stacked
    multi-tenant layout (models/lora.py ``num_slots``): factors become
    ``(adapter_slots, …)`` HBM slabs and every forward takes a per-row
    ``adapter_idx`` routed through the grouped kernel.  Slot 0 is the
    zero-initialized identity adapter.

    Every family declares its weights in ``dtype`` (``param_dtype=dtype``):
    what a forward multiplies in the compute dtype, the engine holds in it.
    What is used in f32 — norm scales and offsets, ``lora_s``, quantization
    scales — is declared f32 whatever ``dtype`` is."""
    if lora is not None:
        lora = dataclasses.replace(
            lora,
            weights_static=True,
            fused="auto" if lora.fused is False else lora.fused,
            num_slots=adapter_slots if adapter_slots else lora.num_slots,
        )
    kwargs = dict(
        config=model_cfg,
        lora=lora,
        dtype=dtype,
        param_dtype=dtype,
        scan_layers=scan_layers,
        remat=False,
        attention_impl=attention_impl,
        logits_dtype=jnp.float32,
        decode=True,
        cache_size=cache_size,
        page_size=page_size,
        num_pages=num_pages,
        kv_dtype=kv_dtype,
    )
    if model_cfg.family == "llama":
        from relora_tpu.models.llama import LlamaForCausalLM

        return LlamaForCausalLM(**kwargs)
    if model_cfg.family == "neox":
        from relora_tpu.models.pythia import GPTNeoXForCausalLM

        return GPTNeoXForCausalLM(**kwargs)
    if model_cfg.family in ("mimo", "afmoe"):
        # the families with layers of unlike kinds: paged only
        if model_cfg.family == "mimo":
            from relora_tpu.models.mimo import MimoForCausalLM as Model
        else:
            from relora_tpu.models.afmoe import AfmoeForCausalLM as Model
        return Model(model_cfg, dtype=dtype, param_dtype=dtype, decode=True, page_size=page_size)
    raise ValueError(f"Unknown model family {model_cfg.family!r}")


def _forward(model, params, cache, ids, ctx: StepContext):
    """One forward over ``cache``: logits, the cache after it and — from a
    model with routed experts — the step's ``[local assignments, distinct
    experts hit]`` summed over its layers (None otherwise).  A model that
    takes the step context gets it whole; Llama and GPT-NeoX get its fields
    as the keywords they have always taken."""
    variables = {"params": params, "cache": cache}
    if getattr(model, "takes_step_context", False):
        logits, new = model.apply(variables, ids, ctx, mutable=["cache", "stats"])
        counts = sum(jax.tree_util.tree_leaves(new.get("stats", {})), jnp.zeros((2,), jnp.int32))
        return logits, new["cache"], counts
    tables = None if ctx.tables is None else ctx.tables[PAGED]
    logits, new = model.apply(
        variables, ids, positions=ctx.positions, block_tables=tables,
        adapter_idx=ctx.adapter_idx, row_map=ctx.row_map, mutable=["cache"],
    )
    return logits, new["cache"], None


class InferenceEngine:
    """Owns the decode-mode model, the jitted step functions, and placement.

    ``params`` must match the training layout (scan-stacked layers when
    ``scan_layers``): a merged LoRA-free tree by default (see
    train.checkpoint.restore_serving_params), or — with ``lora=`` set to the
    checkpoint's spec — the raw tree with its LoRA factors still separate.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        params: PyTree,
        *,
        cache_size: int,
        dtype=jnp.float32,
        scan_layers: bool = True,
        attention_impl: str = "auto",
        mesh: Optional[Mesh] = None,
        lora: Optional[LoraSpec] = None,
        compile_watcher: Optional[CompileWatcher] = None,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        chunk_size: int = 64,
        kv_dtype: str = "bf16",
        spec_k: int = 0,
        adapter_slots: int = 0,
        token_budget: Optional[int] = None,
    ):
        if cache_size < 1:
            raise ValueError(f"cache_size must be >= 1, got {cache_size}")
        if token_budget is not None:
            if page_size is None:
                raise ValueError(
                    "token_budget requires the paged engine (page_size set)"
                )
            if token_budget < 1:
                raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        self.token_budget = token_budget or 0
        if adapter_slots:
            if lora is None:
                raise ValueError(
                    "adapter_slots > 0 requires the checkpoint's LoraSpec "
                    "(multi-tenant serving runs the factors unmerged)"
                )
            if adapter_slots < 2:
                raise ValueError(
                    f"adapter_slots must be >= 2 (slot 0 is the identity "
                    f"adapter), got {adapter_slots}"
                )
        self.adapter_slots = adapter_slots
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}")
        if kv_dtype == "int8" and page_size is None:
            raise ValueError("kv_dtype='int8' requires the paged engine (page_size set)")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k and page_size is None:
            raise ValueError("spec_k > 0 requires the paged engine (page_size set)")
        self.spec_k = spec_k
        # "bf16" means the pool stores at the engine compute dtype
        # (unquantized — bf16 in the serving default, f32 in CPU tests, so
        # the bitwise paged-vs-contiguous parity invariant is untouched);
        # "int8" stores codes + per-(page, kv_head) f32 scales
        self.kv_dtype = kv_dtype
        self.config = model_cfg
        self.cache_size = cache_size
        self.mesh = mesh
        # paged mode: page_size enables the block-granular pool (see
        # serve/paging.py); cache_size stays the per-request capacity bound
        # (validate_request semantics unchanged) and must page-align so the
        # gathered table width W*page_size equals the contiguous contraction
        # length C — the bitwise token-parity invariant
        self.paged = page_size is not None
        if self.paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if cache_size % page_size:
                raise ValueError(
                    f"cache_size ({cache_size}) must be a multiple of "
                    f"page_size ({page_size}) for paged decode"
                )
            self.block_table_width = cache_size // page_size
            if num_pages is None:
                raise ValueError("paged decode requires num_pages")
            if num_pages < self.block_table_width + 1:
                raise ValueError(
                    f"num_pages ({num_pages}) cannot hold one max-size request: "
                    f"need >= {self.block_table_width} + 1 (page 0 is the null page)"
                )
            if chunk_size < 1:
                raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        self.page_size = page_size or 0
        # tp sharding of the pool: each tensor shard holds kv_heads/kv_shards
        # heads of EVERY page, so per-chip pool bytes drop by kv_shards — the
        # freed HBM is returned as kv_shards× more pages (num_pages is the
        # per-chip page budget; the pool grows with the chips serving it)
        self.kv_shards = 1
        if mesh is not None and mesh.shape[TENSOR_AXIS] > 1:
            if model_cfg.kv_heads % mesh.shape[TENSOR_AXIS] == 0:
                self.kv_shards = mesh.shape[TENSOR_AXIS]
        self.requested_num_pages = num_pages or 0
        self.num_pages = (num_pages or 0) * (self.kv_shards if num_pages else 1)
        self.chunk_size = min(chunk_size, cache_size)
        self._pool_dtype = jnp.int8 if kv_dtype == "int8" else jnp.dtype(dtype)
        self.model = build_decode_model(
            model_cfg,
            cache_size=cache_size,
            dtype=dtype,
            scan_layers=scan_layers,
            attention_impl=attention_impl,
            lora=lora,
            adapter_slots=adapter_slots,
        )
        # what this family cannot do yet (its model class says): asked for
        # here, each is an error by the feature's name
        self.refuses = tuple(getattr(self.model, "refuses", ()))
        asked = {
            "the contiguous cache": not self.paged,
            "adapters": lora is not None or adapter_slots,
            "int8 pages": kv_dtype == "int8",
            "speculation": spec_k,
            "tp": mesh is not None,
            "packed steps": token_budget,
        }
        model_step.check_refused(model_cfg.family, self.refuses, asked)
        # the ring spec, if the model has window layers (its table width and
        # window hold for any number of slots)
        self._ring = next((c for c in self.cache_specs() if c.kind == RING), None) if self.paged else None
        #: [local assignments, distinct experts hit] of the last forward, on
        #: the device (None: the model has no routed experts)
        self.moe_counts = None
        declared = self._declared_params()
        if adapter_slots:
            # the checkpoint carries unstacked (in, r) factors; the slotted
            # model wants (num_slots, in, r) slabs.  Rebuild: non-LoRA leaves
            # from the checkpoint, LoRA leaves fresh (zeros / spec scale) so
            # slot 0 is the identity adapter — the base checkpoint's own A/B
            # are deliberately dropped (tenants load theirs via the registry)
            params = self._stack_adapter_params(params, lora, declared)
        # every leaf in the dtype the decode model declares for it, rounded
        # here once and not by every dispatch; then placed
        params = self._as_declared(params, declared)
        if mesh is not None:
            from relora_tpu.models.params_util import logical_partition_specs

            sample_ids = jnp.zeros((1, 1), jnp.int32)
            specs = logical_partition_specs(self.model, sample_ids)
            shardings = param_shardings(mesh, specs)
            params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        else:
            params = jax.tree_util.tree_map(jnp.asarray, params)
        self.params = params
        # optional second tree for model-drafted speculation (--spec model):
        # same shapes/dtypes/shardings as params, installed via
        # load_draft_params, fed through the SAME compiled paged programs
        self.draft_params: Optional[PyTree] = None

        def prefill_fn(p, ids, positions, cache, adapter_idx):
            ctx = StepContext(positions=positions, adapter_idx=adapter_idx)
            return _forward(self.model, p, cache, ids, ctx)

        def decode_fn(p, cache, token, pos, adapter_idx):
            ctx = StepContext(positions=pos, adapter_idx=adapter_idx)
            logits, cache, counts = _forward(self.model, p, cache, token, ctx)
            return logits[:, -1, :], cache, counts

        def insert_fn(dcache, pcache, slot):
            def ins(d, src):
                starts = [0] * d.ndim
                starts[_cache_batch_axis(d)] = slot
                return jax.lax.dynamic_update_slice(d, src.astype(d.dtype), tuple(starts))

            return jax.tree_util.tree_map(ins, dcache, pcache)

        # the fresh prefill cache and the persistent decode cache are both
        # donated: the step's output cache reuses the input buffers in place.
        # The compile watcher tracks each entry point's abstract signatures:
        # warmup() compiles are tagged expected, anything after counts toward
        # compile_steady_state_retraces (docs/observability.md)
        self.compile_watcher = compile_watcher or CompileWatcher(service="engine")
        cw = self.compile_watcher
        self._prefill = cw.wrap("prefill", jax.jit(prefill_fn, donate_argnums=(3,)))
        self._decode = cw.wrap("decode", jax.jit(decode_fn, donate_argnums=(1,)))
        self._insert = cw.wrap("insert", jax.jit(insert_fn, donate_argnums=(0,)))
        self._sample = jax.jit(sample, static_argnames=("top_k",))
        # the schedulers' sampler: per-row (uid, token index) keys are derived
        # inside it, so a round's draws are one dispatch over numpy inputs
        self._sample_rows = jax.jit(sample_rows, static_argnames=("top_k",))

        if adapter_slots:
            # slot writes donate the param tree and trace slot/scale: every
            # adapter load/evict/swap reuses one compiled program (the
            # zero-steady-state-retrace contract for mid-traffic churn)
            self._write_slot = cw.wrap(
                "adapter_write", jax.jit(_write_adapter_slot_tree, donate_argnums=(0,))
            )
            self._factor_template = self._adapter_factor_template()
        # full-tree hot swap (reload_params): the adapter-writer seam scaled
        # up to the whole merged tree — donated live params, host leaves cast
        # onto the live dtypes, one compiled program across every reload
        self._reload = cw.wrap(
            "params_reload", jax.jit(_reload_params_tree, donate_argnums=(0,))
        )

        if self.paged:
            # a second model instance over the same params: cache variables
            # are the shared (num_pages, page_size, n_kv, head_dim) pool and
            # every forward takes a block table.  There is no insert —
            # prefill chunks write straight into the pool through the table.
            self.paged_model = build_decode_model(
                model_cfg,
                cache_size=cache_size,
                dtype=dtype,
                scan_layers=scan_layers,
                attention_impl=attention_impl,
                lora=lora,
                page_size=self.page_size,
                num_pages=self.num_pages,
                kv_dtype=kv_dtype,
                adapter_slots=adapter_slots,
            )

            # ``tables`` is the block tables of each cache kind
            # (:meth:`tables_by_kind`); the step context carries them down
            def prefill_chunk_fn(p, ids, positions, pool, tables, adapter_idx):
                ctx = StepContext(positions=positions, tables=tables, adapter_idx=adapter_idx)
                return _forward(self.paged_model, p, pool, ids, ctx)

            def decode_paged_fn(p, pool, token, pos, tables, adapter_idx):
                ctx = StepContext(positions=pos, tables=tables, adapter_idx=adapter_idx)
                logits, pool, counts = _forward(self.paged_model, p, pool, token, ctx)
                return logits[:, -1, :], pool, counts

            # the pool argument is donated AND (under a mesh) committed to
            # pool_shardings by init_pool: jit infers the input sharding from
            # the committed buffers, donation reuses them in place, and the
            # output pool keeps the same placement — so the kv-head shards
            # never move for the lifetime of the serve loop
            self._prefill_chunk = cw.wrap(
                "prefill_chunk", jax.jit(prefill_chunk_fn, donate_argnums=(3,))
            )
            self._decode_paged = cw.wrap(
                "decode_paged", jax.jit(decode_paged_fn, donate_argnums=(1,))
            )
            # speculative verify shares prefill_chunk's contract — a
            # multi-token forward returning FULL window logits — but runs at
            # (B, spec_k+1) with per-row positions and a W+1-wide table, so
            # it gets its own watcher entry and jit cache
            self._verify_paged = cw.wrap(
                "verify_paged", jax.jit(prefill_chunk_fn, donate_argnums=(3,))
            )

            def step_paged_fn(p, ids, positions, pool, tables, row_map, adapter_idx):
                # the packed mixed-batch forward: one (1, Tb) token-major
                # window where row_map[t] names the slot token t belongs to.
                # Attention routes each token through its own block table
                # (models/llama.attend_with_paged_cache row_map path), so a
                # single dispatch serves every decode row, verify window, and
                # however many prefill chunks the token budget admitted.
                ctx = StepContext(
                    positions=positions, tables=tables, row_map=row_map, adapter_idx=adapter_idx
                )
                return _forward(self.paged_model, p, pool, ids, ctx)

            self._step_paged = cw.wrap(
                "step_paged", jax.jit(step_paged_fn, donate_argnums=(3,))
            )

            # page-run migration seam (disaggregated prefill/decode): gather
            # pulls a run of pool pages to host-bound slices, scatter writes
            # a received run into freshly allocated pages.  Same shape
            # discipline as the adapter writer: ids are bucketed (padded with
            # the null page) so every steady-state transfer replays one of
            # the warmed programs — zero retraces after a migrated insert.
            def gather_pages_fn(pool, ids):
                return jax.tree_util.tree_map(
                    lambda leaf: jnp.take(leaf, ids, axis=_pages_axis(leaf.ndim)),
                    pool,
                )

            def scatter_pages_fn(pool, ids, vals):
                def put(leaf, val):
                    axis = _pages_axis(leaf.ndim)
                    out = jnp.moveaxis(leaf, axis, 0).at[ids].set(
                        jnp.moveaxis(val, axis, 0)
                    )
                    return jnp.moveaxis(out, 0, axis)

                return jax.tree_util.tree_map(put, pool, vals)

            self._gather_pages = cw.wrap(
                "page_gather", jax.jit(gather_pages_fn)
            )
            self._scatter_pages = cw.wrap(
                "page_scatter", jax.jit(scatter_pages_fn, donate_argnums=(0,))
            )

    # -- cache construction --------------------------------------------------

    def cache_shapes(self, batch: int) -> PyTree:
        """Abstract (shape, dtype) tree of the cache for a given batch size —
        eval_shape over model.init, so no FLOPs or memory."""
        ids = jnp.zeros((batch, 1), jnp.int32)
        variables = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0), ids)
        )
        return variables["cache"]

    def cache_shardings(self, batch: int) -> Optional[PyTree]:
        """Batch axis over data×fsdp; K/V heads over tensor when divisible,
        matching the ``kv`` logical axis the k/v projection kernels shard
        over — the cache a tp shard writes is exactly the heads it computed,
        so no resharding collective sits between projection and cache."""
        if self.mesh is None:
            return None

        def spec(leaf):
            axes = [None] * leaf.ndim
            n_shards = (
                self.mesh.shape[DATA_AXIS] * self.mesh.shape[FSDP_AXIS]
            )
            if batch % n_shards == 0:
                axes[_cache_batch_axis(leaf)] = (DATA_AXIS, FSDP_AXIS)
            if self.kv_shards > 1:
                axes[leaf.ndim - 2] = TENSOR_AXIS  # (..., kv_heads, head_dim)
            return NamedSharding(self.mesh, P(*axes))

        return jax.tree_util.tree_map(spec, self.cache_shapes(batch))

    def init_cache(self, batch: int) -> PyTree:
        """Concrete zero cache for ``batch`` rows, placed per the mesh."""
        shardings = self.cache_shardings(batch)
        shapes = self.cache_shapes(batch)
        if shardings is None:
            return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.device_put(jnp.zeros(s.shape, s.dtype), sh),
            shapes,
            shardings,
        )

    # -- the held parameter tree ---------------------------------------------

    def _declared_params(self) -> PyTree:
        """Abstract (shape, dtype) tree of the parameters the decode model
        declares — eval_shape over model.init, so no FLOPs or memory."""
        from flax import linen as nn

        ids = jnp.zeros((1, 1), jnp.int32)
        return nn.meta.unbox(jax.eval_shape(lambda: self.model.init(jax.random.PRNGKey(0), ids))["params"])

    def _as_declared(self, params: PyTree, declared: PyTree) -> PyTree:
        """``params`` with every leaf in the dtype the decode model declares
        for it (``declared``: :meth:`_declared_params`): the compute dtype for what the
        forward multiplies in it, f32 for what it uses in f32.  A leaf that
        has that dtype already is the leaf handed; another is rounded once, a
        leaf at a time (no second whole tree): one that arrives on the host is
        cast there, so that what is transferred is what is held, and a device
        leaf is replaced by its cast.  The forward casts each weight to the
        compute dtype at its use, so the rounding is the one every dispatch
        made: same bits into the same operations."""
        dtypes = {_dict_path(path): leaf.dtype for path, leaf in jax.tree_util.tree_leaves_with_path(declared)}

        def held(path, leaf):
            if not isinstance(leaf, jax.Array):
                leaf = np.asarray(leaf)
            dtype = dtypes.get(_dict_path(path), leaf.dtype)
            return leaf if leaf.dtype == dtype else leaf.astype(dtype)

        return jax.tree_util.tree_map_with_path(held, params)

    def param_bytes(self) -> int:
        """Resident bytes of the parameter tree as held (per replica: a
        sharded leaf counts whole).  The ``serve/param_bytes`` gauge."""
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.params))

    # -- multi-tenant adapter slots (adapter_slots set at construction) ------

    def _stack_adapter_params(self, params: PyTree, lora: LoraSpec, shapes: PyTree) -> PyTree:
        """Rebuild the checkpoint tree for the slotted model: every non-LoRA
        leaf comes from the checkpoint, every lora_a/lora_b leaf becomes its
        zero stacked ``(num_slots, …)`` twin and lora_s fills with the spec
        scale — so every slot starts as the identity adapter.  ``shapes`` is
        the slotted model's own tree (:meth:`_declared_params`)."""
        from flax import linen as nn

        params = nn.meta.unbox(params)

        def merge(ckpt, init):
            out = {}
            for key, value in init.items():
                if isinstance(value, dict):
                    sub = ckpt.get(key) if isinstance(ckpt, dict) else None
                    out[key] = merge(sub if isinstance(sub, dict) else {}, value)
                elif key in _LORA_FACTOR_LEAVES:
                    out[key] = jnp.zeros(value.shape, value.dtype)
                elif key == "lora_s":
                    out[key] = jnp.full(value.shape, lora.scale, value.dtype)
                else:
                    if not isinstance(ckpt, dict) or key not in ckpt:
                        raise ValueError(
                            f"checkpoint is missing param leaf {key!r} required "
                            "by the slotted decode model"
                        )
                    # copy, don't alias: slot writes donate the whole param
                    # tree, and donating a buffer the caller still holds
                    # would delete it out from under them
                    out[key] = jnp.array(ckpt[key], copy=True)
            return out

        return merge(params, shapes)

    def _adapter_factor_template(self) -> PyTree:
        """Zero factors tree shaped like one adapter's lora_a/lora_b leaves
        (the stacked leaves minus the slot axis).  Every real load is cast
        onto this template so the slot-write jit sees one signature."""

        def walk(p):
            out = {}
            for key, value in p.items():
                if isinstance(value, dict):
                    sub = walk(value)
                    if sub:
                        out[key] = sub
                elif key in _LORA_FACTOR_LEAVES:
                    axis = _factor_slot_axis(value)
                    out[key] = jnp.zeros(
                        value.shape[:axis] + value.shape[axis + 1 :], value.dtype
                    )
            return out

        return walk(self.params)

    def _require_slots(self):
        if not self.adapter_slots:
            raise ValueError("engine was built without adapter_slots: no slot writes")

    def write_adapter_slot(self, slot: int, factors: PyTree, scale: float) -> None:
        """Copy one adapter's unmerged factors into HBM slot ``slot`` (a
        traced dynamic_update_slice over the donated param tree — pure data
        movement, zero steady-state retraces).  ``factors`` is the
        lora_a/lora_b subtree an AdapterRegistry loader returns; leaves are
        cast onto the engine's template — the dtype the slabs are held in,
        the compute dtype — so an f32 adapter checkpoint is rounded once
        here and dtype drift between checkpoints cannot change the compiled
        signature."""
        self._require_slots()
        if not (0 < slot < self.adapter_slots):
            raise ValueError(
                f"slot must be in [1, {self.adapter_slots}) (slot 0 is the "
                f"identity adapter), got {slot}"
            )

        def cast(tmpl, f):
            out = {}
            for key, value in tmpl.items():
                sub = f.get(key) if isinstance(f, dict) else None
                if isinstance(value, dict):
                    out[key] = cast(value, sub if isinstance(sub, dict) else {})
                elif sub is None:
                    out[key] = value  # module the adapter does not touch: zeros
                else:
                    leaf = jnp.asarray(sub)
                    if leaf.shape != value.shape:
                        raise ValueError(
                            f"adapter factor {key!r} has shape {leaf.shape}, "
                            f"expected {value.shape}"
                        )
                    out[key] = leaf.astype(value.dtype)
            return out

        self.params = self._write_slot(
            self.params,
            cast(self._factor_template, factors),
            jnp.asarray(scale, jnp.float32),
            jnp.asarray(slot, jnp.int32),
        )

    def adapter_writer(self):
        """The ``writer(slot, factors, scale)`` callback an AdapterRegistry
        wants (serve/adapters.py)."""
        self._require_slots()
        return lambda slot, factors, scale: self.write_adapter_slot(slot, factors, scale)

    # -- in-place weight reload (continuous deployment) ----------------------

    def _prepare_reload_tree(self, live: PyTree, new: PyTree, prefix: str = "params") -> PyTree:
        """Validate a restored checkpoint tree against the live tree and cast
        it for the jitted swap: every live leaf must have a same-shape twin
        (mismatches fail closed with the offending leaf named), dtypes are
        cast host-side onto the live leaf so every reload presents one
        abstract signature, and — on adapter-slot engines — incoming LoRA
        factors are dropped so tenant slabs survive the swap."""
        if not isinstance(new, dict):
            raise ValueError(f"reload: expected a subtree at {prefix}, got {type(new).__name__}")
        extra = set(new) - set(live)
        if extra:
            raise ValueError(
                f"reload: checkpoint leaf {prefix}/{sorted(extra)[0]} does not "
                "exist in the live tree (wrong model config?)"
            )
        out = {}
        for key, value in live.items():
            path = f"{prefix}/{key}"
            if self.adapter_slots and key in (*_LORA_FACTOR_LEAVES, "lora_s"):
                continue  # tenant slabs: never overwritten by a base reload
            if isinstance(value, dict):
                out[key] = self._prepare_reload_tree(value, new.get(key, {}), path)
                continue
            if key not in new:
                raise ValueError(f"reload: checkpoint is missing leaf {path}")
            f = np.asarray(new[key])
            if tuple(f.shape) != tuple(value.shape):
                raise ValueError(
                    f"reload: shape mismatch at {path}: checkpoint "
                    f"{tuple(f.shape)} vs live {tuple(value.shape)}"
                )
            if f.dtype != value.dtype:
                f = f.astype(value.dtype)
            if self.mesh is not None:
                # place on the live leaf's sharding so the jitted swap never
                # reshards (and the signature stays placement-stable)
                f = jax.device_put(f, value.sharding)
            out[key] = f
        return out

    def reload_params(self, new_params: PyTree) -> None:
        """In-place hot swap of the full serving tree — the deployment twin
        of ``write_adapter_slot``.  ``new_params`` is a restored host tree
        (``train/checkpoint.restore_serving_params``); shapes are enforced
        against the live tree before any device write, the live tree is
        donated (no transient 2x params in HBM), and the jitted swap keeps
        one signature across reloads, so the CompileWatcher pins zero
        steady-state retraces under reload churn.  Leaves are cast on the
        host onto the live tree's dtypes — the ones the decode model declares
        (:meth:`_as_declared`) — so an f32 checkpoint is rounded to the held
        dtype once, here, as at construction.  On any validation error the
        live tree is untouched — the server's fail-closed contract."""
        fresh = self._prepare_reload_tree(self.params, new_params)
        self.params = self._reload(self.params, fresh)
        # surface transfer/execution errors here, not on the next decode
        jax.block_until_ready(self.params)

    # -- draft model (model-drafted speculative decoding) --------------------

    def load_draft_params(self, new_params: PyTree) -> None:
        """Install a second (draft) param tree next to the base — the
        pruned+merged checkpoint ``--spec model`` proposes from.

        Same validation and placement as ``reload_params`` (every live leaf
        needs a same-shape twin, dtypes cast host-side onto the held ones — an
        f32 draft checkpoint lands in the compute dtype like the base —
        shards placed on the live leaf's sharding) but with NO donation: base and draft stay
        resident together, sharing the one page pool, tokenizer, and — the
        point — the already-compiled paged programs.  The params argument of
        every paged jit is traced, and the draft tree presents the identical
        abstract signature, so draft forwards replay the base's executables:
        zero new compiles in steady state, pinned by CompileWatcher."""
        self._require_paged()
        if self.adapter_slots:
            raise ValueError(
                "draft models and adapter slots are mutually exclusive: the "
                "draft tree is a merged base with no tenant slabs (serve the "
                "draft from a dedicated replica instead)"
            )
        fresh = self._prepare_reload_tree(self.params, new_params)
        self.draft_params = jax.tree_util.tree_map(jnp.asarray, fresh)
        jax.block_until_ready(self.draft_params)

    def _require_draft(self):
        if self.draft_params is None:
            raise ValueError("no draft model loaded (call load_draft_params first)")

    def draft_prefill_chunk(
        self, ids: jax.Array, start: int, pool: PyTree, block_table, slot: int = 0
    ) -> Tuple[jax.Array, PyTree]:
        """``prefill_chunk`` through the draft weights: same chunk, same
        positions, the draft's own block table (draft pages are allocated
        alongside the base's at admission).  Replays the compiled
        prefill_chunk program — the traced param tree is the only change."""
        self._require_paged()
        self._require_draft()
        B, T = ids.shape
        positions = _chunk_positions(start, B, T)
        return self._took(self._prefill_chunk(
            self.draft_params,
            jnp.asarray(ids),
            positions,
            pool,
            self.tables_by_kind(block_table, slot),
            self._row_idx(None, B),
        ))

    def draft_decode_paged(
        self, pool: PyTree, token: jax.Array, pos: jax.Array, block_tables
    ) -> Tuple[jax.Array, PyTree]:
        """One autoregressive draft-proposal step (``--spec model``): the
        draft model's ``decode_paged`` over the draft block tables.  Null
        rows follow the same convention as the base step — all-null tables
        and ``pos = cache_size`` clip their writes into the null page."""
        self._require_paged()
        self._require_draft()
        return self._took(self._decode_paged(
            self.draft_params,
            pool,
            jnp.asarray(token),
            jnp.asarray(pos, jnp.int32),
            self.tables_by_kind(block_tables),
            self._row_idx(None, token.shape[0]),
        ))

    def _row_idx(self, adapter_idx, rows: int):
        """Normalize an optional per-row adapter index to a concrete (rows,)
        int32 array (None -> all slot 0, the identity adapter)."""
        if adapter_idx is None:
            return np.zeros((rows,), np.int32)  # a transfer, not a program
        idx = jnp.asarray(adapter_idx, jnp.int32)
        if idx.shape != (rows,):
            raise ValueError(f"adapter_idx must have shape ({rows},), got {idx.shape}")
        return idx

    def _took(self, out):
        """A jitted step's ``(logits, cache, MoE counts or None)``: the counts
        kept as :attr:`moe_counts`, the two the callers know returned."""
        logits, cache, self.moe_counts = out
        return logits, cache

    def cache_specs(self, batch: int = 0) -> Tuple[model_step.CacheSpec, ...]:
        """One spec per cache kind of the model's layers (models/step.py);
        ``batch`` decode slots size the rings of window layers."""
        self._require_paged()
        return model_step.cache_specs(
            self.config, page_size=self.page_size, num_pages=self.num_pages,
            cache_size=self.cache_size, chunk_size=self.chunk_size, max_batch=batch,
            itemsize=jnp.dtype(self._pool_dtype).itemsize,
        )

    def tables_by_kind(self, block_tables, slot=None) -> dict:
        """The block tables of every cache kind for a step over
        ``block_tables`` ``(rows, W)``, the paged kind's: row ``i`` is decode
        slot ``i`` (or the one ``slot``, a prefill chunk's), and a row whose
        paged table is null is null in every kind."""
        tables = {PAGED: jnp.asarray(block_tables, jnp.int32)}
        if self._ring is not None:
            paged = np.asarray(block_tables)  # noqa: RTL204 - the scheduler's own numpy tables
            slots = np.arange(len(paged)) if slot is None else np.full(len(paged), slot)
            tables[RING] = model_step.ring_tables(self._ring, slots, paged[:, 0] != 0)
        return tables

    # -- step functions ------------------------------------------------------

    def prefill(self, ids: jax.Array, lengths=None, adapter_idx=None) -> Tuple[jax.Array, PyTree]:
        """Run a right-padded prompt batch ``(B, T)``; returns full logits
        ``(B, T, V)`` and the populated cache.  ``T`` must be <= cache_size
        (bucket prompts with ``bucket_length`` before calling).
        ``adapter_idx`` is an optional ``(B,)`` slot index per row (slot 0 —
        the identity adapter — when omitted)."""
        B, T = ids.shape
        if T > self.cache_size:
            raise ValueError(f"prompt length {T} exceeds cache capacity {self.cache_size}")
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
        cache = self.init_cache(B)
        return self._took(self._prefill(
            self.params, jnp.asarray(ids), positions, cache, self._row_idx(adapter_idx, B)
        ))

    def decode(self, cache: PyTree, token: jax.Array, pos: jax.Array, adapter_idx=None) -> Tuple[jax.Array, PyTree]:
        """One decode step: ``token``/``pos`` are ``(B, 1)``; returns logits
        ``(B, V)`` and the updated cache.  The input cache is donated —
        the caller must not reuse it after this call."""
        B = token.shape[0]
        return self._took(self._decode(
            self.params, cache, jnp.asarray(token), jnp.asarray(pos, jnp.int32),
            self._row_idx(adapter_idx, B),
        ))

    def insert(self, dcache: PyTree, pcache: PyTree, slot) -> PyTree:
        """Copy a single-row prefilled cache into decode slot ``slot``.
        ``dcache`` is donated; ``slot`` is traced (no retrace per slot)."""
        return self._insert(dcache, pcache, jnp.asarray(slot, jnp.int32))

    # -- paged step functions (page_size set at construction) ----------------

    def _require_paged(self):
        if not self.paged:
            raise ValueError("engine was built without page_size: no paged entry points")

    def pool_shapes(self, batch: int = 0) -> PyTree:
        """Abstract tree of the shared K/V page pool — per-layer leaves of
        shape (num_pages, page_size, kv_heads, head_dim) (a leading layers
        axis when scanned).  Its byte size scales with ``num_pages``, not
        ``max_batch × cache_size`` — the paged memory win, visible in
        ``memory_plans()``'s pytree breakdown.  The model family lays the
        leaves out from :meth:`cache_specs` (its ``pool_shapes``); a window
        layer's rings are sized for ``batch`` decode slots."""
        self._require_paged()
        return self.paged_model.pool_shapes(self.cache_specs(batch), self._pool_dtype)

    def pool_bytes(self, batch: int = 0, kind: Optional[str] = None) -> int:
        """Resident bytes of the shared K/V page pool — codes plus (int8)
        the per-page scale leaves; of one cache kind alone, what its spec
        says (rings at ``batch`` slots).  The ``serve/kv_cache_bytes`` gauge."""
        self._require_paged()
        if kind is not None:
            return sum(c.pool_bytes for c in self.cache_specs(batch) if c.kind == kind)
        return sum(
            int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(self.pool_shapes(batch))
        )

    def kv_bytes_per_token(self) -> float:
        """Paged-pool bytes amortized per cacheable token position
        (``num_pages × page_size`` across the whole pool) — the
        ``serve/kv_bytes_per_token`` gauge.  ~2×heads×head_dim×itemsize per
        layer; int8 roughly quarters it against an f32 pool."""
        self._require_paged()
        paged = self.pool_bytes() - self.pool_bytes(kind=RING)
        return paged / float(self.num_pages * self.page_size)

    def pool_shardings(self) -> Optional[PyTree]:
        """NamedSharding tree for the page pool: the kv_heads axis shards
        over ``tensor`` when divisible (matching the ``kv`` logical axis the
        k/v projection kernels shard over), everything else replicated.
        Code leaves are ``(..., num_pages, page_size, kv_heads, head_dim)``
        (kv axis at ndim-2); int8 scale leaves are ``(..., num_pages,
        kv_heads)`` (kv axis last).  The pool has no batch axis — every
        request's pages live on every tp shard, sliced by head."""
        self._require_paged()
        if self.mesh is None:
            return None

        def spec(leaf):
            axes = [None] * leaf.ndim
            if self.kv_shards > 1:
                axes[leaf.ndim - 2 if leaf.ndim >= 4 else leaf.ndim - 1] = TENSOR_AXIS
            return NamedSharding(self.mesh, P(*axes))

        return jax.tree_util.tree_map(spec, self.pool_shapes())

    def init_pool(self, batch: int = 0) -> PyTree:
        """Concrete zero page pool, kv-head-sharded over ``tensor`` when a
        mesh is set (pool_shardings); the committed placement is what the
        donated prefill_chunk/decode_paged steps inherit, so the pool never
        leaves its shards across the whole serve loop.  ``batch`` decode
        slots size the rings of a model's window layers (models/step.py)."""
        self._require_paged()
        shardings = self.pool_shardings()
        shapes = self.pool_shapes(batch)
        if shardings is None:
            return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.device_put(jnp.zeros(s.shape, s.dtype), sh),
            shapes,
            shardings,
        )

    def prefill_chunk(
        self, ids: jax.Array, start: int, pool: PyTree, block_table, adapter_idx=None, slot: int = 0
    ) -> Tuple[jax.Array, PyTree]:
        """Prefill one fixed-size chunk of a single prompt: ``ids`` is
        ``(1, chunk_size)`` (right-padded past the prompt), written at
        absolute positions ``start .. start+chunk_size-1`` through
        ``block_table`` ``(1, W)``.  Returns full chunk logits
        ``(1, chunk_size, V)`` and the updated pool (input pool donated).
        One compiled shape total — chunking is what keeps a long prompt off
        the decode loop's critical path for more than one chunk.  ``slot`` is
        the decode slot the request will take: a window layer's ring is the
        slot's (models/step.py)."""
        self._require_paged()
        B, T = ids.shape
        positions = _chunk_positions(start, B, T)
        return self._took(self._prefill_chunk(
            self.params,
            jnp.asarray(ids),
            positions,
            pool,
            self.tables_by_kind(block_table, slot),
            self._row_idx(adapter_idx, B),
        ))

    def decode_paged(
        self, pool: PyTree, token: jax.Array, pos: jax.Array, block_tables, adapter_idx=None
    ) -> Tuple[jax.Array, PyTree]:
        """One paged decode step: ``token``/``pos`` are ``(B, 1)``,
        ``block_tables`` is ``(B, W)``.  Rows without an active decoding
        request must carry all-null tables so their garbage write lands in
        the null page, never in a page another request is prefilling into.
        Returns logits ``(B, V)`` and the updated pool (input donated)."""
        self._require_paged()
        return self._took(self._decode_paged(
            self.params,
            pool,
            jnp.asarray(token),
            jnp.asarray(pos, jnp.int32),
            self.tables_by_kind(block_tables),
            self._row_idx(adapter_idx, token.shape[0]),
        ))

    def verify_paged(
        self, pool: PyTree, tokens: jax.Array, pos: jax.Array, block_tables, adapter_idx=None
    ) -> Tuple[jax.Array, PyTree]:
        """Speculative verify step: ``tokens``/``pos`` are ``(B, S)`` with
        ``S = spec_k + 1`` (last committed token followed by the drafted
        candidates, at consecutive positions), ``block_tables`` is
        ``(B, W+1)`` — the request's table plus a trailing null column so
        any write past ``cache_size`` (padding rows, drafts beyond a row's
        remaining budget) clips into the null page instead of a live one.
        Rows without an active decoding request carry all-null tables and
        ``pos = cache_size`` everywhere.  Returns FULL window logits
        ``(B, S, V)`` (row ``i`` judges drafted token ``i+1``; the last row
        is the bonus distribution) and the updated pool (input donated).
        Rejected drafts need no pool rollback: their K/V land inside the
        request's worst-case admission allocation (or the null page) and are
        overwritten by the next round's forward before any query can attend
        them."""
        self._require_paged()
        return self._took(self._verify_paged(
            self.params,
            jnp.asarray(tokens),
            jnp.asarray(pos, jnp.int32),
            pool,
            self.tables_by_kind(block_tables),
            self._row_idx(adapter_idx, tokens.shape[0]),
        ))

    def step_paged(
        self,
        pool: PyTree,
        ids: jax.Array,
        positions: jax.Array,
        block_tables,
        row_map,
        adapter_idx=None,
    ) -> Tuple[jax.Array, PyTree]:
        """One packed mixed-batch step: ``ids``/``positions`` are ``(1, Tb)``
        token-major, ``row_map`` is ``(Tb,)`` mapping each packed token to
        the block-table row it belongs to, ``block_tables`` is
        ``(rows, W+1)`` — every slot's table plus a trailing null column and
        a final all-null pad row.  Pad tokens carry ``row_map = rows-1`` and
        ``positions = cache_size`` so their writes clip into the null page.
        ``adapter_idx`` is per-TOKEN here (``(Tb,)``), not per-row — the
        grouped LoRA kernel sees one row per packed token.  Returns full
        window logits ``(1, Tb, V)`` and the updated pool (input donated).
        Token t's K/V is written before any token attends, so later packed
        tokens of the same request attend earlier same-dispatch tokens —
        whole prompts can prefill inside one step."""
        self._require_paged()
        T = ids.shape[1]
        return self._took(self._step_paged(
            self.params,
            jnp.asarray(ids),
            jnp.asarray(positions, jnp.int32),
            pool,
            self.tables_by_kind(block_tables),
            jnp.asarray(row_map, jnp.int32),
            self._row_idx(adapter_idx, T),
        ))

    def packed_buckets(self) -> Tuple[int, ...]:
        """The packed-step shapes warmed and used at steady state: halving
        from ``token_budget`` down to 8, so a lightly loaded round (a few
        decode rows, no prefill backlog) pads to a small bucket instead of
        the full budget.  A handful of shapes replaces the per-bucket
        chunk/decode/verify warmup trio."""
        self._require_paged()
        if not self.token_budget:
            raise ValueError("engine was built without token_budget: no packed step")
        buckets = set()
        t = self.token_budget
        while True:
            buckets.add(t)
            if t <= 8:
                break
            t = max(8, t // 2)
        return tuple(sorted(buckets))

    def _warm_page_run(self, pool: PyTree) -> PyTree:
        """Compile the migration gather/scatter pair at every page-run
        bucket (null-page ids: reads/writes touch only the page nothing
        attends).  Called inside warmup's ``expected_compiles`` block so a
        migrated-slot insert at steady state is never a retrace."""
        for nb in self.page_run_buckets():
            ids = jnp.full((nb,), NULL_PAGE, jnp.int32)
            vals = self._gather_pages(pool, ids)
            pool = self._scatter_pages(pool, ids, vals)
        return pool

    def page_run_buckets(self) -> Tuple[int, ...]:
        """Page-count shapes the migration gather/scatter compile for:
        powers of two up to ``block_table_width`` (the widest run a single
        request can own), plus the width itself.  Transfers pad their page
        ids (with the null page) and payload (with zeros) up to the next
        bucket, so steady-state migration replays warmed programs only."""
        self._require_paged()
        buckets: List[int] = []
        t = 1
        while t < self.block_table_width:
            buckets.append(t)
            t *= 2
        buckets.append(self.block_table_width)
        return tuple(buckets)

    def _page_run_bucket(self, n: int) -> int:
        for b in self.page_run_buckets():
            if b >= n:
                return b
        raise ValueError(
            f"page run of {n} pages exceeds block_table_width {self.block_table_width}"
        )

    def export_page_run(
        self, pool: PyTree, pages: Sequence[int]
    ) -> List[Tuple[str, str, Tuple[int, ...], bytes]]:
        """Pull the pool slices for a page run to host bytes, ready for
        :func:`wire.encode_page_run`.  One gather dispatch at the padded
        bucket shape, then a host-side trim back to ``len(pages)`` — the
        wire carries only real pages (int8 codes + their scales), the 4×
        transfer win over a bf16 pool."""
        self._require_paged()
        n = len(pages)
        if n < 1:
            raise ValueError("empty page run")
        bucket = self._page_run_bucket(n)
        ids = list(pages) + [NULL_PAGE] * (bucket - n)
        slices = self._gather_pages(pool, jnp.asarray(ids, jnp.int32))
        flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(slices))
        out: List[Tuple[str, str, Tuple[int, ...], bytes]] = []
        for path, leaf in flat:
            arr = np.asarray(leaf)
            arr = np.take(arr, range(n), axis=_pages_axis(arr.ndim))
            out.append(
                (jax.tree_util.keystr(path), str(arr.dtype), tuple(arr.shape),
                 np.ascontiguousarray(arr).tobytes())
            )
        return out

    def import_page_run(
        self,
        pool: PyTree,
        pages: Sequence[int],
        entries: Sequence[Tuple[str, str, Sequence[int], bytes]],
    ) -> PyTree:
        """Scatter a received page run into freshly allocated ``pages`` of
        ``pool`` (donated).  Validates every entry against the engine's own
        pool leaves — name set, dtype, and shape (with the pages axis equal
        to ``len(pages)``) — and raises ValueError on any mismatch, so a
        frame from a differently configured peer is rejected before a byte
        lands in the pool.  Pads ids/payload up to the gather/scatter bucket
        (pad writes land in the null page)."""
        self._require_paged()
        n = len(pages)
        if n < 1:
            raise ValueError("empty page run")
        bucket = self._page_run_bucket(n)
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.pool_shapes())
        by_name = {jax.tree_util.keystr(p): leaf for p, leaf in flat}
        got = {e[0]: e for e in entries}
        if set(got) != set(by_name):
            raise ValueError(
                f"page-run leaves mismatch: got {sorted(got)}, want {sorted(by_name)}"
            )
        vals = []
        for path, spec in flat:
            name = jax.tree_util.keystr(path)
            _, dtype, shape, raw = got[name]
            axis = _pages_axis(spec.ndim)
            want = list(spec.shape)
            want[axis] = n
            if str(dtype) != str(spec.dtype) or list(shape) != want:
                raise ValueError(
                    f"page-run leaf {name!r}: got {dtype}{list(shape)}, "
                    f"want {spec.dtype}{want}"
                )
            arr = np.frombuffer(raw, dtype=np.dtype(str(dtype)))
            if arr.size != int(np.prod(shape)):
                raise ValueError(f"page-run leaf {name!r}: payload size mismatch")
            arr = arr.reshape(shape)
            if bucket > n:
                pad = [(0, 0)] * arr.ndim
                pad[axis] = (0, bucket - n)
                arr = np.pad(arr, pad)
            vals.append(arr)
        ids = list(pages) + [NULL_PAGE] * (bucket - n)
        return self._scatter_pages(
            pool,
            jnp.asarray(ids, jnp.int32),
            jax.tree_util.tree_unflatten(treedef, vals),
        )

    def default_prompt_buckets(self) -> Tuple[int, ...]:
        """Every prefill shape a prompt can actually land in: powers of two
        from the bucket minimum up, capped at ``cache_size`` (which is
        itself a bucket when it is not a power of two).  Warming all of
        them means the first long prompt is never a steady-state retrace."""
        buckets: List[int] = []
        t = bucket_length(1)
        while t < self.cache_size:
            buckets.append(t)
            t *= 2
        buckets.append(self.cache_size)
        return tuple(buckets)

    def warmup(
        self,
        batch: int,
        *,
        prompt_buckets: Optional[Sequence[int]] = None,
        packed: bool = False,
        migrate: bool = False,
    ) -> dict:
        """Compile the serving step functions before traffic arrives.
        An online server calls this at startup so the first real request
        pays queueing latency, not XLA compilation.

        Contiguous engine: one prefill per prompt bucket — defaulting to
        *every* power-of-two bucket up to ``cache_size`` (a prompt can land
        in any of them; warming only the smallest made the first long
        prompt a steady-state retrace) — plus one insert and one decode at
        ``batch`` rows.  Paged engine: exactly two shapes total, the
        ``(1, chunk_size)`` prefill chunk and the ``(batch, 1)`` paged
        decode — prompt length no longer appears in any compiled shape.
        Packed paged engine (``packed=True``, requires ``token_budget``):
        one ``step_paged`` compile per token-budget bucket
        (``packed_buckets()``) replaces the chunk/decode/verify trio —
        the scheduler's round then never issues any other model entry, so
        admission/cancel/spec churn cannot retrace.

        Returns a report of what was compiled — shapes plus per-compile
        durations — so operators can log it and compile telemetry can tell
        these expected compiles apart from steady-state retraces."""
        cw = self.compile_watcher
        n_before = len(cw.compile_events())
        if packed:
            self._require_paged()
            buckets = self.packed_buckets()
            W1 = self.block_table_width + 1
            with cw.expected_compiles("warmup"):
                pool = self.init_pool(batch)
                logits = None
                for Tb in buckets:
                    logits, pool = self.step_paged(
                        pool,
                        jnp.zeros((1, Tb), jnp.int32),
                        jnp.full((1, Tb), self.cache_size, jnp.int32),
                        jnp.zeros((batch + 1, W1), jnp.int32),
                        jnp.full((Tb,), batch, jnp.int32),
                    )
                if self.adapter_slots:
                    self.write_adapter_slot(
                        self.adapter_slots - 1, self._factor_template, 0.0
                    )
                if migrate:
                    pool = self._warm_page_run(pool)
                jax.block_until_ready(logits)
            events = cw.compile_events()[n_before:]
            shapes: dict = {"step_paged": [[1, Tb] for Tb in buckets]}
            if self.adapter_slots:
                shapes["adapter_write"] = [self.adapter_slots]
            if migrate:
                shapes["page_run"] = list(self.page_run_buckets())
            return {
                "batch": batch,
                "prompt_buckets": [],
                "packed_buckets": list(buckets),
                "token_budget": self.token_budget,
                "kv_dtype": self.kv_dtype,
                "spec_k": self.spec_k,
                "shapes": shapes,
                "n_compiles": len(events),
                "compiles": [
                    {"fn": ev.fn, "duration_s": round(ev.duration_s, 4), "reason": ev.reason}
                    for ev in events
                ],
            }
        if self.paged:
            with cw.expected_compiles("warmup"):
                pool = self.init_pool(batch)
                _, pool = self.prefill_chunk(
                    jnp.zeros((1, self.chunk_size), jnp.int32),
                    0,
                    pool,
                    jnp.zeros((1, self.block_table_width), jnp.int32),
                )
                logits, pool = self.decode_paged(
                    pool,
                    jnp.zeros((batch, 1), jnp.int32),
                    jnp.zeros((batch, 1), jnp.int32),
                    jnp.zeros((batch, self.block_table_width), jnp.int32),
                )
                if self.spec_k > 0:
                    S = self.spec_k + 1
                    logits, pool = self.verify_paged(
                        pool,
                        jnp.zeros((batch, S), jnp.int32),
                        jnp.full((batch, S), self.cache_size, jnp.int32),
                        jnp.zeros((batch, self.block_table_width + 1), jnp.int32),
                    )
                if self.adapter_slots:
                    # zeros into the last free slot: a no-op write that
                    # compiles the one slot-write program before any tenant
                    # load (warm up BEFORE preloading adapters)
                    self.write_adapter_slot(
                        self.adapter_slots - 1, self._factor_template, 0.0
                    )
                if migrate:
                    pool = self._warm_page_run(pool)
                jax.block_until_ready(logits)
            events = cw.compile_events()[n_before:]
            shapes = {
                "prefill_chunk": [1, self.chunk_size],
                "decode_paged": [batch, 1],
            }
            if self.spec_k > 0:
                shapes["verify_paged"] = [batch, self.spec_k + 1]
            if self.adapter_slots:
                shapes["adapter_write"] = [self.adapter_slots]
            if migrate:
                shapes["page_run"] = list(self.page_run_buckets())
            return {
                "batch": batch,
                "prompt_buckets": [],
                "kv_dtype": self.kv_dtype,
                "spec_k": self.spec_k,
                "shapes": shapes,
                "n_compiles": len(events),
                "compiles": [
                    {"fn": ev.fn, "duration_s": round(ev.duration_s, 4), "reason": ev.reason}
                    for ev in events
                ],
            }
        if prompt_buckets is None:
            prompt_buckets = self.default_prompt_buckets()
        buckets: List[int] = []
        with cw.expected_compiles("warmup"):
            pcache = None
            for bucket in prompt_buckets:
                T = min(bucket_length(bucket), self.cache_size)
                if T not in buckets:
                    buckets.append(T)
                _, pcache = self.prefill(jnp.zeros((1, T), jnp.int32))
            cache = self.init_cache(batch)
            if pcache is not None:
                cache = self.insert(cache, pcache, 0)
            logits, cache = self.decode(
                cache, jnp.zeros((batch, 1), jnp.int32), jnp.zeros((batch, 1), jnp.int32)
            )
            if self.adapter_slots:
                self.write_adapter_slot(
                    self.adapter_slots - 1, self._factor_template, 0.0
                )
            jax.block_until_ready(logits)
        events = cw.compile_events()[n_before:]
        shapes = {
            "prefill": [[1, T] for T in buckets],
            "insert": [[batch], [1]],
            "decode": [batch, 1],
        }
        if self.adapter_slots:
            shapes["adapter_write"] = [self.adapter_slots]
        return {
            "batch": batch,
            "prompt_buckets": buckets,
            "shapes": shapes,
            "n_compiles": len(events),
            "compiles": [
                {"fn": ev.fn, "duration_s": round(ev.duration_s, 4), "reason": ev.reason}
                for ev in events
            ],
        }

    def paged_programs(self, batch: int) -> dict:
        """Every jitted paged entry point this engine serves with, by name,
        with the abstract arguments of its one compiled shape at ``batch``
        rows: ``{name: (jitted, args)}``.  ``jitted.lower(*args).compile()``
        is the program the serve loop runs — what :meth:`memory_plans` plans
        and what the tests read (aliasing, what is copied) — with no device
        work."""
        self._require_paged()
        i32 = jnp.int32
        pool = self.pool_shapes(batch)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, i32)

        def tables(rows: int, width: int) -> dict:
            kinds = {PAGED: ints(rows, width)}
            if self._ring is not None:
                kinds[RING] = ints(rows, self._ring.table_width)
            return kinds

        W, C = self.block_table_width, self.chunk_size
        programs = {
            "prefill_chunk": (
                self._prefill_chunk, (self.params, ints(1, C), ints(1, C), pool, tables(1, W), ints(1))
            ),
            "decode_paged": (
                self._decode_paged,
                (self.params, pool, ints(batch, 1), ints(batch, 1), tables(batch, W), ints(batch)),
            ),
        }
        if self.spec_k > 0:
            S = self.spec_k + 1
            programs["verify_paged"] = (
                self._verify_paged,
                (self.params, ints(batch, S), ints(batch, S), pool, tables(batch, W + 1), ints(batch)),
            )
        if self.token_budget:
            Tb = self.token_budget
            programs["step_paged"] = (
                self._step_paged,
                (self.params, ints(1, Tb), ints(1, Tb), pool, tables(batch + 1, W + 1), ints(Tb), ints(Tb)),
            )
        return programs

    def memory_plans(self, batch: int, *, prompt_buckets: Optional[Sequence[int]] = None) -> dict:
        """Static HBM plans for every jitted serving entry point (per-bucket
        prefill, insert, decode at ``batch`` rows — or :meth:`paged_programs`
        when paged) plus the per-pytree breakdown of what stays resident
        (params, KV cache).  On a paged engine the ``kv_cache`` entry is the
        shared page pool, whose bytes scale with ``num_pages`` rather than
        ``max_batch × cache_size``.

        Uses AOT lower+compile, which does NOT warm the traced-call cache —
        each plan pays a real compile (tagged expected), so call this at
        startup or in reports, not per request.  Off-accelerator the XLA
        numbers describe host buffers, but the relative breakdown holds."""
        i32 = jnp.int32
        if self.paged:
            plans: dict = {
                "pytree": obs_memory.pytree_breakdown(
                    {"params": self.params, "kv_cache": self.pool_shapes(batch)}
                )
            }
            for name, (jitted, args) in self.paged_programs(batch).items():
                plans[name] = obs_memory.plan_for(jitted, *args)
            return plans
        if prompt_buckets is None:
            prompt_buckets = self.default_prompt_buckets()
        plans = {
            "pytree": obs_memory.pytree_breakdown(
                {"params": self.params, "kv_cache": self.cache_shapes(batch)}
            )
        }
        dcache = self.cache_shapes(batch)
        pcache1 = self.cache_shapes(1)
        # AOT plans bypass __call__, so the watcher never sees them — no
        # expected_compiles block needed
        for bucket in prompt_buckets:
            T = min(bucket_length(bucket), self.cache_size)
            plans[f"prefill_b{T}"] = obs_memory.plan_for(
                self._prefill,
                self.params,
                jax.ShapeDtypeStruct((1, T), i32),
                jax.ShapeDtypeStruct((1, T), i32),
                pcache1,
                jax.ShapeDtypeStruct((1,), i32),
            )
        plans["insert"] = obs_memory.plan_for(
            self._insert, dcache, pcache1, jax.ShapeDtypeStruct((), i32)
        )
        plans["decode"] = obs_memory.plan_for(
            self._decode,
            self.params,
            dcache,
            jax.ShapeDtypeStruct((batch, 1), i32),
            jax.ShapeDtypeStruct((batch, 1), i32),
            jax.ShapeDtypeStruct((batch,), i32),
        )
        return plans

    # -- convenience: one-shot batch generation ------------------------------

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        max_new_tokens: int,
        sampling: SamplingParams = SamplingParams(),
        eos_id: Optional[int] = None,
        key: Optional[jax.Array] = None,
        adapter_idx: Optional[Sequence[int]] = None,
    ) -> List[List[int]]:
        """Batch generation without continuous batching: pad all prompts to one
        bucket, prefill, then decode until every row hits EOS/max_new_tokens.
        The scheduler (serve/scheduler.py) is the production path; this is the
        one-shot ``--prompt`` path and the parity-test oracle."""
        if not prompts:
            return []
        if key is None:
            key = jax.random.PRNGKey(0)
        lengths = np.array([len(p) for p in prompts], np.int32)
        if lengths.min() < 1:
            raise ValueError("empty prompt")
        T = min(bucket_length(int(lengths.max())), self.cache_size)
        if int(lengths.max()) + max_new_tokens > self.cache_size:
            raise ValueError(
                f"prompt ({lengths.max()}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds cache capacity {self.cache_size}"
            )
        B = len(prompts)
        ids = np.zeros((B, T), np.int32)
        for i, p in enumerate(prompts):
            ids[i, : lengths[i]] = np.asarray(p, np.int32)

        idx = None
        if adapter_idx is not None:
            idx = jnp.asarray(adapter_idx, jnp.int32)
        logits, cache = self.prefill(jnp.asarray(ids), lengths, adapter_idx=idx)
        last = jnp.take_along_axis(
            logits, jnp.asarray(lengths - 1)[:, None, None], axis=1
        )[:, 0, :]
        token = self._sample(
            last,
            jax.random.fold_in(key, 0),
            temperature=sampling.temperature,
            top_k=sampling.top_k,
            top_p=sampling.top_p,
        )
        pos = jnp.asarray(lengths, jnp.int32)
        out: List[List[int]] = [[] for _ in range(B)]
        done = np.zeros(B, bool)
        for step in range(max_new_tokens):
            host_tok = np.asarray(token)
            for i in range(B):
                if not done[i]:
                    out[i].append(int(host_tok[i]))
                    if eos_id is not None and host_tok[i] == eos_id:
                        done[i] = True
            if done.all() or step == max_new_tokens - 1:
                break
            logits, cache = self.decode(cache, token[:, None], pos[:, None], adapter_idx=idx)
            pos = pos + 1
            token = self._sample(
                logits,
                jax.random.fold_in(key, step + 1),
                temperature=sampling.temperature,
                top_k=sampling.top_k,
                top_p=sampling.top_p,
            )
        return out
