"""Which code counts as "hot path" for the RTL2xx host-sync rules.

Hot means: executed once per training update or once per decode step, where
a single stray ``.item()`` / ``np.asarray`` blocks the host on the device
every single step.  Code
at save/eval/merge cadence is *not* hot — syncs there are intentional and
either live in non-hot helper functions or carry a baseline justification.

Three ways a region becomes hot, checked in order:

1. the file's repo-relative path ends with a key of :data:`HOT_FUNCTIONS`
   and the enclosing function's qualname matches one of the listed
   prefixes (an empty-string prefix marks the whole file, module level
   included);
2. the file contains the literal marker comment ``relora-lint: hot-path``
   (whole file; used by fixtures and by new modules that want the strict
   rules without editing this table);
3. the ``FileContext`` was built with ``force_hot=True`` (tests).

The sanctioned fix for a genuine sync need is to move it into a helper
*outside* the hot functions, called at a logging/metrics cadence —
``train/trainer._pull_metric_records`` is the model citizen.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

from relora_tpu.analysis.core import FileContext

#: repo-relative path suffix -> hot function qualname prefixes ("" = whole file)
HOT_FUNCTIONS: Dict[str, List[str]] = {
    "relora_tpu/train/step.py": [""],  # every step builder is jitted hot code
    # kernel + dispatch modules: traced inside every LoRA linear, training
    # and decode both — a host sync here hits once per layer per step
    "relora_tpu/ops/pallas_lora_matmul.py": [""],
    "relora_tpu/ops/lora_dispatch.py": [""],
    "relora_tpu/ops/pallas_quant_matmul.py": [""],
    "relora_tpu/train/trainer.py": [
        "Trainer.fit",  # the update loop, including nested closures
        "Trainer._prefetched",
        "Trainer.evaluate",  # per-batch eval loop (syncs every sync_every)
    ],
    # decode attention (contiguous + paged gather) traces inside every
    # decode step; a host sync here stalls every active stream
    "relora_tpu/ops/attention.py": [
        "cached_attention",
        "gather_kv_pages",
        "paged_cached_attention",
        "dequantize_gathered_pages",
        "paged_decode_attention",
    ],
    "relora_tpu/ops/attention_dispatch.py": [""],
    "relora_tpu/serve/engine.py": [
        "InferenceEngine.prefill",
        "InferenceEngine.decode",
        "InferenceEngine.insert",
        "InferenceEngine.init_cache",
        "InferenceEngine.prefill_chunk",  # paged: once per round
        "InferenceEngine.decode_paged",  # paged: every decode step
        "InferenceEngine.init_pool",
        "InferenceEngine._row_idx",  # adapter routing, once per prefill/decode
        # model-drafted speculation: the draft forward runs per prefill
        # chunk / per draft-proposal step, right inside the round
        "InferenceEngine.draft_prefill_chunk",
        "InferenceEngine.draft_decode_paged",
    ],
    # multi-tenant registry: acquire/release run inside the schedulers' admit
    # and retire passes, once per request per round.  Loads and evictions do
    # intentional device writes at swap cadence in _load_into — a separate
    # non-hot helper, following the sanctioned pattern above.
    "relora_tpu/serve/adapters.py": [
        "AdapterRegistry.acquire",
        "AdapterRegistry.release",
    ],
    "relora_tpu/serve/sampling.py": [""],  # jitted per decode step
    # serve/paging.py carries the HOT_MARKER comment instead of an entry
    # here: the allocator + prefix cache run on every paged admit/retire
    "relora_tpu/serve/scheduler.py": [
        "ContinuousBatchingScheduler.run",  # the drain loop
        "ContinuousBatchingScheduler.step",  # one admit-plus-decode round
        "ContinuousBatchingScheduler._sample_rows",  # per decode step
        "PagedContinuousBatchingScheduler.step",  # one budgeted round
        "PagedContinuousBatchingScheduler._admit_pass",  # per round
        "PagedContinuousBatchingScheduler._prefill_pass",  # per round
        # its two halves, also run from step: a chunk sent behind the decode
        # ahead of its pull, and landed at the next round's start
        "PagedContinuousBatchingScheduler._send_chunk",
        "PagedContinuousBatchingScheduler._land_chunk",
        "PagedContinuousBatchingScheduler._pull_first",  # a first token's read
        # --spec model: K autoregressive draft forwards per decode round
        "PagedContinuousBatchingScheduler._model_draft_pass",
        "ContinuousBatchingScheduler._acquire_adapter",  # per admitted request
        "ContinuousBatchingScheduler._release_adapter",  # per retired request
        # disaggregation seams that run on the model thread, inside the
        # round: export-and-park after a prefill finishes, adopt-and-resume
        # on the receiver, peer prefix fetch during admission.  The async
        # transfer itself (server._migrate_task and the /internal handlers)
        # is event-loop code that never touches device values — deliberately
        # NOT hot, same scoping as the rest of the HTTP front-end.
        "PagedContinuousBatchingScheduler._maybe_migrate",
        "PagedContinuousBatchingScheduler.submit_migrated",
        "PagedContinuousBatchingScheduler._fetch_prefix",
        "PagedContinuousBatchingScheduler.migration_commit",
        "PagedContinuousBatchingScheduler.migration_failed",
        "PagedContinuousBatchingScheduler.migration_abort",
    ],
    # role classification and the fleet prefix-page directory run per
    # routed request / per collector scrape on threads adjacent to the
    # serving plane; wire.py (framing) is transfer-cadence and stays cold
    "relora_tpu/serve/disagg.py": [
        "classify_request",
        "PrefixPageDirectory.update",
        "PrefixPageDirectory.lookup",
        "pick_peers",
    ],
    # the HTTP front-end's model thread calls scheduler.step() in a loop; a
    # stray sync there stalls every in-flight stream.  The asyncio handlers
    # and admission.py are host-side code that never touches device values —
    # deliberately NOT hot, so RTL2xx stays scoped to the decode loop.
    "relora_tpu/serve/server.py": [
        "GenerateServer._model_loop",
        "GenerateServer._drain_disagg_inbox",  # runs inside _model_loop's round
    ],
    # the tracer/metrics/flight-recorder run INSIDE the hot loops above (a
    # few spans per decode step / train update) — stdlib-only by design;
    # marking them hot keeps device syncs and hot-loop footguns out
    "relora_tpu/obs/tracer.py": [""],
    "relora_tpu/obs/metrics.py": [""],
    "relora_tpu/obs/flight.py": [""],
    # compile watcher wraps every jitted entry point (its __call__ runs per
    # train update and per decode step); the memory poller is cadence-gated
    # by contract — hot registration keeps device syncs out of both
    "relora_tpu/obs/compile.py": [""],
    "relora_tpu/obs/memory.py": [""],
    # fleet-tier entry points (PR-18 drift fix): these run once per scrape /
    # scale decision / monitor tick, not per decode step, but they execute on
    # dedicated threads next to the model loop — a device sync or hot-loop
    # footgun here stalls the serving plane just the same.  Registration also
    # puts them under the RTL6xx thread-root analysis via the call graph.
    "relora_tpu/serve/autoscale.py": [
        "Autoscaler._loop",
        "Autoscaler.step",
        "AutoscalerPolicy.decide",
    ],
    "relora_tpu/serve/deploy.py": [
        "CheckpointWatcher._run",
        "CheckpointWatcher.poll_once",
        "RollingUpdater.run",
    ],
    "relora_tpu/serve/supervisor.py": [
        "ReplicaSupervisor.scale_up",
        "ReplicaSupervisor.scale_down",
        "ReplicaSupervisor._monitor_loop",
        "ReplicaSupervisor._check",
    ],
    "relora_tpu/train/elastic.py": [
        "reshard_tree",
        "restore_resharded",
    ],
    "relora_tpu/obs/fleet.py": [
        "FleetCollector._loop",
        "FleetCollector.scrape_once",
        "FleetCollector._scrape_target",
        "FleetCollector._ingest_metrics",
        "SeriesStore.add_samples",
        "SeriesStore.add_event",
    ],
}

HOT_MARKER = "relora-lint: hot-path"


def hot_prefixes(ctx: FileContext) -> Sequence[str]:
    """Hot qualname prefixes for this file; empty sequence = nothing hot.
    A [""] result marks the whole file (module level included)."""
    if ctx.force_hot or HOT_MARKER in ctx.text:
        return [""]
    for suffix, prefixes in HOT_FUNCTIONS.items():
        if ctx.relpath.endswith(suffix):
            return prefixes
    return ()


def qualname_is_hot(qualname: str, prefixes: Sequence[str]) -> bool:
    for prefix in prefixes:
        if prefix == "":
            return True
        if qualname == prefix or qualname.startswith(prefix + "."):
            return True
    return False
