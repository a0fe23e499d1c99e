"""relora-tpu inference CLI — generate from a ReLoRA (or full-rank) checkpoint.

Loads a ``model_{step}`` checkpoint dir, merges any LoRA factors into the base
kernels (train/checkpoint.restore_serving_params), and generates with the
KV-cache engine (relora_tpu/serve).  Two modes:

- one-shot: ``--prompt`` (repeatable) generates for the given prompts and
  prints one result per line;
- request loop: ``--input-file FILE`` (or ``-`` for stdin) reads one request
  per line and drains them through the continuous-batching scheduler;
- online server: ``--port`` launches the async HTTP front-end
  (relora_tpu/serve/server.py) — ``POST /v1/generate`` with SSE token
  streaming, ``/healthz``, ``/metrics``, bounded admission (429 on
  overload), and SIGTERM graceful drain.  See docs/serving.md.

Prompts are token ids (comma- or space-separated ints) by default, so the CLI
has no tokenizer dependency; ``--tokenizer NAME`` opts into HF tokenization
when ``transformers`` is installed.

Examples::

    # greedy one-shot over token-id prompts
    python serve.py --checkpoint ckpts/relora/model_20000 \
        --model_config llama_250m --prompt "1 15 27 4" --max-new-tokens 32

    # sampled request loop from a file, 8 decode slots
    python serve.py --checkpoint ckpts/relora/model_20000 \
        --model_config llama_250m --input-file prompts.txt \
        --temperature 0.8 --top-p 0.9 --max-batch 8 --run-dir runs/serve

    # online HTTP server, 8 decode slots, 64 waiting requests max
    python serve.py --checkpoint ckpts/relora/model_20000 \
        --model_config llama_250m --port 8000 --max-batch 8 --max-queue 64
"""

from __future__ import annotations

import argparse
import os
import sys


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--checkpoint", default=None, help="model_{step} checkpoint dir")
    p.add_argument(
        "--random-init",
        action="store_true",
        help="serve randomly initialized weights instead of a checkpoint "
        "(load/fault drills and the bench harness; garbage tokens, real serving "
        "path)",
    )
    p.add_argument(
        "--model_config",
        required=True,
        help="zoo name (llama_35m), HF config JSON path, or dir with config.json",
    )
    p.add_argument("--prompt", action="append", default=[], help="one-shot prompt (repeatable)")
    p.add_argument("--input-file", default=None, help="request file, one prompt per line ('-' = stdin)")
    p.add_argument("--tokenizer", default=None, help="HF tokenizer name (default: token-id prompts)")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0, help="0 = greedy")
    p.add_argument("--top-k", type=int, default=0, help="0 disables")
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--eos-id", type=int, default=None, help="default: model config eos_token_id")
    p.add_argument("--cache-size", type=int, default=None, help="default: max_sequence_length")
    p.add_argument("--max-batch", type=int, default=4, help="decode slots (request-loop mode)")
    p.add_argument(
        "--dtype", choices=["f32", "bf16"], default="f32",
        help="the type the forward computes in and the engine holds its weights in: a "
        "checkpoint is rounded to it once, at load and at every reload (norm scales, "
        "lora_s and quantization scales stay f32)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--run-dir", default=None, help="metrics.jsonl destination (request-loop/server mode)")
    p.add_argument("--port", type=int, default=None, help="launch the HTTP server on this port (0 = ephemeral)")
    p.add_argument("--host", default="127.0.0.1", help="server bind address")
    p.add_argument("--max-queue", type=int, default=64, help="server: max waiting requests before 429")
    p.add_argument("--port-file", default=None, help="server: write the bound port here once listening")
    p.add_argument("--no-warmup", action="store_true", help="server: skip compile warmup at startup")
    p.add_argument(
        "--watch-checkpoints",
        default=None,
        metavar="DIR",
        help="server: poll DIR/latest (published by the trainer at every "
        "manifest commit) and hot-swap verified new checkpoints in place — "
        "zero downtime, in-flight requests finish on the old weights "
        "(docs/operations.md continuous deployment); requires --port",
    )
    p.add_argument(
        "--watch-interval-s",
        type=float,
        default=2.0,
        help="checkpoint watcher poll interval",
    )
    p.add_argument(
        "--stall-timeout-s",
        type=float,
        default=0.0,
        help="server: decode-progress watchdog — no scheduler step for this "
        "long flips /healthz to 503 'stuck' and dumps the flight recorder "
        "(0 disables; set it above your worst cold compile, or warm up first)",
    )
    p.add_argument(
        "--paged",
        action="store_true",
        help="block-granular paged KV cache: chunked prefill interleaved with "
        "decode, page-pool admission (queue on exhaustion, never reject), "
        "prefix caching (docs/serving.md)",
    )
    p.add_argument("--page-size", type=int, default=16, help="paged: tokens per KV page")
    p.add_argument(
        "--num-pages",
        type=int,
        default=0,
        help="paged: pool capacity in pages (0 = max_batch full-length "
        "requests plus the null page)",
    )
    p.add_argument("--chunk-size", type=int, default=64, help="paged: prefill chunk length")
    p.add_argument(
        "--packed",
        action="store_true",
        help="paged: packed mixed-batch rounds — ONE step_paged dispatch per "
        "round carrying every decode/verify window plus token-budget prefill "
        "from multiple slots (Sarathi-style; token-identical output, "
        "docs/serving.md)",
    )
    p.add_argument(
        "--token-budget",
        type=int,
        default=0,
        help="packed: max tokens per packed dispatch (0 = max_batch x "
        "(spec_k+1) + chunk_size); larger buckets raise throughput per "
        "dispatch, smaller bound per-round TTFT/TPOT jitter",
    )
    p.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor-parallel group size: shard params (and the paged KV "
        "pool over kv-heads, scaling --num-pages per chip) across this many "
        "devices as ONE replica (docs/parallelism.md)",
    )
    p.add_argument(
        "--kv-dtype",
        choices=("bf16", "int8"),
        default="bf16",
        help="paged: KV pool storage — bf16 stores at the compute dtype, "
        "int8 quantizes pages (per-page/kv-head absmax scales, ~half the "
        "pool HBM, so ~double the pages per chip; docs/serving.md)",
    )
    p.add_argument(
        "--no-prefix-cache",
        action="store_true",
        help="paged: disable shared-prefix page reuse",
    )
    p.add_argument(
        "--role",
        choices=("prefill", "decode", "mixed"),
        default="mixed",
        help="disaggregated fleet role (docs/serving.md): 'prefill' replicas "
        "hand finished prompts' KV pages to a decode peer over "
        "/internal/migrate, 'decode' replicas adopt them, 'mixed' serves "
        "everything (the fallback pool); requires --paged for prefill/decode",
    )
    p.add_argument(
        "--peer-file",
        default=None,
        help="disagg: supervisor-maintained peers.json roster path (prefill "
        "replicas pick migration targets from it); requires --port",
    )
    p.add_argument(
        "--fleet-url",
        default=None,
        help="disagg: the collector's /fleet/prefix directory — 'host:port' "
        "or a file containing the port (the supervisor's router.port); a "
        "local prefix-cache miss becomes a peer page fetch; requires --port",
    )
    p.add_argument(
        "--migrate-timeout-s",
        type=float,
        default=30.0,
        help="disagg: per-I/O timeout on the migration wire transfer",
    )
    p.add_argument(
        "--spec",
        choices=("off", "ngram", "model"),
        default="off",
        help="paged: speculative decoding — 'ngram' drafts continuations by "
        "prompt lookup over each request's own context; 'model' runs a "
        "pruned draft model (--draft-checkpoint) autoregressively for K "
        "proposals; both verify K per step in one forward and greedy "
        "output stays token-identical (docs/serving.md, "
        "docs/compression.md)",
    )
    p.add_argument(
        "--spec-k",
        type=int,
        default=4,
        help="speculative: drafted tokens per verify step (compiled window "
        "is spec-k+1 wide; only meaningful with --spec ngram/model)",
    )
    p.add_argument(
        "--draft-checkpoint",
        default=None,
        help="--spec model: a pruned+merged draft checkpoint dir (model_N, "
        "from relora_tpu.compress.draft / export_hf --pruned) with the "
        "same architecture as the base; loads next to the base weights "
        "and shares the one KV page pool",
    )
    p.add_argument("--no-scan", action="store_true", help="checkpoint was trained with scan_layers=false")
    p.add_argument(
        "--no-merge",
        action="store_true",
        help="serve LoRA factors unmerged (quantized bases / adapter hot-swap); "
        "the decode forward routes the composite through ops/lora_dispatch",
    )
    p.add_argument(
        "--adapter-dir",
        default=None,
        help="multi-tenant serving: directory of unmerged adapter checkpoint "
        "dirs (one subdir per tenant, each with a relora_config.json "
        'sidecar); requests pick one via the "adapter" body field and decode '
        "through the grouped per-row LoRA kernel (docs/serving.md); "
        "requires --no-merge",
    )
    p.add_argument(
        "--adapters",
        default=None,
        help="comma-separated adapter names to preload into slots at startup "
        "(warm tenants skip the first-request load stall); requires "
        "--adapter-dir",
    )
    p.add_argument(
        "--adapter-slots",
        type=int,
        default=None,
        help="HBM adapter slot pool size, including the reserved identity "
        "slot 0 (default 4); requires --adapter-dir",
    )
    return p.parse_args(argv)


def _encode(text: str, tokenizer):
    if tokenizer is not None:
        return tokenizer.encode(text)
    try:
        return [int(t) for t in text.replace(",", " ").split()]
    except ValueError:
        raise SystemExit(
            f"prompt {text!r} is not a token-id list; pass --tokenizer to use text prompts"
        )


def _decode_tokens(tokens, tokenizer) -> str:
    if tokenizer is not None:
        return tokenizer.decode(tokens)
    return " ".join(str(t) for t in tokens)


def main(argv=None) -> int:
    from relora_tpu.utils.logging import enable_compile_cache, get_logger

    args = parse_args(argv)
    # a restarted server loads prefill/decode/warm-up programs from disk
    enable_compile_cache()
    logger = get_logger("relora_tpu.serve")

    from relora_tpu.utils import faults

    if faults.active():
        # a drill must never be mistaken for production: say so, loudly, once
        logger.warning(faults.summary())

    if args.prompt and args.input_file:
        raise SystemExit(
            "--prompt and --input-file are mutually exclusive: one-shot mode "
            "would silently ignore the file; pass one or the other"
        )
    if args.port is not None and (args.prompt or args.input_file):
        raise SystemExit("--port runs the HTTP server; drop --prompt/--input-file")
    if args.adapter_dir is not None and not args.no_merge:
        raise SystemExit(
            "--adapter-dir requires --no-merge (tenant adapters hot-swap "
            "against an unmerged base; a merged checkpoint has no LoRA slots)"
        )
    if args.adapters is not None and args.adapter_dir is None:
        raise SystemExit(
            "--adapters preloads tenant adapters and requires --adapter-dir"
        )
    if args.adapter_slots is not None:
        if args.adapter_dir is None:
            raise SystemExit(
                "--adapter-slots sizes the tenant slot pool and requires "
                "--adapter-dir"
            )
        if args.adapter_slots < 2:
            raise SystemExit(
                f"--adapter-slots must be >= 2 (slot 0 is the reserved "
                f"identity adapter), got {args.adapter_slots}"
            )
    if args.adapter_dir is not None and not os.path.isdir(args.adapter_dir):
        raise SystemExit(f"--adapter-dir {args.adapter_dir} is not a directory")
    if args.role != "mixed" and not args.paged:
        raise SystemExit(
            f"--role {args.role} requires --paged (KV-page migration ships "
            "page runs; the contiguous cache has none)"
        )
    if (args.peer_file or args.fleet_url) and args.port is None:
        raise SystemExit("--peer-file/--fleet-url configure the HTTP server; pass --port")
    if args.watch_checkpoints is not None:
        if args.port is None:
            raise SystemExit(
                "--watch-checkpoints hot-swaps a running server and requires --port"
            )
        if args.random_init:
            raise SystemExit(
                "--watch-checkpoints needs a checkpoint-backed server, not --random-init"
            )

    tokenizer = None
    if args.tokenizer:
        from transformers import AutoTokenizer  # optional dep, opt-in flag

        tokenizer = AutoTokenizer.from_pretrained(args.tokenizer)

    import jax.numpy as jnp

    from relora_tpu.config.model import load_model_config
    from relora_tpu.train.checkpoint import (
        load_lora_spec,
        restore_params_host,
        restore_serving_params,
    )

    model_cfg = load_model_config(args.model_config)
    lora_spec = None
    if args.random_init:
        if args.checkpoint or args.no_merge:
            raise SystemExit("--random-init excludes --checkpoint/--no-merge")
        import jax

        from relora_tpu.models.params_util import init_params
        from relora_tpu.serve.engine import build_decode_model

        logger.info(f"random-init weights for {args.model_config} (drill/bench mode)")
        model = build_decode_model(
            model_cfg,
            cache_size=args.cache_size or model_cfg.max_sequence_length,
            dtype=jnp.bfloat16 if args.dtype == "bf16" else jnp.float32,
        )

        # the decode model declares every leaf in the type the engine holds
        # it in, so the draw is the held tree: no f32 copy exists
        params = jax.jit(lambda key: init_params(model, key, jnp.zeros((1, 8), jnp.int32)))(
            jax.random.PRNGKey(args.seed)
        )
    elif args.checkpoint is None:
        raise SystemExit("pass --checkpoint (or --random-init for drills)")
    else:
        logger.info(f"restoring {args.checkpoint}")
        if args.no_merge:
            lora_spec = load_lora_spec(args.checkpoint)
            if lora_spec is None:
                raise SystemExit(
                    f"--no-merge: {args.checkpoint} has no relora_config.json sidecar "
                    "(full-rank checkpoint? drop the flag)"
                )
            params = restore_params_host(args.checkpoint)
        else:
            params = restore_serving_params(args.checkpoint)

    import jax

    from relora_tpu.serve.engine import InferenceEngine
    from relora_tpu.serve.sampling import SamplingParams

    cache_size = args.cache_size or model_cfg.max_sequence_length
    eos_id = args.eos_id if args.eos_id is not None else model_cfg.eos_token_id
    paged_kwargs = {}
    if args.paged:
        # default pool: every slot at full length simultaneously, + null page
        # (--spec model doubles the per-slot run: admission reserves a second
        # worst-case page run for the draft model's KV)
        slot_pages = cache_size // args.page_size
        if args.spec == "model":
            slot_pages *= 2
        num_pages = args.num_pages or (args.max_batch * slot_pages + 1)
        if args.spec != "off" and args.spec_k < 1:
            raise SystemExit(f"--spec {args.spec} needs --spec-k >= 1, got {args.spec_k}")
        if args.spec == "model":
            if not args.draft_checkpoint:
                raise SystemExit(
                    "--spec model needs --draft-checkpoint (a pruned+merged "
                    "draft export; see docs/compression.md)"
                )
            if args.packed:
                raise SystemExit(
                    "--spec model is incompatible with --packed (the draft "
                    "proposal loop runs on the per-row decode path)"
                )
            if args.role != "mixed":
                raise SystemExit(
                    "--spec model needs --role mixed: draft KV pages cannot "
                    "migrate between disaggregated peers"
                )
            if args.adapter_dir:
                raise SystemExit(
                    "--spec model is incompatible with --adapter-dir (draft "
                    "models and adapter slots share the reload plumbing)"
                )
        elif args.draft_checkpoint:
            raise SystemExit("--draft-checkpoint only applies with --spec model")
        paged_kwargs = dict(
            page_size=args.page_size,
            num_pages=num_pages,
            chunk_size=args.chunk_size,
            kv_dtype=args.kv_dtype,
            spec_k=args.spec_k if args.spec != "off" else 0,
        )
        if args.packed:
            window = (args.spec_k + 1) if args.spec != "off" else 1
            paged_kwargs["token_budget"] = args.token_budget or (
                args.max_batch * window + args.chunk_size
            )
    elif args.packed:
        raise SystemExit(
            "--packed requires --paged (the packed step routes every token "
            "through the paged pool's block tables)"
        )
    elif args.kv_dtype != "bf16":
        p_err = "--kv-dtype int8 requires --paged (the contiguous cache is unquantized)"
        raise SystemExit(p_err)
    elif args.spec != "off":
        raise SystemExit(
            "--spec requires --paged (the verify window writes through the "
            "paged engine's block tables)"
        )
    if args.token_budget and not args.packed:
        raise SystemExit("--token-budget only applies with --packed")
    mesh = None
    if args.tp > 1:
        from relora_tpu.parallel.mesh import MeshSpec, make_mesh

        if len(jax.devices()) < args.tp:
            raise SystemExit(
                f"--tp {args.tp} needs {args.tp} devices, have {len(jax.devices())}"
            )
        mesh = make_mesh(
            MeshSpec(data=1, fsdp=1, tensor=args.tp, sequence=1),
            devices=jax.devices()[: args.tp],
        )
        logger.info(f"tensor-parallel serving over {args.tp} devices")
    adapter_slots = (args.adapter_slots or 4) if args.adapter_dir else 0
    engine = InferenceEngine(
        model_cfg,
        params,
        cache_size=cache_size,
        dtype=jnp.bfloat16 if args.dtype == "bf16" else jnp.float32,
        scan_layers=not args.no_scan,
        lora=lora_spec,
        mesh=mesh,
        adapter_slots=adapter_slots,
        **paged_kwargs,
    )
    if args.spec == "model":
        # the draft shares the engine's compiled prefill/decode programs
        # (identical abstract signature) and the one KV page pool
        logger.info(f"restoring draft model {args.draft_checkpoint}")
        engine.load_draft_params(restore_serving_params(args.draft_checkpoint))
    key = jax.random.PRNGKey(args.seed)

    adapter_registry = None
    if args.adapter_dir:
        from relora_tpu.serve.adapters import AdapterRegistry

        adapter_registry = AdapterRegistry(
            args.adapter_dir,
            adapter_slots,
            expected_r=lora_spec.r,
            writer=engine.adapter_writer(),
        )
        names = adapter_registry.list_adapters()
        logger.info(
            f"adapter registry: {adapter_slots} slots over {args.adapter_dir} "
            f"({len(names)} adapters: {', '.join(names) or 'none'})"
        )

    def build_scheduler(metrics):
        from relora_tpu.serve.scheduler import (
            ContinuousBatchingScheduler,
            PagedContinuousBatchingScheduler,
        )

        common = dict(
            max_batch=args.max_batch,
            eos_id=eos_id,
            top_k=args.top_k,
            metrics=metrics,
            key=key,
            adapter_registry=adapter_registry,
        )
        if args.paged:
            return PagedContinuousBatchingScheduler(
                engine,
                # a family that cannot reuse prefixes yet serves without
                prefix_cache=not args.no_prefix_cache and "prefix reuse" not in engine.refuses,
                spec=args.spec,
                packed=args.packed,
                role=args.role,
                **common,
            )
        return ContinuousBatchingScheduler(engine, **common)

    if args.port is not None:
        from relora_tpu.serve.server import run_server
        from relora_tpu.utils.logging import MetricsLogger

        # _source = replica identity (the supervisor sets RELORA_TPU_REPLICA_ID
        # per replica) so fleet tooling can join this metrics.jsonl against
        # the collector's scraped series by source
        metrics = (
            MetricsLogger(
                run_dir=args.run_dir,
                source=os.environ.get("RELORA_TPU_REPLICA_ID", "serve"),
            )
            if args.run_dir
            else None
        )
        def preload_adapters():
            # preload AFTER warmup: the warmup pass writes a zero adapter
            # into the last slot to compile the slot-write program, which
            # would clobber a preloaded tenant if it ran second
            if adapter_registry is not None and args.adapters:
                for name in [n.strip() for n in args.adapters.split(",") if n.strip()]:
                    try:
                        slot = adapter_registry.acquire(name)
                    except ValueError as e:
                        raise SystemExit(f"--adapters: {e}")
                    adapter_registry.release(name)
                    logger.info(f"preloaded adapter {name!r} into slot {slot}")

        # router-aware warmup: the compile pass runs on the server's model
        # thread, so the listener binds (and the port file lands) first and
        # /healthz answers 503 "warming" until the buckets are paid — a
        # cold replica joining a fleet is discoverable but never routable
        # mid-compile.  --no-warmup keeps the old shape: no warming window,
        # first request pays the compiles.
        warmup_fn = None
        if not args.no_warmup:
            # a disagg replica also warms the page-run gather/scatter programs
            # (export on the donor, import on the receiver) so the first
            # migration is not a steady-state retrace
            disagg_on = args.paged and (
                args.role != "mixed" or bool(args.peer_file) or bool(args.fleet_url)
            )

            def warmup_fn():
                logger.info("warming serving compiles (disable with --no-warmup)")
                report = engine.warmup(
                    args.max_batch, packed=args.packed, migrate=disagg_on
                )
                timings = ", ".join(
                    f"{c['fn']} {c['duration_s']:.2f}s" for c in report["compiles"]
                )
                buckets = report.get("packed_buckets") or report["prompt_buckets"]
                logger.info(
                    f"warmup compiled {report['n_compiles']} programs "
                    f"({'packed' if args.packed else 'prompt'} buckets {buckets}, "
                    f"decode batch {report['batch']}): {timings}"
                )
                if metrics is not None:
                    metrics.event(
                        "warmup",
                        batch=report["batch"],
                        prompt_buckets=report["prompt_buckets"],
                        packed_buckets=report.get("packed_buckets", []),
                        n_compiles=report["n_compiles"],
                    )
                preload_adapters()
                return {"batch": report["batch"], "n_compiles": report["n_compiles"]}
        else:
            preload_adapters()
        scheduler = build_scheduler(metrics)

        from relora_tpu.serve.deploy import CheckpointWatcher, checkpoint_step

        def reload_prepare(path):
            """Host-side half of a weight hot-swap: verify + restore the new
            checkpoint off the model thread, return the device-side apply.
            Raising here fails the reload closed — the server keeps serving
            the old weights untouched."""
            if args.no_merge:
                from relora_tpu.train.checkpoint import verify_checkpoint

                ok, reason = verify_checkpoint(path)
                if not ok:
                    raise ValueError(
                        f"refusing to reload corrupt checkpoint {path}: {reason}"
                    )
                spec = load_lora_spec(path)
                if spec is not None and spec.r != (lora_spec.r if lora_spec else None):
                    raise ValueError(
                        f"reload rank mismatch: serving r={lora_spec.r if lora_spec else None}, "
                        f"{path} has r={spec.r}"
                    )
                new_params = restore_params_host(path)
            else:
                # restore_serving_params verifies the manifest before reading
                new_params = restore_serving_params(path)
            return lambda: engine.reload_params(new_params)

        watcher = None

        def ready(server):
            nonlocal watcher
            if args.port_file:
                with open(args.port_file, "w") as f:
                    f.write(str(server.port))
            if args.watch_checkpoints:
                # standalone self-update: verified new checkpoints from the
                # watcher go straight through the server's reload fence
                def on_new(path):
                    try:
                        apply = reload_prepare(path)
                        req = server.request_reload(
                            apply,
                            checkpoint_step(path) or server.weights_version + 1,
                            path,
                        )
                    except Exception as e:
                        logger.error(f"self-update to {path} failed: {e!r}")
                        return False  # watcher retries on the next poll
                    req.done.wait()
                    if req.ok:
                        logger.info(
                            f"self-update: now serving {path} "
                            f"(weights_version {server.weights_version})"
                        )
                    else:
                        logger.error(f"self-update to {path} failed: {req.error}")
                        return False  # watcher retries on the next poll

                watcher = CheckpointWatcher(
                    args.watch_checkpoints,
                    on_new,
                    interval_s=args.watch_interval_s,
                    current=args.checkpoint,
                ).start()
                logger.info(
                    f"watching {args.watch_checkpoints}/latest every "
                    f"{args.watch_interval_s:g}s for verified checkpoints"
                )

        rc = run_server(
            scheduler,
            host=args.host,
            port=args.port,
            max_queue=args.max_queue,
            peer_file=args.peer_file,
            fleet_url=args.fleet_url,
            migrate_timeout_s=args.migrate_timeout_s,
            default_max_new_tokens=args.max_new_tokens,
            default_temperature=args.temperature,
            default_top_p=args.top_p,
            stall_timeout_s=args.stall_timeout_s,
            metrics=metrics,
            ready_cb=ready,
            warmup_fn=warmup_fn,
            reload_prepare=reload_prepare,
            weights_version=(
                checkpoint_step(args.checkpoint) if args.checkpoint else None
            )
            or 0,
            weights_checkpoint=os.path.abspath(args.checkpoint)
            if args.checkpoint
            else "",
        )
        if watcher is not None:
            watcher.stop()
        if metrics is not None:
            metrics.finish()
        return rc

    if args.prompt:
        prompts = [_encode(t, tokenizer) for t in args.prompt]
        outs = engine.generate(
            prompts,
            max_new_tokens=args.max_new_tokens,
            sampling=SamplingParams(
                temperature=args.temperature, top_k=args.top_k, top_p=args.top_p
            ),
            eos_id=eos_id,
            key=key,
        )
        for tokens in outs:
            print(_decode_tokens(tokens, tokenizer))
        return 0

    if args.input_file is None:
        raise SystemExit("nothing to do: pass --prompt or --input-file")

    from relora_tpu.serve.scheduler import Request
    from relora_tpu.utils.logging import MetricsLogger

    fh = sys.stdin if args.input_file == "-" else open(args.input_file)
    try:
        requests = [
            Request(
                uid=i,
                prompt=_encode(line, tokenizer),
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature,
                top_p=args.top_p,
            )
            for i, line in enumerate(fh)
            if line.strip()
        ]
    finally:
        if fh is not sys.stdin:
            fh.close()
    if not requests:
        raise SystemExit(f"no requests in {args.input_file}")

    metrics = MetricsLogger(run_dir=args.run_dir) if args.run_dir else None
    scheduler = build_scheduler(metrics)
    completions = scheduler.run(requests)
    for uid in sorted(completions):
        print(_decode_tokens(completions[uid].tokens, tokenizer))
    if metrics is not None:
        metrics.finish()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
