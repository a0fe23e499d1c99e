"""Serving cells of the ``afmoe`` family (Arcee Trinity): what ``serve.py
--paged`` builds, driven as ``drivers/serve.py`` drives its cells — the same
closed loop, the same window, the same end-to-end arithmetic, imported
unchanged — over this family's weights, reference and required operations,
as ``drivers/serve_mimo.py`` (whose docstring says what a family's driver
brings) does for its own.  What is no family's — the counters the loop
differences and the reading of the decode program's operations — is that
driver's, imported.

``build`` imports the family's model first: a commit without it fails there,
at once, before any weight is made.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

import numpy as np

from benchmark import flops_afmoe, harness, weights_afmoe
from benchmark.drivers import serve as base
from benchmark.drivers.serve import (  # noqa: F401  (the family-independent parts, as they are)
    Tracer, drive, end_to_end, one_request, sample_for_check, tokens_in_window,
)
from benchmark.drivers.serve_mimo import COUNTERS, decode_ops
from benchmark.traffic.requests import make_requests


def _dtype(cell: harness.Cell):
    import jax.numpy as jnp

    return {"bf16": jnp.bfloat16, "f32": jnp.float32}[cell.workload["dtype"]]


def build(cell: harness.Cell, seed: int):
    """Engine, scheduler and server over the benchmark's weights."""
    import relora_tpu.models.afmoe  # noqa: F401  (first: a commit without the family stops here)

    import jax

    from relora_tpu.config.model import load_model_config
    from relora_tpu.serve.engine import InferenceEngine
    from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler
    from relora_tpu.serve.server import GenerateServer

    w, dtype = cell.workload, _dtype(cell)
    model_cfg = load_model_config(cell.config_file)
    params = weights_afmoe.make_weights(cell.config, seed, dtype)
    engine = InferenceEngine(
        model_cfg, params, cache_size=w["cache_size"], dtype=dtype,
        page_size=w["page_size"], num_pages=w["num_pages"], chunk_size=w["chunk_size"], kv_dtype=w["kv_dtype"],
    )
    del params
    check_tree(engine, cell, dtype)
    engine.warmup(w["max_batch"])
    scheduler = PagedContinuousBatchingScheduler(
        engine, max_batch=w["max_batch"], eos_id=w["eos_id"], top_k=0,
        key=jax.random.PRNGKey(seed % (2**31 - 1)), prefix_cache=w["prefix_cache"],
    )
    server = GenerateServer(scheduler, port=0, max_queue=w["max_queue"])
    return engine, scheduler, server


def check_tree(engine, cell: harness.Cell, dtype) -> None:
    """The engine's model must want the very tree the configuration gives,
    and the engine must hold every matrix in the type it was handed."""
    import jax
    import jax.numpy as jnp

    from relora_tpu.models.params_util import init_params

    abstract = jax.eval_shape(lambda: init_params(engine.model, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    have = {p: tuple(v.shape) for p, v in weights_afmoe.flatten(abstract).items()}
    want = {p: tuple(s) for p, s in weights_afmoe.flatten(weights_afmoe.param_shapes(cell.config)).items()}
    if have != want:
        raise RuntimeError(f"the server's parameter tree is not the configuration's: {sorted(set(have.items()) ^ set(want.items()))[:6]}")
    widened = [p for p, v in weights_afmoe.flatten(engine.params).items() if v.ndim > 1 and v.dtype != dtype]
    if widened:
        raise RuntimeError(f"the engine holds {widened[:4]} in another type than the {dtype} it was handed")


def served_gap(cell: harness.Cell, seed: int, sample: list, cast=None, faults=()) -> dict:
    """How far a served token's reference logit lies below the reference's
    best, over the sample: the widest gap and the mean one.  With ``cast`` or
    ``faults`` the control's: the token a forward pass in that precision, or
    with that fault planted, puts first, at the same positions.  The widest
    gap is set by the rare token where bf16 arithmetic flips a router's
    choice between a held and an absent expert; the mean is what a part of
    the mathematics left out moves."""
    from benchmark.reference import afmoe

    t = cell.traffic
    pad_to = t["prompt_tokens"]["max"] + t["max_new_tokens"]["max"]
    seqs = [r["prompt"] + r["tokens"] for r in sample]
    padded = np.asarray([s + [0] * (pad_to - len(s)) for s in seqs], np.int32)
    gaps = np.asarray(afmoe.gaps(cell.config, seed, padded, afmoe.CASTS[cast or "f32"], tuple(faults), dtype=_dtype(cell)))
    # position p's logits choose token p + 1: the served tokens sit at n_prompt .. n - 1
    served = np.concatenate([row[len(r["prompt"]) - 1 : len(seq) - 1] for r, seq, row in zip(sample, seqs, gaps)])
    return {"gap": float(served.max()), "mean_gap": float(served.mean()), "tokens": len(served)}


def required_flops(cfg: dict, results: list, t_open: float, t_close: float, assignments_local: float) -> float:
    """Forward FLOPs of the work the window saw: the prompt of every request
    whose first token arrived in it, every output token that arrived in it,
    and the held experts' part from what the program counted as routed to them."""
    total = flops_afmoe.expert_flops(cfg, assignments_local)
    for r in results:
        if not r["ok"]:
            continue
        n_prompt = len(r["prompt"])
        if t_open <= r["times"][0] <= t_close:
            total += flops_afmoe.serve_flops_span(cfg, 0, n_prompt)
        # output token i (i >= 1) was computed by a decode step at position n_prompt + i - 1
        n_out = sum(1 for t in r["times"][1:] if t_open <= t <= t_close)
        total += flops_afmoe.serve_flops_span(cfg, n_prompt, n_prompt + n_out)
    return total


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, compiles: harness.CompileCounter) -> dict:
    import jax

    w, t = cell.workload, cell.traffic
    trace_dir = os.path.join(harness.ROOT, ".bench_work", cell.name, "trace") if trace else None
    engine, scheduler, server = build(cell, seed)
    requests = make_requests(t, cell.config["vocab_size"], seed)
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    base.COUNTERS = COUNTERS  # the loop reads the counters it differences from its module
    out = asyncio.run(drive(server, cell, requests, seconds, trace_dir, compiles))
    results, t_open, t_close = out["results"], out["t_open"], out["t_close"]
    peak = harness.memory_peak_bytes()
    retraces = engine.compile_watcher.steady_state_retraces

    # free the program's state before the reference runs
    jax.tree_util.tree_map(lambda x: x.delete(), engine.params)
    if scheduler._pool is not None:
        jax.tree_util.tree_map(lambda x: x.delete(), scheduler._pool)
    del engine, scheduler, server
    t_ref = harness.now()
    sample = sample_for_check([r for r in results if r["times"] and r["times"][-1] >= t_open], seed, w["checked_requests"])
    check = harness.Check()
    if sample:
        gap = served_gap(cell, seed, sample)
        check.add("served_logit_gap", gap["gap"], w["limits"]["served_logit_gap"])
        check.add("served_logit_gap_mean", gap["mean_gap"], w["limits"]["served_logit_gap_mean"])
    sent = [r for r in results if t_open <= r["t_send"] <= t_close]
    check.add("requests_failed", sum(1 for r in results if not r["ok"]), 0)
    check.add("compiles_in_window", out["compiles_in_window"] + retraces, 0)
    counters = out["counters"]
    print(f"window counters {json.dumps(counters)}", file=sys.stderr, flush=True)
    return {
        "end_to_end": end_to_end(results, t_open, t_close),
        "attempted": len(sent),
        "failed": sum(1 for r in sent if not r["ok"]),
        "t_open": t_open,
        "window_s": t_close - t_open,
        "memory_peak_bytes": peak,
        "check": check,
        "reference_s": harness.now() - t_ref,
        "trace_dir": trace_dir,
        "trace_span": out["trace_span"],
        "debug": {"sample": sample},
        "obs": {
            "counters": dict(counters, compiles_in_window=out["compiles_in_window"]),
            "host": {},
            "inside": decode_ops(trace_dir) if out["trace_span"] else {},
            "work": {
                "required_flops": required_flops(
                    cell.config, results, t_open, t_close, counters["moe_assignments_local_total"]
                )
            },
        },
    }
