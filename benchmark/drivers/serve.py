"""Serving cells: what ``serve.py --random-init --paged`` builds — an
``InferenceEngine``, the paged scheduler and ``GenerateServer`` — in this
process, driven over loopback HTTP through streamed ``POST /v1/generate``.

A closed loop: each client sends its next request when its last one ends.
The window opens after the warm-up requests and closes after ``--seconds``;
requests in flight at the close run to their end (their later tokens are not
counted) so that every request sent in the window has its latencies.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading

import numpy as np

from benchmark import flops, harness, weights
from benchmark.traffic.requests import make_requests

COUNTERS = ("sched_rounds_total", "dispatch_tokens_total", "dispatch_tokens_real_total", "model_dispatches_total")


def build(cell: harness.Cell, seed: int):
    """Engine, scheduler and server over the benchmark's weights."""
    import jax
    import jax.numpy as jnp

    from relora_tpu.config.model import load_model_config
    from relora_tpu.serve.engine import InferenceEngine
    from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler
    from relora_tpu.serve.server import GenerateServer

    w = cell.workload
    model_cfg = load_model_config(cell.config_file)
    params = weights.make_weights(cell.config, seed)
    engine = InferenceEngine(
        model_cfg, params, cache_size=w["cache_size"],
        dtype=jnp.bfloat16 if w["dtype"] == "bf16" else jnp.float32,
        page_size=w["page_size"], num_pages=w["num_pages"], chunk_size=w["chunk_size"], kv_dtype=w["kv_dtype"],
    )
    del params
    check_tree(engine, cell)
    engine.warmup(w["max_batch"])
    scheduler = PagedContinuousBatchingScheduler(
        engine, max_batch=w["max_batch"], eos_id=w["eos_id"], top_k=0,
        key=jax.random.PRNGKey(seed % (2**31 - 1)), prefix_cache=w["prefix_cache"],
    )
    server = GenerateServer(scheduler, port=0, max_queue=w["max_queue"])
    return engine, scheduler, server


def check_tree(engine, cell: harness.Cell) -> None:
    """The engine's model must want the very tree the configuration gives."""
    import jax
    import jax.numpy as jnp

    from relora_tpu.models.params_util import init_params

    abstract = jax.eval_shape(lambda: init_params(engine.model, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    have = {p: tuple(v.shape) for p, v in weights.flatten(abstract).items()}
    want = {p: tuple(s) for p, s in weights.flatten(weights.param_shapes(cell.config)).items()}
    if have != want:
        raise RuntimeError(f"the server's parameter tree is not the configuration's: {sorted(set(have.items()) ^ set(want.items()))[:6]}")


async def one_request(port: int, payload: dict, on_first_token=None) -> dict:
    """One streamed ``POST /v1/generate``; the times its tokens arrived."""
    body = json.dumps(dict(payload, stream=True)).encode()
    t_send = harness.now()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        (
            "POST /v1/generate HTTP/1.1\r\nHost: bench\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
        ).encode()
        + body
    )
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    while (await reader.readline()).strip():
        pass
    times, tokens, finish, error = [], [], None, None
    buf = b""
    while status == 200:
        chunk = await reader.read(65536)
        if not chunk:
            break
        buf += chunk
        t = harness.now()
        while b"\n\n" in buf:
            raw, buf = buf.split(b"\n\n", 1)
            if not raw.startswith(b"data: ") or raw == b"data: [DONE]":
                continue
            event = json.loads(raw[6:])
            if "token" in event:
                if on_first_token is not None and not times:
                    on_first_token()
                times.append(t)
                tokens.append(event["token"])
            elif "finish_reason" in event:
                finish = event
            elif "error" in event:
                error = event["error"]
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionError, OSError):
        pass
    ok = status == 200 and finish is not None and error is None and finish.get("tokens") == tokens
    return {"ok": ok, "status": status, "t_send": t_send, "times": times, "tokens": tokens,
            "prompt": payload["prompt"], "max_new_tokens": payload["max_new_tokens"]}


class Tracer(threading.Thread):
    """Starts and stops the profiler from a thread of its own, so that the
    event loop that serves and sends is not held while the trace is written."""

    def __init__(self, trace_dir: str, after_s: float, for_s: float):
        super().__init__(name="bench-trace", daemon=True)
        self.trace_dir, self.after_s, self.for_s = trace_dir, after_s, for_s
        self.span = None
        self._halt = threading.Event()

    def run(self) -> None:
        import jax

        if self._halt.wait(self.after_s):
            return
        jax.profiler.start_trace(self.trace_dir)
        t0 = harness.now()
        self._halt.wait(self.for_s)
        t1 = harness.now()
        jax.profiler.stop_trace()
        self.span = (t0, t1)

    def finish(self) -> None:
        self._halt.set()
        self.join()


async def drive(server, cell: harness.Cell, requests, seconds: float, trace_dir, compiles) -> dict:
    """The closed loop.  The ramp is set-up: the clients start, and the window
    opens once every client's first request has its first token, so that every
    slot has been prefilled once and every shape is warm.  It closes after
    ``seconds``; what is in flight then runs to its end."""
    t = cell.traffic
    serve_task = asyncio.ensure_future(server.serve_forever(install_signal_handlers=False))
    while not server.started.is_set():
        await asyncio.sleep(0.01)
        if serve_task.done():
            serve_task.result()
    results: list = []
    stats = server.stats
    state = {"first_tokens": 0, "t_stop": float("inf")}
    opened = asyncio.Event()

    def on_first_token() -> None:
        state["first_tokens"] += 1
        if state["first_tokens"] == t["clients"]:
            opened.set()

    async def client() -> None:
        first = True
        while harness.now() < state["t_stop"]:
            results.append(await one_request(server.port, next(requests), on_first_token if first else None))
            first = False

    clients = [asyncio.ensure_future(client()) for _ in range(t["clients"])]
    ramp = asyncio.ensure_future(opened.wait())
    await asyncio.wait([ramp, *clients], return_when=asyncio.FIRST_COMPLETED)
    if not opened.is_set():
        for c in clients:
            c.result()
        raise RuntimeError("the clients ended before the ramp did")
    before = {c: stats.counter_value(c) for c in COUNTERS}
    compiles_before = compiles.count
    tracer = Tracer(trace_dir, cell.workload["trace_after_s"], cell.workload["trace_for_s"]) if trace_dir else None
    t_open = harness.now()
    state["t_stop"] = t_open + seconds
    if tracer:
        tracer.start()
    await asyncio.sleep(seconds)
    t_close = harness.now()
    after = {c: stats.counter_value(c) for c in COUNTERS}
    compiles_in_window = compiles.count - compiles_before
    # requests in flight at the close run to their end: late is late, not wrong
    await asyncio.wait_for(asyncio.gather(*clients), timeout=120 + seconds)
    if tracer:
        tracer.finish()
    server.begin_drain()
    await serve_task
    return {
        "results": results, "t_open": t_open, "t_close": t_close,
        "counters": {c: after[c] - before[c] for c in COUNTERS},
        "compiles_in_window": compiles_in_window, "trace_span": tracer.span if tracer else None,
    }


def tokens_in_window(times: list, t_open: float, t_close: float) -> float:
    """Output tokens of one request that fall in the window.  A first token
    counts where it arrives.  A later token was computed between its
    predecessor's arrival and its own, so it counts by the share of that
    interval that lies in the window: a whole token when the interval is
    inside, a part of one at either edge.  Tokens come a scheduler round at a
    time, and counting whole rounds at the edges would make the rate step by
    a round's worth from run to run."""
    n = 1.0 if times and t_open <= times[0] <= t_close else 0.0
    for a, b in zip(times, times[1:]):
        overlap = min(b, t_close) - max(a, t_open)
        if overlap > 0:
            n += overlap / (b - a) if b > a else 1.0
    return n


def end_to_end(results: list, t_open: float, t_close: float) -> dict:
    """The serving metrics over all requests sent in the window.  A failed
    request counts as the worst time to first token seen, or the time from its
    send to the close where that is longer."""
    window_s = t_close - t_open
    arrived = sum(tokens_in_window(r["times"], t_open, t_close) for r in results if r["ok"])
    sent = [r for r in results if t_open <= r["t_send"] <= t_close]
    ttft = [r["times"][0] - r["t_send"] for r in sent if r["ok"]]
    worst = max(ttft, default=window_s)
    ttft += [max(worst, t_close - r["t_send"]) for r in sent if not r["ok"]]
    tpot = [
        (r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1)
        for r in results
        if r["ok"] and len(r["times"]) > 1 and t_open <= r["times"][-1] <= t_close
    ]
    nan = float("nan")  # a window too short for a tail: run.py refuses to print it
    return {
        "serve_tokens_per_s": arrived / window_s,
        "ttft_p95_ms": 1e3 * harness.percentile(ttft, 95) if ttft else nan,
        "tpot_p95_ms": 1e3 * harness.percentile(tpot, 95) if tpot else nan,
    }


def required_flops(cfg: dict, results: list, t_open: float, t_close: float) -> float:
    """Forward FLOPs of the work the window saw: the prompt of every request
    whose first token arrived in it, and every output token that arrived in it."""
    total = 0.0
    for r in results:
        if not r["ok"]:
            continue
        n_prompt = len(r["prompt"])
        if t_open <= r["times"][0] <= t_close:
            total += flops.serve_flops_span(cfg, 0, n_prompt)
        # output token i (i >= 1) was computed by a decode step at position n_prompt + i - 1
        n_out = sum(1 for t in r["times"][1:] if t_open <= t <= t_close)
        total += flops.serve_flops_span(cfg, n_prompt, n_prompt + n_out)
    return total


def sample_for_check(results: list, seed: int, n: int) -> list:
    """A sample of the finished requests, drawn from the seed, with the
    longest in it."""
    done = [r for r in results if r["ok"]]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["prompt"]) + len(done[i]["tokens"]))
    rs = np.random.RandomState(seed % (2**32))
    others = [i for i in rs.permutation(len(done)) if i != longest][: max(0, n - 1)]
    return [done[i] for i in [longest, *others]]


def served_gap(cell: harness.Cell, seed: int, sample: list, cast=None) -> dict:
    """The widest gap by which a served token's reference logit lies below the
    reference's best, over the sample; with ``cast`` the control's: the token a
    forward pass in that precision puts first, at the same positions."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import neox

    params = weights.make_weights(cell.config, seed)
    t = cell.traffic
    pad_to = t["prompt_tokens"]["max"] + t["max_new_tokens"]["max"]
    gap_fn = neox.make_gap_fn(cell.config, neox.CASTS[cast or "f32"])
    worst, n_tokens = 0.0, 0
    for r in sample:
        seq = r["prompt"] + r["tokens"]
        n_prompt, n = len(r["prompt"]), len(seq)
        padded = jnp.asarray(seq + [0] * (pad_to - n), jnp.int32)
        gaps = np.asarray(gap_fn(params, padded))
        # position p's logits choose token p + 1: the served tokens sit at n_prompt .. n - 1
        served = gaps[n_prompt - 1 : n - 1]
        worst = max(worst, float(served.max()))
        n_tokens += len(served)
    jax.tree_util.tree_map(lambda x: x.delete(), params)
    return {"gap": worst, "tokens": n_tokens}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, compiles: harness.CompileCounter) -> dict:
    import jax

    w, t = cell.workload, cell.traffic
    trace_dir = os.path.join(harness.ROOT, ".bench_work", cell.name, "trace") if trace else None
    engine, scheduler, server = build(cell, seed)
    requests = make_requests(t, cell.config["vocab_size"], seed)
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
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
        check.add("served_logit_gap", served_gap(cell, seed, sample)["gap"], w["limits"]["served_logit_gap"])
    sent = [r for r in results if t_open <= r["t_send"] <= t_close]
    failed = sum(1 for r in results if not r["ok"])
    check.add("requests_failed", failed, 0)
    check.add("compiles_in_window", out["compiles_in_window"] + retraces, 0)
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
            "counters": dict(out["counters"], compiles_in_window=out["compiles_in_window"]),
            "host": {},
            "work": {"required_flops": required_flops(cell.config, results, t_open, t_close)},
        },
    }
