#!/usr/bin/env python3
"""Quickest proof that relora-tpu still starts on the chip.

Drives the system's main path once on one TPU v5e, through the entry points a
user calls, at the published width and depth of ``pythia_1b`` (16 layers,
hidden 2048, 8 heads of 256, FFN 8192, vocab 50304, sequence 2048; random
weights from ``--seed``):

1. device   — exactly one ``tpu`` device, versions, compile-cache directory;
2. kernels  — flash attention (forward + gradients) and the paged serving
   kernels, compiled (never interpreted), against f32 references on the device;
3. train    — ``python main.py``: ReLoRA r=128 updates across one
   merge-and-reinit, one optimizer reset and a checkpoint save;
4. serve    — ``python serve.py --paged`` on that checkpoint: a few HTTP
   requests, then SIGTERM.

One process holds the chip at a time, so this parent never imports JAX: each
phase is a child that is gone before the next starts.  Every phase prints one
JSON object; a phase that fails ends the script with a non-zero code.  The
last line of standard output is ``{"ok": true, "device": {...}}`` with the
device as JAX reported it to the first child.  Step and compile seconds
printed on the way are set-up evidence, not a benchmark.

``--multichip`` runs, instead of all of the above, the four-chip path and
what it is compared with: the same ``main.py`` job on an fsdp=4 mesh and on
one device (same seed, same global batch), per-update losses compared.

Run it on the chip machine (``chiprun -- python chip_smoke.py``).  With no
accelerator, or under ``JAX_PLATFORMS=cpu``, it exits non-zero and says why.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke_work")  # corpus + checkpoints (GBs), git-ignored
LOGS = os.path.join(REPO, "chiprun_out", "chip_smoke")  # small: child logs, report

MODEL = "pythia_1b"
SEQ = 2048
VOCAB = 50304
LORA_R = 128
# global batch of 4 x 2048 tokens: with the optimizer state the compiler's
# memory_analysis puts the one-chip step at 11.9 of 16 GB, 5.3 of them
# temporaries that a batch of 8 would double
GLOBAL_BATCH = 4
# 16 updates, cycle of 8: merge-and-reinit + optimizer reset after update 9,
# a checkpoint inside the loop at 12 and the final one at 16
STEPS, CYCLE, SAVE_EVERY = 16, 8, 12

# max |kernel - reference| allowed, relative to max(1, max |reference|):
# operands and outputs are bf16 (8 bits of mantissa, 2^-8 = 3.9e-3 per
# rounding), the references are f32 at Precision.HIGHEST
KERNEL_TOL = 2e-2
# fsdp=4 vs one device, |loss difference| per update, on losses that fall by
# about one unit an update: through the first update on the merged base, and
# after the magnitude-pruned optimizer reset (see multichip_phase)
MULTICHIP_LOSS_TOL = 0.1
MULTICHIP_LOSS_TOL_AFTER_RESET = 1.0


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fail(phase: str, why: str, log: str = "") -> "NoReturn":  # noqa: F821
    emit(phase, ok=False, error=why)
    if log and os.path.exists(log):
        with open(log, errors="replace") as f:
            tail = f.read()[-6000:]
        print(f"--- tail of {log} ---\n{tail}", file=sys.stderr, flush=True)
    sys.exit(1)


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def run_child(phase: str, cmd: list, timeout: float) -> str:
    """Run one child to its end, its stderr in LOGS/<phase>.log; returns its
    stdout.  A non-zero exit ends the script."""
    log = os.path.join(LOGS, f"{phase}.log")
    t0 = time.time()
    with open(log, "w") as err:
        proc = subprocess.run(
            cmd, cwd=REPO, env=child_env(), stdout=subprocess.PIPE, stderr=err,
            text=True, timeout=timeout,
        )
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    if proc.returncode != 0:
        fail(phase, f"{' '.join(cmd[:3])} ... exited {proc.returncode} after {time.time() - t0:.0f}s", log)
    return proc.stdout


# ---------------------------------------------------------------------------
# phases 1-2, in a child of their own: the only code here that imports JAX
# ---------------------------------------------------------------------------


def device_and_kernels(seed: int, want_devices: int, kernels: bool) -> None:
    from relora_tpu.utils.logging import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    devs = jax.devices()
    d0 = devs[0]
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    ok = d0.platform == "tpu" and len(devs) == want_devices
    emit(
        "device", ok=ok, platform=d0.platform, kind=d0.device_kind, count=len(devs),
        jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu_version,
        compile_cache_dir=cache_dir,
        compile_cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
    )
    if not ok:
        print(
            f"chip_smoke needs {want_devices} tpu device(s); JAX found "
            f"{len(devs)} x {d0.platform!r} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
            file=sys.stderr,
        )
        sys.exit(3)
    if kernels:
        kernel_checks(seed)


def kernel_checks(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from relora_tpu.ops.attention import (
        _naive_attention,
        _pallas_attention,
        packed_paged_attention,
        paged_cached_attention,
        paged_decode_attention,
    )
    from relora_tpu.ops.quant import quantize_kv_page

    # pythia_1b heads; 8 rows of 128 pages of 16 tokens (2048-token tables),
    # the pool the server's defaults give --max-batch 8, plus the null page
    N, H, PS, W, B = 8, 256, 16, 128, 8
    NP = B * W + 1
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 16)
    results = {}

    def check(name, fn, ref_fn, args):
        jitted = jax.jit(fn)
        lowered = jitted.lower(*args)
        if "tpu_custom_call" not in lowered.as_text():
            results[name] = {"ok": False, "error": "no tpu_custom_call in the lowered program"}
            return
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
        got = jax.tree_util.tree_leaves(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree_util.tree_leaves(jax.jit(ref_fn)(*args))
        err, scale = 0.0, 1.0
        finite = True
        for g, w in zip(got, want):
            g32, w32 = g.astype(jnp.float32), w.astype(jnp.float32)
            finite = finite and bool(jnp.isfinite(g32).all())
            s = max(1.0, float(jnp.abs(w32).max()))
            e = float(jnp.abs(g32 - w32).max()) / s
            if e > err:
                err, scale = e, s
        results[name] = {
            "ok": finite and err <= KERNEL_TOL, "max_err": err, "ref_scale": scale,
            "finite": finite, "compile_s": round(compile_s, 2),
        }

    # flash attention, forward and gradients, at the trainer's shape
    q, k, v, wgt = (
        jax.random.normal(ks[i], (2, SEQ, N, H), jnp.float32).astype(dt)
        for i, dt in ((0, jnp.bfloat16), (1, jnp.bfloat16), (2, jnp.bfloat16), (3, jnp.float32))
    )

    def attn_fwd_bwd(impl):
        def f(q, k, v, wgt):
            def loss(q, k, v):
                out = impl(q, k, v, causal=True, scale=H**-0.5)
                return (out.astype(jnp.float32) * wgt).sum(), out

            (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out, *grads)

        return f

    def naive_f32(q, k, v, **kw):
        return _naive_attention(*(x.astype(jnp.float32) for x in (q, k, v)), **kw)

    check("flash_attention_fwd_bwd", attn_fwd_bwd(_pallas_attention), attn_fwd_bwd(naive_f32), (q, k, v, wgt))

    # paged pool: B rows, each owning a random set of pages, at random lengths
    pool_k = jax.random.normal(ks[4], (NP, PS, N, H), jnp.float32).astype(jnp.bfloat16)
    pool_v = jax.random.normal(ks[5], (NP, PS, N, H), jnp.float32).astype(jnp.bfloat16)
    tables = (jax.random.permutation(ks[6], NP - 1) + 1).reshape(B, W).astype(jnp.int32)
    lengths = jax.random.randint(ks[7], (B,), 40, W * PS - 8, jnp.int32)

    def f32_ref(q, pk, pv, bt, pos, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return paged_cached_attention(q.astype(jnp.float32), pk, pv, bt, pos, **kw)

    for S in (1, 5):
        qd = jax.random.normal(ks[8 + S % 2], (B, S, N, H), jnp.float32).astype(jnp.bfloat16)
        pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        check(
            f"paged_decode_S{S}_bf16",
            lambda q, pk, pv, bt, pos: paged_decode_attention(q, pk, pv, bt, pos),
            f32_ref, (qd, pool_k, pool_v, tables, pos),
        )

    # int8 pool with per-(page, kv_head) scales
    qk, sk = jax.vmap(quantize_kv_page)(pool_k.astype(jnp.float32))
    qv, sv = jax.vmap(quantize_kv_page)(pool_v.astype(jnp.float32))
    qd = jax.random.normal(ks[10], (B, 1, N, H), jnp.float32).astype(jnp.bfloat16)
    check(
        "paged_decode_S1_int8",
        lambda q, pk, pv, bt, pos, s1, s2: paged_decode_attention(q, pk, pv, bt, pos, k_scale=s1, v_scale=s2),
        f32_ref, (qd, qk, qv, tables, lengths[:, None], sk, sv),
    )

    # packed mixed batch: 64 tokens spread over the B rows
    T = 64
    row_map = jax.random.randint(ks[11], (T,), 0, B, jnp.int32)
    tpos = jnp.take(lengths, row_map) - jax.random.randint(ks[12], (T,), 0, 32, jnp.int32)
    qp = jax.random.normal(ks[13], (1, T, N, H), jnp.float32).astype(jnp.bfloat16)

    def packed_ref(q, pk, pv, bt, rm, pos):
        tt = jnp.take(bt, rm, axis=0)
        out = paged_cached_attention(q.astype(jnp.float32).reshape(T, 1, N, H), pk, pv, tt, pos.reshape(T, 1))
        return out.reshape(1, T, N, H)

    check(
        "packed_paged_T64_bf16",
        lambda q, pk, pv, bt, rm, pos: packed_paged_attention(q, pk, pv, bt, rm, pos),
        packed_ref, (qp, pool_k, pool_v, tables, row_map, tpos),
    )

    ok = all(r["ok"] for r in results.values())
    emit("kernels", ok=ok, tolerance=KERNEL_TOL, interpret=False, kernels=results)
    if not ok:
        sys.exit(4)


# ---------------------------------------------------------------------------
# phase 3: the trainer, through its CLI
# ---------------------------------------------------------------------------


def write_corpus(seed: int, run_dir: str) -> str:
    """A Megatron mmap corpus for the model's vocabulary, from the seed:
    arithmetic progressions inside one band of 512 token ids, so that a few
    updates can lower the loss from ln(vocab)."""
    import numpy as np

    from relora_tpu.data.memmap import MemmapTokenWriter, best_dtype

    rs = np.random.RandomState(seed)
    band = int(rs.randint(VOCAB - 512))
    prefix = os.path.join(run_dir, "corpus")
    n_tokens = 0
    with MemmapTokenWriter(prefix, dtype=best_dtype(VOCAB)) as w:
        while n_tokens < 1_200_000:
            n = int(rs.randint(256, 3000))
            start, stride = int(rs.randint(512)), int(rs.choice([1, 3, 7]))
            w.add_document(((start + stride * np.arange(n)) % 512 + band).tolist())
            n_tokens += n
    cfg = os.path.join(run_dir, "mega.yaml")
    with open(cfg, "w") as f:
        f.write(f'data_path: {prefix}\nsplit: "8,1,1"\nseq_length: {SEQ}\nseed: {seed}\ndata_impl: mmap\n')
    return cfg


def train_cmd(mega: str, save_dir: str, seed: int, micro_batch: int, fsdp: int) -> list:
    """The reference's 1B recipe (training_configs/1B_v1.0.yaml) with the
    cycle, warm-ups and step budget cut to a smoke's length."""
    return [
        sys.executable, "main.py",
        "--megatron_dataset_config", mega, "--model_config", MODEL,
        "--max_length", str(SEQ), "--dtype", "bfloat16", "--remat", "true",
        "--use_peft", "true", "--force_keep_original", "true", "--lora_r", str(LORA_R),
        "--relora", str(CYCLE), "--cycle_length", str(CYCLE),
        "--restart_warmup_steps", "2", "--warmup_steps", "4",
        "--reset_optimizer_on_relora", "false", "--optimizer_magnitude_pruning", "0.8",
        "--optimizer", "adam", "--lr", "4e-4", "--adam_beta1", "0.9", "--adam_beta2", "0.95",
        "--weight_decay", "0.01", "--scheduler", "cosine_restarts",
        "--batch_size", str(micro_batch), "--total_batch_size", str(GLOBAL_BATCH),
        # say what is meant: MeshSpec grows `data` to fill the pool otherwise
        "--dp_size", "1", "--fsdp_size", str(fsdp),
        "--num_training_steps", str(STEPS), "--save_every", str(SAVE_EVERY),
        "--eval_every", "1000", "--final_eval_tokens", str(2 * GLOBAL_BATCH * SEQ),
        "--seed", str(seed), "--save_dir", save_dir,
    ]


def read_train_run(save_dir: str, log: str) -> dict:
    """What the run recorded about itself (metrics.jsonl + its log)."""
    records = []
    with open(os.path.join(save_dir, "metrics.jsonl")) as f:
        for line in f:
            records.append(json.loads(line))
    steps = sorted((r for r in records if "loss" in r), key=lambda r: r["update_step"])
    losses = [r["loss"] for r in steps]
    compiles = [r for r in records if r.get("_event") == "compile"]
    retraces = max((r.get("compile/steady_state_retraces", 0) for r in records), default=0)
    peaks = [r["hbm/peak_bytes_in_use"] for r in records if "hbm/peak_bytes_in_use" in r]
    step_s = [
        GLOBAL_BATCH * (SEQ + 1) / r["throughput_tokens"] for r in steps if r.get("throughput_tokens")
    ]
    with open(log, errors="replace") as f:
        text = f.read()
    params_line = next((l for l in text.splitlines() if "params: total=" in l), "")
    builder = next((l.split("index builder: ")[1] for l in text.splitlines() if "index builder: " in l), None)
    attention = sorted({l.split("traced: ")[1] for l in text.splitlines() if "dot_product_attention traced: " in l})
    return {
        "losses": losses, "compiles": compiles, "retraces": retraces,
        "peak_bytes_in_use": max(peaks) if peaks else None, "step_s": step_s,
        "params_line": params_line.split("| ")[-1], "index_builder": builder, "attention": attention,
        "merges": max((r.get("n_lora_restarts", 0) for r in steps), default=0),
        "resets": max((r.get("n_optimizer_resets", 0) for r in steps), default=0),
        "eval_loss": next((r["final_eval_loss"] for r in records if "final_eval_loss" in r), None),
        "placement": next((r for r in records if r.get("_event") == "placement"), None),
        "xla_plan": next(
            (r for r in records if r.get("_event") == "memory_plan" and r.get("source") == "xla_train_step"), None
        ),
    }


def check_train_run(phase: str, run: dict, save_dir: str, log: str) -> dict:
    losses = run["losses"]
    problems = []
    if len(losses) != STEPS:
        problems.append(f"{len(losses)} updates logged, expected {STEPS}")
    if not all(math.isfinite(x) for x in losses):
        problems.append("non-finite loss")
    ln_v = math.log(VOCAB)
    if losses and not (ln_v - 0.5 <= losses[0] <= ln_v + 1.0):
        problems.append(f"first loss {losses[0]:.3f} is not near ln({VOCAB}) = {ln_v:.3f}")
    if len(losses) >= 4 and not (sum(losses[-2:]) / 2 < losses[0] - 0.05):
        problems.append(f"loss did not fall: first {losses[0]:.3f}, last two {losses[-2:]}")
    if run["merges"] != 1 or run["resets"] != 1:
        problems.append(f"expected one merge and one reset, saw {run['merges']} and {run['resets']}")
    n_step_compiles = sum(1 for c in run["compiles"] if c["fn"] == "train_step")
    if run["retraces"] != 0 or n_step_compiles != 1:
        problems.append(
            f"train_step compiled {n_step_compiles} times, {run['retraces']} steady-state retraces"
        )
    if not any(f", {SEQ}, " in a for a in run["attention"]):
        problems.append(f"no attention traced at sequence length {SEQ}: {run['attention']}")
    for step in (SAVE_EVERY, STEPS):
        if not os.path.exists(os.path.join(save_dir, f"model_{step}", "manifest.json")):
            problems.append(f"no committed checkpoint model_{step}")
    # the recipe's split: LoRA factors on every attention/MLP linear of every
    # layer — qkv, dense, h_to_4h, 4h_to_h (67.11M at pythia_1b, r=128)
    from relora_tpu.config.model import load_model_config

    mc = load_model_config(MODEL)
    h, f = mc.hidden_size, mc.intermediate_size
    want_lora = mc.num_hidden_layers * LORA_R * ((h + 3 * h) + (h + h) + 2 * (h + f)) / 1e6
    if f"lora={want_lora:.2f}M" not in run["params_line"]:
        problems.append(f"LoRA parameter count is not {want_lora:.2f}M: {run['params_line']}")
    summary = {
        "losses": [round(x, 4) for x in losses], "final_eval_loss": run["eval_loss"],
        "params": run["params_line"], "merges": run["merges"], "optimizer_resets": run["resets"],
        # with the persistent cache on, the memory plan's AOT compile is the
        # real one and the first call loads it
        "train_step_compile_s": {
            "memory_plan_aot": (run["xla_plan"] or {}).get("compile_s"),
            "first_call": [c["duration_s"] for c in run["compiles"] if c["fn"] == "train_step"],
        },
        "steady_state_retraces": run["retraces"],
        "step_s_setup_evidence": [round(s, 3) for s in run["step_s"]],
        "peak_bytes_in_use": run["peak_bytes_in_use"],
        "index_builder": run["index_builder"], "attention_traced": run["attention"],
        "checkpoints": [f"model_{SAVE_EVERY}", f"model_{STEPS}"],
    }
    if problems:
        emit(phase, ok=False, **summary)
        fail(phase, "; ".join(problems), log)
    return summary


def train_phase(seed: int) -> str:
    run_dir = os.path.join(WORK, "train")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    free_gb = shutil.disk_usage(WORK).free / 1e9
    if free_gb < 14:
        fail("train", f"{free_gb:.1f} GB free under {WORK}; two 1B checkpoints need about 11")
    mega = write_corpus(seed, run_dir)
    save_dir = os.path.join(run_dir, "run")
    t0 = time.time()
    run_child("train", train_cmd(mega, save_dir, seed, GLOBAL_BATCH, 1), timeout=900)
    log = os.path.join(LOGS, "train.log")
    summary = check_train_run("train", read_train_run(save_dir, log), save_dir, log)
    emit("train", ok=True, wall_s=round(time.time() - t0, 1), **summary)
    return os.path.join(save_dir, f"model_{STEPS}")


# ---------------------------------------------------------------------------
# phase 4: the server, through its CLI and over HTTP
# ---------------------------------------------------------------------------


def http_json(url: str, body: dict | None = None, timeout: float = 300):
    data = json.dumps(body).encode() if body is not None else None
    try:
        with urllib.request.urlopen(urllib.request.Request(url, data=data), timeout=timeout) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def generate_streamed(base: str, prompt: list, n: int) -> list:
    req = urllib.request.Request(
        f"{base}/v1/generate", data=json.dumps({"prompt": prompt, "max_new_tokens": n}).encode()
    )
    with urllib.request.urlopen(req, timeout=300) as resp:
        events = [line[len(b"data: "):].strip() for line in resp if line.startswith(b"data: ")]
    assert events[-1] == b"[DONE]", events[-3:]
    streamed = [json.loads(e)["token"] for e in events[:-2]]
    final = json.loads(events[-2])
    assert streamed == final["tokens"], (streamed, final["tokens"])
    return final["tokens"]


def serve_phase(checkpoint: str, seed: int) -> None:
    log = os.path.join(LOGS, "serve.log")
    port_file = os.path.join(WORK, "serve.port")
    run_dir = os.path.join(WORK, "serve_run")
    shutil.rmtree(run_dir, ignore_errors=True)
    if os.path.exists(port_file):
        os.remove(port_file)
    cmd = [
        sys.executable, "serve.py", "--checkpoint", checkpoint, "--model_config", MODEL,
        "--paged", "--dtype", "bf16", "--port", "0", "--port-file", port_file,
        "--max-batch", "8", "--max-new-tokens", "16", "--eos-id", "-1",
        "--seed", str(seed), "--run-dir", run_dir,
    ]
    t0 = time.time()
    with open(log, "w") as err:
        server = subprocess.Popen(cmd, cwd=REPO, env=child_env(), stderr=err)
    try:
        while not (os.path.exists(port_file) and os.path.getsize(port_file)):
            if server.poll() is not None:
                fail("serve", f"serve.py exited {server.returncode} before listening", log)
            if time.time() - t0 > 600:
                fail("serve", "serve.py did not listen within 600 s", log)
            time.sleep(0.5)
        with open(port_file) as f:
            base = f"http://127.0.0.1:{f.read().strip()}"
        while True:  # 503 "warming" until the compile warm-up is paid
            code, health = http_json(f"{base}/healthz", timeout=30)
            if health.get("status") == "ok":
                break
            if health.get("status") != "warming" or server.poll() is not None or time.time() - t0 > 900:
                fail("serve", f"/healthz said {code} {health} after {time.time() - t0:.0f}s", log)
            time.sleep(1.0)
        ready_s = time.time() - t0

        prompt = [(7 * i) % 1000 + 1 for i in range(40)]
        streamed = generate_streamed(base, prompt, 16)
        code, plain = http_json(f"{base}/v1/generate", {"prompt": prompt, "max_new_tokens": 16, "stream": False})
        if code != 200 or plain["tokens"] != streamed or len(streamed) != 16:
            fail("serve", f"streamed {streamed} vs non-streamed {code} {plain}", log)
        # two prompts sharing three pages of prefix, the second after the first
        shared = [(11 * i) % 1000 + 1 for i in range(48)]
        a = generate_streamed(base, shared + [5, 6, 7], 8)
        b = generate_streamed(base, shared + [9, 10], 8)
        _, health = http_json(f"{base}/healthz", timeout=30)
        metrics = urllib.request.urlopen(f"{base}/metrics", timeout=30).read().decode()
        generated = next(
            (float(l.split()[-1]) for l in metrics.splitlines() if l.startswith("relora_serve_tokens_generated_total")),
            None,
        )
        server.send_signal(signal.SIGTERM)
        rc = server.wait(timeout=120)
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()

    with open(log, errors="replace") as f:
        text = f.read()
    arms = sorted({l.split("| ")[-1] for l in text.splitlines() if "_attention traced: " in l})
    decode_arm = [a for a in arms if "q_shape=(8, 1, " in a]
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    retraces = max((r.get("compile/steady_state_retraces", 0) for r in records), default=0)
    warm = next((l.split("| ")[-1] for l in text.splitlines() if "warmup compiled" in l), None)
    paging = health.get("paging", {})
    problems = []
    if rc != 0:
        problems.append(f"serve.py exited {rc} on SIGTERM")
    if not decode_arm or not all("arm=paged_decode" in a and "interpret=False" in a for a in decode_arm):
        problems.append(f"decode did not trace the compiled Pallas arm: {arms}")
    if generated != 16 + 16 + 8 + 8:
        problems.append(f"/metrics counts {generated} generated tokens, expected 48")
    if retraces != 0:
        problems.append(f"{retraces} steady-state retraces after warm-up")
    if not paging.get("prefix_cache", {}).get("hits"):
        problems.append(f"the shared prefix was not reused: {paging.get('prefix_cache')}")
    summary = dict(
        ready_s=round(ready_s, 1), tokens=streamed, stream_equals_plain=True,
        shared_prefix_tokens=[a, b], prefix_cache=paging.get("prefix_cache"),
        attention_arms_traced=arms, warmup=warm, steady_state_retraces=retraces,
        tokens_generated_total=generated, exit_code_on_sigterm=rc,
    )
    if problems:
        emit("serve", ok=False, **summary)
        fail("serve", "; ".join(problems), log)
    emit("serve", ok=True, **summary)


# ---------------------------------------------------------------------------
# --multichip: fsdp=4 against one device
# ---------------------------------------------------------------------------


def multichip_phase(seed: int) -> None:
    run_dir = os.path.join(WORK, "multichip")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    mega = write_corpus(seed, run_dir)
    runs = {}
    for name, fsdp in (("fsdp4", 4), ("one_device", 1)):
        save_dir = os.path.join(run_dir, name)
        phase = f"multichip_{name}"
        run_child(phase, train_cmd(mega, save_dir, seed, GLOBAL_BATCH // fsdp, fsdp), timeout=1200)
        log = os.path.join(LOGS, f"{phase}.log")
        run = read_train_run(save_dir, log)
        emit(phase, ok=True, **check_train_run(phase, run, save_dir, log), placement=run["placement"],
             collectives=(run["xla_plan"] or {}).get("collectives"))
        runs[name] = run
        shutil.rmtree(save_dir, ignore_errors=True)  # 11 GB of checkpoints each
    diffs = [abs(x - y) for x, y in zip(runs["fsdp4"]["losses"], runs["one_device"]["losses"])]
    # updates 1..CYCLE+2 run the same program up to the order of bf16
    # reductions across shards; the last of them is the first on the merged
    # base (B = 0 again), so it checks the sharded merge.  After the
    # magnitude-pruned reset Adam divides kept first moments by pruned (zero)
    # second moments, and rounding-level differences pick different
    # survivors: only the loose bound holds from there
    split = CYCLE + 2
    place = runs["fsdp4"]["placement"] or {}
    param_bytes = [b for b in place.get("param_bytes") or [] if b]
    total_bytes = sum((runs["one_device"]["placement"] or {}).get("param_bytes") or [])
    collectives = (runs["fsdp4"]["xla_plan"] or {}).get("collectives")
    problems = []
    if max(diffs[:split]) > MULTICHIP_LOSS_TOL:
        problems.append(f"losses of updates 1..{split} differ by up to {max(diffs[:split]):.4f}")
    if max(diffs[split:]) > MULTICHIP_LOSS_TOL_AFTER_RESET:
        problems.append(f"losses after the reset differ by up to {max(diffs[split:]):.4f}")
    if len(param_bytes) != 4 or len(place.get("batch_devices") or []) != 4:
        problems.append(f"shardings do not name four devices: {place}")
    elif max(param_bytes) > 0.4 * total_bytes:
        problems.append(f"a device holds more than 40% of the parameters: {param_bytes} of {total_bytes}")
    if not collectives:
        problems.append("no collective in the compiled fsdp=4 step")
    summary = dict(
        max_loss_diff_through_merge=max(diffs[:split]), tolerance=MULTICHIP_LOSS_TOL,
        max_loss_diff_after_reset=max(diffs[split:]), tolerance_after_reset=MULTICHIP_LOSS_TOL_AFTER_RESET,
        loss_diffs=[round(d, 5) for d in diffs],
        device_ids=place.get("device_ids"), param_bytes_by_device=place.get("param_bytes"),
        one_device_param_bytes=total_bytes, batch_devices=place.get("batch_devices"),
        bytes_in_use_by_device=place.get("bytes_in_use"), collectives=collectives,
    )
    if problems:
        emit("multichip", ok=False, **summary)
        fail("multichip", "; ".join(problems))
    emit("multichip", ok=True, **summary)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="weights, corpus and sampling all derive from it")
    ap.add_argument("--multichip", action="store_true", help="only the four-chip path and its one-device twin")
    ap.add_argument("--phase", choices=["device", "device+kernels"], help=argparse.SUPPRESS)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(REPO, "relora_tpu")) or not os.path.exists(os.path.join(REPO, "main.py")):
        print(f"chip_smoke.py drives the repo it sits in; {REPO} holds no relora_tpu/ and main.py", file=sys.stderr)
        return 2
    if args.phase:  # a child of the run below: the only place JAX is imported
        device_and_kernels(args.seed, 4 if args.multichip else 1, kernels=args.phase == "device+kernels")
        return 0

    os.makedirs(WORK, exist_ok=True)
    os.makedirs(LOGS, exist_ok=True)
    me = [sys.executable, os.path.abspath(__file__), "--seed", str(args.seed)]
    if args.multichip:
        out = run_child("device", me + ["--multichip", "--phase", "device"], timeout=300)
        multichip_phase(args.seed)
    else:
        out = run_child("device", me + ["--phase", "device+kernels"], timeout=600)
        checkpoint = train_phase(args.seed)
        serve_phase(checkpoint, args.seed)
        shutil.rmtree(os.path.join(WORK, "train"), ignore_errors=True)
    dev = next(r for r in map(json.loads, out.splitlines()) if r.get("phase") == "device")
    print(json.dumps({"ok": True, "device": {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
