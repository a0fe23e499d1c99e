"""Real multi-process distributed training test.

Launches two separate Python processes that form one JAX distributed system
(jax.distributed.initialize over a local coordinator, CPU devices), build the
same Trainer on a 2-way data-parallel mesh, read disjoint per-host batch
slices, and train — exercising the actual multi-host code paths
(process_count > 1 branch of device_batch via
make_array_from_process_local_data, per-host TokenBatchIterator slicing,
process-0-only checkpoint JSON) that single-process tests cannot reach.

The reference has no equivalent test (single-node only, SURVEY.md §4.4).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
coordinator, pid, out_path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(coordinator_address=coordinator, num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 2

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(out_path))))
sys.path.insert(0, "/root/repo")
from tests.test_end_to_end import TINY, FakeTokens, make_cfg
from relora_tpu.data.hf_pipeline import TokenBatchIterator
from relora_tpu.train.trainer import Trainer

cfg = make_cfg(
    __import__("pathlib").Path(os.path.dirname(out_path)),
    num_training_steps=6, relora=None, use_peft=False, scheduler="cosine",
    cycle_length=6, save_every=6, dp_size=2, batch_size=4, total_batch_size=8,
)
trainer = Trainer(cfg, model_cfg=TINY)
data = FakeTokens(n=256)
it = TokenBatchIterator(
    data,
    microbatch=cfg.batch_size * trainer.n_batch_shards // jax.process_count(),
    grad_accum=trainer.grad_accum,
    process_index=jax.process_index(),
    process_count=jax.process_count(),
)
result = trainer.fit(iter(it), None)
import numpy as np
probe = float(np.asarray(trainer.state.params["lm_head"]["kernel"]).sum())
with open(out_path, "w") as f:
    json.dump({"process": pid, "result": result, "probe": probe}, f)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_data_parallel_training(tmp_path):
    coordinator = f"127.0.0.1:{_free_port()}"
    worker_file = tmp_path / "worker.py"
    worker_file.write_text(WORKER)
    procs = []
    outs = []
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    for pid in range(2):
        out = tmp_path / f"out_{pid}.json"
        outs.append(out)
        procs.append(
            subprocess.Popen(
                [sys.executable, str(worker_file), coordinator, str(pid), str(out)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    for p in procs:
        try:
            stdout, stderr = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process run timed out")
        assert p.returncode == 0, f"worker failed:\n{stderr[-3000:]}"

    results = [json.load(open(o)) for o in outs]
    # both processes completed the same run and hold identical replicated-state
    assert all(r["result"]["update_step"] == 6 for r in results)
    assert results[0]["probe"] == pytest.approx(results[1]["probe"], rel=1e-6)
    assert np.isfinite(results[0]["probe"])


# ---------------------------------------------------------------------------
# 2-process x 2-local-device (fsdp=2 x data=2 mesh) ReLoRA over the megatron
# per-host data path, killed mid-run and autoresumed — the places multi-host
# bugs actually live: sharded params + merge under fsdp, coordinator-built
# index mappings with a cross-process barrier, per-host batch slicing,
# deterministic data rewind, and the commit-aware autoresume probe after a
# SIGKILL that may interrupt an async checkpoint write.  Multiple local
# devices per process mirrors real TPU-pod topology (4 chips/host); it also
# keeps cross-process compile skew inside gloo's 30s context-init deadline,
# which a 4-singleton-process layout exceeds on a contended CPU host.
# The continuity oracle: the resumed run's per-step losses must reproduce
# the killed run's exactly (same data order, restored optimizer/schedule
# state, same compiled program).
# ---------------------------------------------------------------------------

WORKER_2X2 = r"""
import faulthandler, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
# Fail fast WITH diagnostics: a wedged worker (e.g. a cross-process
# collective deadlock — see the jitted zeroed_fraction note in
# core/optim.py, found by exactly this dump) prints all thread stacks to
# stderr and exits instead of hanging the suite to the phase deadline.
faulthandler.dump_traceback_later(360, exit=True)
import jax
coordinator, pid, workdir, steps = sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
jax.distributed.initialize(coordinator_address=coordinator, num_processes=2, process_id=pid)
assert jax.process_count() == 2 and len(jax.devices()) == 4

sys.path.insert(0, "/root/repo")
import main as cli

cli.main([
    "--megatron_dataset_config", f"{workdir}/mega.yaml",
    "--model_config", f"{workdir}/model.json",
    "--batch_size", "2", "--total_batch_size", "8", "--max_length", "16",
    "--dp_size", "2", "--fsdp_size", "2",
    "--lr", "5e-3", "--use_peft", "true", "--lora_r", "4",
    "--relora", "5", "--cycle_length", "5",
    "--scheduler", "cosine_restarts", "--warmup_steps", "2",
    "--restart_warmup_steps", "2",
    "--num_training_steps", steps, "--save_every", "5",
    "--eval_every", "1000", "--seed", "0",
    "--save_dir", f"{workdir}/run", "--autoresume", "true",
])
"""


def _read_losses(metrics_path):
    losses = {}
    if not os.path.exists(metrics_path):
        return losses
    with open(metrics_path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # half-written line at kill time
            if "loss" in rec and "update_step" in rec:
                losses[rec["update_step"]] = rec["loss"]
    return losses


def _drain(p):
    """communicate() that tolerates an already-drained process (a second
    call on a text=True piped Popen raises ValueError)."""
    try:
        return p.communicate()
    except ValueError:
        return ("", "")


def _spawn_2x2(tmp_path, worker_file, coordinator, steps):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    return [
        subprocess.Popen(
            [sys.executable, str(worker_file), coordinator, str(pid), str(tmp_path), steps],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for pid in range(2)
    ]


@pytest.mark.slow
def test_two_by_two_fsdp_megatron_kill_autoresume(tmp_path):
    import time

    from relora_tpu.data.memmap import MemmapTokenWriter, best_dtype

    # shared mmap corpus (structured so loss is comparable across runs)
    rs = np.random.RandomState(0)
    with MemmapTokenWriter(str(tmp_path / "corpus"), dtype=best_dtype(128)) as w:
        for _ in range(2000):
            start = rs.randint(128)
            w.add_document([(start + j) % 128 for j in range(rs.randint(10, 60))])
    (tmp_path / "mega.yaml").write_text(
        f"data_path: {tmp_path}/corpus\nsplit: '10,0,0'\nseq_length: 16\nseed: 0\ndata_impl: mmap\n"
    )
    from tests.test_end_to_end import TINY

    (tmp_path / "model.json").write_text(json.dumps(TINY.to_dict()))
    worker_file = tmp_path / "worker_2x2.py"
    worker_file.write_text(WORKER_2X2)
    metrics = tmp_path / "run" / "metrics.jsonl"

    # phase A: long run; kill both processes once a checkpoint committed and
    # step >= 7.  gloo's context init has a hard 30s deadline with no config
    # knob (make_gloo_tcp_collectives exposes none); on a contended host,
    # compile skew between the two processes can blow it on the cold first
    # attempt, so a load-induced transient gets ONE retry (the persistent
    # compile cache makes the second attempt skew-free) and anything else
    # fails immediately with the workers' stderr.  The budget is bounded:
    # workers self-kill with stack dumps at 360s (see WORKER_2X2), so a hang
    # surfaces as a fast failure with diagnostics, never a suite stall.
    for attempt in (1, 2):
        procs = _spawn_2x2(tmp_path, worker_file, f"127.0.0.1:{_free_port()}", "20")
        deadline = time.time() + 480
        gloo_skew = False
        try:
            while time.time() < deadline:
                committed = os.path.isdir(tmp_path / "run" / "model_5" / "state")
                if committed and max(_read_losses(metrics), default=0) >= 7:
                    break
                if any(p.poll() is not None for p in procs):
                    errs = "\n".join(
                        (_drain(p)[1] or "")[-3000:] for p in procs if p.poll() is not None
                    )
                    gloo_skew = (
                        "Gloo context initialization failed" in errs
                        # XLA:CPU's 40s cross-device rendezvous abort is the
                        # same class of load-induced transient as gloo skew
                        or "Termination timeout for" in errs
                    )
                    if gloo_skew and attempt < 2:
                        break
                    pytest.fail(f"phase A worker exited early:\n{errs}")
                time.sleep(1.0)
            else:
                pytest.fail("phase A never reached step 7 with a committed checkpoint")
        finally:
            for p in procs:
                p.kill()
        for p in procs:
            _drain(p)
        if not gloo_skew:
            break

    losses_a = _read_losses(metrics)
    assert losses_a and max(losses_a) >= 7

    # phase B: autoresume with the SAME step budget (the schedule envelope is
    # a function of num_training_steps; changing it would change lr and break
    # the continuity oracle) — must pick up model_5 and rewind data
    for attempt in (1, 2):
        procs = _spawn_2x2(tmp_path, worker_file, f"127.0.0.1:{_free_port()}", "20")
        stderrs = []
        for p in procs:
            try:
                # workers self-kill with stack dumps at 360s, so this outer
                # bound only fires if even that failed
                _, stderr = p.communicate(timeout=480)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail("phase B timed out (and the worker self-kill did not fire)")
            stderrs.append(stderr or "")
        if all(p.returncode == 0 for p in procs):
            break
        if attempt < 2 and any(
            "Gloo context initialization failed" in s
            or "Termination timeout for" in s
            for s in stderrs
        ):
            continue  # same skew retry as phase A; autoresume makes it safe
        bad = next(i for i, p in enumerate(procs) if p.returncode != 0)
        pytest.fail(f"phase B worker failed:\n{stderrs[bad][-3000:]}")

    losses_b = _read_losses(metrics)
    # resumed losses reproduce the killed run bit-for-bit on overlapping steps
    overlap = [s for s in range(6, 21) if s in losses_a and s in losses_b and losses_b[s] is not None]
    assert overlap, f"no overlapping steps: A={sorted(losses_a)}, B={sorted(losses_b)}"
    for s in overlap:
        assert losses_b[s] == pytest.approx(losses_a[s], rel=1e-6), (
            f"loss diverged at resumed step {s}: {losses_a[s]} vs {losses_b[s]}"
        )
    # the run completed and a final checkpoint exists
    assert os.path.isdir(tmp_path / "run" / "model_20" / "state")
