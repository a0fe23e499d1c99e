#!/usr/bin/env bash
# Smoke-test battery (systematizes the reference's README.dev.md command
# list): tiny configs covering the common training regimes, runnable on CPU
# in a few minutes.  Exercises the real CLIs end-to-end.
#
#   JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
#       bash scripts/smoke_test.sh [workdir]
set -euo pipefail
cd "$(dirname "$0")/.."
WORK="${1:-$(mktemp -d /tmp/relora_smoke.XXXX)}"
echo "workdir: $WORK"

echo "=== 0. static analysis (relora-lint) ==="
# cheapest gate first: stdlib-only AST lint, fails on new RTL findings
bash scripts/lint.sh

echo "=== 0b. fused LoRA kernel parity (interpret mode) ==="
# the fused pallas composite vs the unfused reference, forward and grads,
# on the CPU interpreter — catches kernel regressions before any training
python - <<'EOF'
import jax, jax.numpy as jnp
from relora_tpu.ops.lora_dispatch import lora_matmul
from relora_tpu.ops.quant import quantize_int8

k = jax.random.PRNGKey(0)
M, K, N, r = 32, 256, 128, 8
x = jax.random.normal(jax.random.fold_in(k, 1), (M, K), jnp.float32)
w = jax.random.normal(jax.random.fold_in(k, 2), (K, N), jnp.float32)
a = jax.random.normal(jax.random.fold_in(k, 3), (K, r), jnp.float32) * 0.1
b = jax.random.normal(jax.random.fold_in(k, 4), (r, N), jnp.float32) * 0.1
ref = lambda x, a, b: x @ w + (x @ a) @ b * 0.25
for base, tag in ((w, "dense"), (quantize_int8(w), "int8")):
    wd = base if tag == "dense" else base[0].astype(jnp.float32) * base[1]
    refd = lambda x, a, b, wd=wd: x @ wd + (x @ a) @ b * 0.25
    y = lora_matmul(x, base, a, b, 0.25, arm="fused")
    assert float(jnp.abs(y - refd(x, a, b)).max()) < 1e-4, f"{tag} fwd parity"
    gf = jax.grad(lambda *o: jnp.sum(jnp.sin(lora_matmul(*o[:1], base, *o[1:], 0.25, arm="fused"))), argnums=(0, 1, 2))(x, a, b)
    gr = jax.grad(lambda *o: jnp.sum(jnp.sin(refd(*o))), argnums=(0, 1, 2))(x, a, b)
    for f_, r_ in zip(gf, gr):
        assert float(jnp.abs(f_ - r_).max()) < 1e-4, f"{tag} grad parity"
    print(f"fused kernel parity OK ({tag} base)")
EOF

python - "$WORK" <<'EOF'
import sys, numpy as np
from relora_tpu.data.memmap import MemmapTokenWriter, best_dtype
rs = np.random.RandomState(0)
with MemmapTokenWriter(f"{sys.argv[1]}/corpus", dtype=best_dtype(128)) as w:
    for _ in range(3000):
        start = rs.randint(128); n = rs.randint(10, 80)
        w.add_document([(start + j) % 128 for j in range(n)])
print("corpus written")
EOF

cat > "$WORK/mega.yaml" <<EOF
data_path: $WORK/corpus
split: "8,1,1"
seq_length: 32
seed: 0
data_impl: mmap
EOF

common=(--megatron_dataset_config "$WORK/mega.yaml" --model_config llama_9m
        --batch_size 4 --total_batch_size 8 --max_length 32 --dp_size 2
        --warmup_steps 2 --eval_every 1000 --seed 0)

echo "=== 1. full-rank ==="
python main.py "${common[@]}" --lr 3e-3 --scheduler cosine --cycle_length 8 \
    --num_training_steps 8 --save_every 8 --save_dir "$WORK/full"

echo "=== 2. ReLoRA from warm start ==="
python main.py "${common[@]}" --lr 5e-3 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --warmed_up_model "$WORK/full/model_8" \
    --num_training_steps 32 --save_every 8 --save_dir "$WORK/relora"

echo "=== 3. ReLoRA + magnitude pruning + int8 base ==="
python main.py "${common[@]}" --lr 5e-3 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --reset_optimizer_on_relora false --optimizer_magnitude_pruning 0.8 \
    --quantize int8 --warmed_up_model "$WORK/full/model_8" \
    --num_training_steps 24 --save_every 100 --save_dir "$WORK/relora_q"

echo "=== 3b. ReLoRA + nf4 double-quant base ==="
python main.py "${common[@]}" --lr 5e-3 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --quantize nf4 --use_double_quant true --warmed_up_model "$WORK/full/model_8" \
    --num_training_steps 24 --save_every 100 --save_dir "$WORK/relora_nf4"

echo "=== 4. autoresume continues run 2 ==="
python main.py "${common[@]}" --lr 5e-3 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --num_training_steps 40 --save_every 8 --save_dir "$WORK/relora" \
    --autoresume true

echo "=== 5. pythia + ReLoRA under fsdp (reference README.dev.md:4-34 regime) ==="
python main.py --megatron_dataset_config "$WORK/mega.yaml" --model_config pythia_14m \
    --batch_size 1 --total_batch_size 8 --max_length 32 --fsdp_size 2 \
    --warmup_steps 2 --eval_every 1000 --seed 0 \
    --lr 5e-3 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --num_training_steps 16 --save_every 100 --save_dir "$WORK/pythia_relora"

echo "=== 6. fp32 full-rank (reference README.dev.md:65-77 regime) ==="
python main.py "${common[@]}" --lr 3e-3 --scheduler cosine --cycle_length 8 \
    --dtype float32 --num_training_steps 8 --save_every 100 \
    --save_dir "$WORK/full_fp32"

echo "=== 6b. tp x fsdp composition parity (8 virtual devices, pytest -m parallel) ==="
# the tentpole oracle: a tp=2 x fsdp=4 train step (and merge-and-reinit)
# must match the single-device loss trace, and the kv-head-sharded page
# pool must stay token-identical to the meshless paged engine
python -m pytest tests/test_parallel_composition.py -q -m parallel -p no:cacheprovider

echo "=== 7. analysis tools ==="
python tools/analyze_rank.py --before "$WORK/relora/model_16" --after "$WORK/relora/model_40" | head -4
python tools/inspect_optimizer.py "$WORK/relora/model_40" | head -3

echo "=== 8. generate from the ReLoRA checkpoint (serve path) ==="
# one-shot greedy over token-id prompts: loads model_40, merges the LoRA
# factors, and decodes with the KV-cache engine
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --prompt "1 2 3 4" --prompt "5 6 7" --max-new-tokens 8 --cache-size 64 \
    --eos-id -1
# request-loop mode through the continuous-batching scheduler
printf '1 2 3\n4 5 6 7\n8 9\n' > "$WORK/serve_requests.txt"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --input-file "$WORK/serve_requests.txt" --max-new-tokens 6 --cache-size 64 \
    --max-batch 2 --eos-id -1 --run-dir "$WORK/serve_run"
grep -q serve_request "$WORK/serve_run/metrics.jsonl"

echo "=== 9. HTTP serving front-end (boot, healthz, stream, SIGTERM drain) ==="
rm -f "$WORK/serve_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/serve_port" --max-batch 2 --max-queue 4 \
    --cache-size 64 --max-new-tokens 6 --eos-id -1 &
SERVER_PID=$!
for _ in $(seq 300); do [ -s "$WORK/serve_port" ] && break; sleep 0.2; done
[ -s "$WORK/serve_port" ] || { echo "server never wrote its port"; kill "$SERVER_PID"; exit 1; }
python - "$(cat "$WORK/serve_port")" <<'EOF'
import json, sys, urllib.request
port = sys.argv[1]
import time, urllib.error
deadline = time.time() + 600
while True:  # cold replica: healthz is 503 "warming" until compile warmup completes
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
req = urllib.request.Request(
    f"http://127.0.0.1:{port}/v1/generate",
    data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6}).encode(),
)
with urllib.request.urlopen(req, timeout=120) as resp:
    events = [line[len(b"data: "):] for line in resp if line.startswith(b"data: ")]
assert events[-1].strip() == b"[DONE]", events
final = json.loads(events[-2])
assert final["finish_reason"] == "length" and len(final["tokens"]) == 6, final
metrics = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
assert "relora_serve_tokens_generated_total 6" in metrics, metrics
print("HTTP stream OK:", final["tokens"])
EOF
kill -TERM "$SERVER_PID"
wait "$SERVER_PID"   # exit 0 = SIGTERM drain completed cleanly

echo "=== 9b. paged KV server (chunked prefill, long+short prompt mix) ==="
rm -f "$WORK/paged_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/paged_port" --max-batch 2 --max-queue 4 \
    --cache-size 64 --max-new-tokens 6 --eos-id -1 \
    --paged --page-size 8 --chunk-size 16 --run-dir "$WORK/paged_run" &
PAGED_PID=$!
for _ in $(seq 300); do [ -s "$WORK/paged_port" ] && break; sleep 0.2; done
[ -s "$WORK/paged_port" ] || { echo "paged server never wrote its port"; kill "$PAGED_PID"; exit 1; }
python - "$(cat "$WORK/paged_port")" "$WORK/paged_tokens.json" <<'EOF'
import json, sys, urllib.request
port = sys.argv[1]
import time, urllib.error
deadline = time.time() + 600
while True:  # cold replica: healthz is 503 "warming" until compile warmup completes
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
assert "paging" in health, health
assert health["paging"]["kv_pages_used"] == 0, health["paging"]

def generate(prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": 6}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [line[len(b"data: "):] for line in resp if line.startswith(b"data: ")]
    final = json.loads(events[-2])
    assert final["finish_reason"] == "length" and len(final["tokens"]) == 6, final
    return final["tokens"]

# long prompt (spans several chunks + pages) and short prompts interleaved
long_prompt = [(i % 100) + 1 for i in range(40)]
first = generate(long_prompt)
generate([1, 2, 3])
# identical long prompt again: served through the prefix cache, same tokens
assert generate(long_prompt) == first, "prefix-cache replay diverged"
health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
paging = health["paging"]
assert paging["kv_pages_used"] > 0, paging  # prefix entries hold pages
assert paging["prefix_cache"]["hits"] >= 1, paging
json.dump(first, open(sys.argv[2], "w"))  # 9c compares the int8 pool to these
print("paged HTTP OK:", first, "| paging:", paging)
EOF
kill -TERM "$PAGED_PID"
wait "$PAGED_PID"
grep -q "serve/kv_pages_used" "$WORK/paged_run/metrics.jsonl"
grep -q "serve/prefix_cache_hit_rate" "$WORK/paged_run/metrics.jsonl"

echo "=== 9c. int8 paged KV server (quantized pool, greedy token parity vs 9b) ==="
rm -f "$WORK/int8_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/int8_port" --max-batch 2 --max-queue 4 \
    --cache-size 64 --max-new-tokens 6 --eos-id -1 \
    --paged --page-size 8 --chunk-size 16 --kv-dtype int8 \
    --run-dir "$WORK/int8_run" &
INT8_PID=$!
for _ in $(seq 300); do [ -s "$WORK/int8_port" ] && break; sleep 0.2; done
[ -s "$WORK/int8_port" ] || { echo "int8 server never wrote its port"; kill "$INT8_PID"; exit 1; }
python - "$(cat "$WORK/int8_port")" "$WORK/paged_tokens.json" <<'EOF'
import json, sys, urllib.request
port = sys.argv[1]
import time, urllib.error
deadline = time.time() + 600
while True:  # cold replica: healthz is 503 "warming" until compile warmup completes
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
paging = health["paging"]
assert paging["kv_dtype"] == "int8", paging
# int8 codes + per-page scales undercut half the unquantized pool bytes
assert paging["kv_bytes_per_token"] > 0, paging

def generate(prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": 6}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [line[len(b"data: "):] for line in resp if line.startswith(b"data: ")]
    final = json.loads(events[-2])
    assert final["finish_reason"] == "length" and len(final["tokens"]) == 6, final
    return final["tokens"]

# the 9b prompts again: greedy decode from the quantized pool must produce
# the exact tokens the unquantized pool produced
want = json.load(open(sys.argv[2]))
long_prompt = [(i % 100) + 1 for i in range(40)]
got = generate(long_prompt)
assert got == want, f"int8 diverged from bf16 pool: {got} != {want}"
assert generate(long_prompt) == want, "int8 prefix-cache replay diverged"
print("int8 paged HTTP OK:", got, "| kv_bytes_per_token:", paging["kv_bytes_per_token"])
EOF
kill -TERM "$INT8_PID"
wait "$INT8_PID"
grep -q "serve/kv_cache_bytes" "$WORK/int8_run/metrics.jsonl"
grep -q "serve/kv_bytes_per_token" "$WORK/int8_run/metrics.jsonl"

echo "=== 9d. speculative paged server (--spec ngram, greedy token parity vs 9b) ==="
rm -f "$WORK/spec_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/spec_port" --max-batch 2 --max-queue 4 \
    --cache-size 64 --max-new-tokens 6 --eos-id -1 \
    --paged --page-size 8 --chunk-size 16 --spec ngram --spec-k 4 \
    --run-dir "$WORK/spec_run" &
SPEC_PID=$!
for _ in $(seq 300); do [ -s "$WORK/spec_port" ] && break; sleep 0.2; done
[ -s "$WORK/spec_port" ] || { echo "spec server never wrote its port"; kill "$SPEC_PID"; exit 1; }
python - "$(cat "$WORK/spec_port")" "$WORK/paged_tokens.json" <<'EOF'
import json, sys, urllib.request
port = sys.argv[1]
import time, urllib.error
deadline = time.time() + 600
while True:  # cold replica: healthz is 503 "warming" until compile warmup completes
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
spec = health["paging"]["spec"]
assert spec["mode"] == "ngram" and spec["k"] == 4, spec

def generate(prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": 6}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [line[len(b"data: "):] for line in resp if line.startswith(b"data: ")]
    final = json.loads(events[-2])
    assert final["finish_reason"] == "length" and len(final["tokens"]) == 6, final
    return final["tokens"]

# the 9b prompt again: greedy speculative decode must produce exactly the
# tokens the non-speculative paged server produced (the parity contract)
want = json.load(open(sys.argv[2]))
long_prompt = [(i % 100) + 1 for i in range(40)]
got = generate(long_prompt)
assert got == want, f"speculative decode diverged: {got} != {want}"
# a self-repeating prompt gives the prompt-lookup drafter material to match
generate([3, 5, 7] * 10)
metrics = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
assert "relora_serve_spec_drafted_total" in metrics, metrics
assert "relora_serve_spec_accept_rate" in metrics, metrics
health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
print("spec paged HTTP OK:", got, "| spec:", health["paging"]["spec"])
EOF
kill -TERM "$SPEC_PID"
wait "$SPEC_PID"
grep -q "serve/spec_drafted_total" "$WORK/spec_run/metrics.jsonl"
grep -q "serve/spec_accept_rate" "$WORK/spec_run/metrics.jsonl"

echo "=== 9e. multi-tenant adapter serving (pytest -m adapters, then the CLI drill) ==="
# the compile-heavy multi-tenant integration tests (per-tenant token parity
# on both model families, scheduler contention, churn-no-retrace, HTTP end
# to end) are slow-marked out of tier-1 and run here, like stage 6b
python -m pytest tests/test_adapters.py -q -m "adapters and slow" -p no:cacheprovider
# tenant A: a short hot-lr continuation of run 2, saved MID-cycle (step 44;
# resets land on 40/48) so its factors are nonzero and actually steer greedy
# decode — checkpoints at reset boundaries (model_8..model_40) have freshly
# reinitialized factors whose contribution is exactly zero.  tenant B is one
# of those boundary checkpoints: a valid, loadable identity-contribution
# adapter that must reproduce the base stream.
python main.py --megatron_dataset_config "$WORK/mega.yaml" --model_config llama_9m \
    --batch_size 4 --total_batch_size 8 --max_length 32 --dp_size 2 \
    --warmup_steps 2 --eval_every 1000 --seed 1 \
    --lr 0.1 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --warmed_up_model "$WORK/relora/model_40" \
    --num_training_steps 48 --save_every 4 --save_dir "$WORK/tenant_a"
mkdir -p "$WORK/adapters"
ln -sfn "$WORK/tenant_a/model_44" "$WORK/adapters/tA"
ln -sfn "$WORK/relora/model_16" "$WORK/adapters/tB"
rm -f "$WORK/adapter_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/adapter_port" --max-batch 2 --max-queue 4 \
    --cache-size 64 --max-new-tokens 8 --eos-id -1 \
    --no-merge --adapter-dir "$WORK/adapters" --adapters tA,tB --adapter-slots 3 \
    --run-dir "$WORK/adapter_run" &
ADPT_PID=$!
for _ in $(seq 300); do [ -s "$WORK/adapter_port" ] && break; sleep 0.2; done
[ -s "$WORK/adapter_port" ] || { echo "adapter server never wrote its port"; kill "$ADPT_PID"; exit 1; }
python - "$(cat "$WORK/adapter_port")" <<'EOF'
import json, sys, urllib.request
port = sys.argv[1]
import time, urllib.error
deadline = time.time() + 600
while True:  # cold replica: healthz is 503 "warming" until compile warmup completes
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
adapters = health["adapters"]
assert adapters["num_slots"] == 3, adapters
assert set(adapters["resident"]) == {"tA", "tB"}, adapters

def generate(adapter=None):
    body = {"prompt": [(i % 50) + 1 for i in range(12)], "max_new_tokens": 8}
    if adapter is not None:
        body["adapter"] = adapter
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate", data=json.dumps(body).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [line[len(b"data: "):] for line in resp if line.startswith(b"data: ")]
    final = json.loads(events[-2])
    assert final["finish_reason"] == "length" and len(final["tokens"]) == 8, final
    return final["tokens"]

base, ta, tb = generate(), generate("tA"), generate("tB")
# tenant A's hot-lr factors must steer greedy decode away from the base;
# tenant B's boundary-checkpoint factors contribute zero and must not
assert ta != base, f"tenant stream identical to base: {ta}"
assert tb == base, f"identity-factor tenant diverged from base: {tb}"
# greedy + resident slot: the same tenant must decode deterministically
assert generate("tA") == ta, "tenant decode not deterministic"
metrics = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
for want in (
    'relora_serve_adapter_requests_total{adapter="base"} 1',
    'relora_serve_adapter_requests_total{adapter="tA"} 2',
    'relora_serve_adapter_requests_total{adapter="tB"} 1',
    "relora_serve_adapter_slots_used 3",
    "relora_serve_adapter_evictions_total 0",
    "relora_serve_adapter_load_seconds_count 0",  # preloads; zero runtime loads
):
    assert want in metrics, f"missing from /metrics: {want}"
print("multi-tenant HTTP OK: base", base, "| tA", ta, "| tB", tb)
EOF
kill -TERM "$ADPT_PID"
wait "$ADPT_PID"
grep -q "serve/adapter_slots_used" "$WORK/adapter_run/metrics.jsonl"
grep -q "serve/adapter_hit_rate" "$WORK/adapter_run/metrics.jsonl"

echo "=== 9f. packed paged server (--packed, one dispatch per round, token parity vs 9b) ==="
rm -f "$WORK/packed_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/packed_port" --max-batch 2 --max-queue 4 \
    --cache-size 64 --max-new-tokens 6 --eos-id -1 \
    --paged --page-size 8 --chunk-size 16 --packed \
    --run-dir "$WORK/packed_run" &
PACKED_PID=$!
for _ in $(seq 300); do [ -s "$WORK/packed_port" ] && break; sleep 0.2; done
[ -s "$WORK/packed_port" ] || { echo "packed server never wrote its port"; kill "$PACKED_PID"; exit 1; }
python - "$(cat "$WORK/packed_port")" "$WORK/paged_tokens.json" <<'EOF'
import json, sys, urllib.request
port = sys.argv[1]
import time, urllib.error
deadline = time.time() + 600
while True:  # cold replica: healthz is 503 "warming" until compile warmup completes
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
dispatch = health["paging"]["dispatch"]
assert dispatch["mode"] == "packed", dispatch
assert dispatch["token_budget"] > 0 and dispatch["buckets"], dispatch

def generate(prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": 6}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [line[len(b"data: "):] for line in resp if line.startswith(b"data: ")]
    final = json.loads(events[-2])
    assert final["finish_reason"] == "length" and len(final["tokens"]) == 6, final
    return final["tokens"]

# the 9b prompt set again: the packed single-dispatch round must produce
# exactly the tokens the sequential paged server produced
want = json.load(open(sys.argv[2]))
long_prompt = [(i % 100) + 1 for i in range(40)]
got = generate(long_prompt)
assert got == want, f"packed step diverged from sequential: {got} != {want}"
generate([1, 2, 3])
assert generate(long_prompt) == want, "packed prefix-cache replay diverged"
health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
dispatch = health["paging"]["dispatch"]
# the tentpole invariant: every round that dispatched, dispatched once
assert dispatch["rounds"] > 0, dispatch
assert dispatch["dispatches_per_round"] == 1.0, dispatch
assert 0.0 < dispatch["packed_token_utilization"] <= 1.0, dispatch
metrics = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
assert "relora_serve_dispatches_per_round" in metrics, metrics
assert "relora_serve_tokens_per_dispatch" in metrics, metrics
assert "relora_serve_packed_token_utilization" in metrics, metrics
assert "relora_serve_model_dispatches_total" in metrics, metrics
print("packed paged HTTP OK:", got, "| dispatch:", dispatch)
EOF
kill -TERM "$PACKED_PID"
wait "$PACKED_PID"
grep -q "serve/dispatches_per_round" "$WORK/packed_run/metrics.jsonl"
grep -q "serve/tokens_per_dispatch" "$WORK/packed_run/metrics.jsonl"
grep -q "serve/packed_token_utilization" "$WORK/packed_run/metrics.jsonl"

echo "=== 10. traced run + SIGTERM flight dump (obs subsystem) ==="
# fault injection fires a real SIGTERM at update 4; the PreemptionGuard
# handler dumps the span flight recorder before the emergency checkpoint
RELORA_TPU_TRACE_DIR="$WORK/traces" RELORA_TPU_FAULTS="preempt:at=4" \
python main.py "${common[@]}" --lr 3e-3 --scheduler cosine --cycle_length 8 \
    --num_training_steps 16 --save_every 100 --save_dir "$WORK/traced"
ls "$WORK"/traced/flight_sigterm_*.json >/dev/null
# the report must parse the dump and see the trainer's span structure
python tools/trace_report.py "$WORK"/traced/flight_sigterm_*.json | tee "$WORK/trace_report.txt" | head -12
grep -q "update_step" "$WORK/trace_report.txt"
grep -q "dispatch" "$WORK/trace_report.txt"
# the JSONL sink recorded the same spans and renders too
python tools/trace_report.py "$WORK/traces/train_spans.jsonl" --max-traces 1 | grep -q "update_step"

echo "=== 11. perf attribution report ==="
# a short clean traced run (no fault injection): the report must render the
# MFU-gap waterfall and HBM plan, and the steady state must be retrace-free
RELORA_TPU_TRACE_DIR="$WORK/traces11" RELORA_TPU_MEM_PLAN=1 \
python main.py "${common[@]}" --lr 3e-3 --scheduler cosine --cycle_length 8 \
    --num_training_steps 8 --log_every 4 --save_every 100 --save_dir "$WORK/perf"
python tools/perf_report.py "$WORK/perf" --traces "$WORK/traces11/train_spans.jsonl" \
    --assert-no-retraces | tee "$WORK/perf_report.txt"
grep -q "MFU-gap waterfall" "$WORK/perf_report.txt"
grep -q "per-pytree" "$WORK/perf_report.txt"
grep -q "steady-state retraces: 0" "$WORK/perf_report.txt"

echo "=== 12. multi-replica fleet: supervisor + router, SIGKILL failover, rolling drain ==="
FLEET="$WORK/fleet"
rm -rf "$FLEET"; mkdir -p "$FLEET"
rm -f "$WORK/router_port"
# two serve.py replicas behind the health-aware router, one front-end process;
# the supervisor appends --port 0 --port-file <workdir>/replica_<i>.port
python -m relora_tpu.serve.supervisor --replicas 2 --workdir "$FLEET" \
    --router-port 0 --router-port-file "$WORK/router_port" \
    --backoff-base-s 0.2 --probe-interval-s 0.1 -- \
    python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --max-batch 2 --max-queue 8 --cache-size 64 --eos-id -1 &
SUP_PID=$!
for _ in $(seq 600); do [ -s "$WORK/router_port" ] && break; sleep 0.2; done
[ -s "$WORK/router_port" ] || { echo "router never wrote its port"; kill "$SUP_PID"; exit 1; }
python - "$(cat "$WORK/router_port")" "$FLEET" <<'EOF'
import json, os, signal, sys, time, urllib.error, urllib.request

port, fleet = sys.argv[1], sys.argv[2]
base = f"http://127.0.0.1:{port}"

def healthz():
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            return json.load(r)
    except urllib.error.HTTPError as e:  # 503 while < 1 replica routable
        return json.loads(e.read().decode())

def wait_healthy(n, tries=600):
    h = {}
    for _ in range(tries):
        h = healthz()
        if h.get("healthy_replicas", 0) >= n:
            return h
        time.sleep(0.2)
    raise SystemExit(f"fleet never reached {n} healthy replicas: {h}")

def stream(max_new_tokens, kill_mid_stream=False):
    """One /v1/generate stream through the router -> (replica_id, events)."""
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": max_new_tokens}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        rid = resp.headers["X-Relora-Replica"]
        events = []
        for line in resp:
            if not line.startswith(b"data: "):
                continue
            events.append(line[len(b"data: "):].strip())
            if kill_mid_stream and len(events) == 1:
                pid = int(open(os.path.join(fleet, f"replica_{rid[1:]}.pid")).read())
                os.kill(pid, signal.SIGKILL)
    return rid, events

wait_healthy(2)
# warm both replicas (a replica's first request compiles the decode graph);
# equal-load ties round-robin, so a few sequential streams cover the fleet
seen = set()
for _ in range(8):
    rid, events = stream(4)
    assert events[-1] == b"[DONE]", events
    seen.add(rid)
    if len(seen) == 2:
        break
assert len(seen) == 2, f"router never spread load across both replicas: {seen}"

# SIGKILL the serving replica mid-stream: bytes already reached the client, so
# no silent replay — the stream must end with a typed error, never a hang
victim, events = stream(32, kill_mid_stream=True)
if events[-1] == b"[DONE]":
    print("note: victim finished its stream before the SIGKILL landed")
else:
    err = json.loads(events[-1]).get("error", {})
    assert err.get("type") == "stream_interrupted", events[-3:]
    assert err.get("retryable") is False, err

# the survivor keeps serving while the victim restarts
other, events = stream(4)
assert other != victim and events[-1] == b"[DONE]", (other, victim, events[-3:])

# the supervisor restarts the victim and the router routes to it again
wait_healthy(2)
for _ in range(60):
    got, events = stream(4)
    assert events[-1] == b"[DONE]", events
    if got == victim:
        break
else:
    raise SystemExit(f"restarted replica {victim} never served traffic again")

metrics = urllib.request.urlopen(f"{base}/metrics", timeout=30).read().decode()
assert "relora_router_healthy_replicas 2" in metrics, metrics
assert "relora_router_requests_total" in metrics, metrics
print(f"router failover OK: {victim} killed mid-stream, restarted, serving again")
EOF
kill -TERM "$SUP_PID"
wait "$SUP_PID"   # exit 0 = rolling drain + router shutdown completed cleanly

echo "=== 13. fleet observability plane: collector, SLO burn drill, fleet report ==="
OBS_FLEET="$WORK/obs_fleet"
rm -rf "$OBS_FLEET"; mkdir -p "$OBS_FLEET"
rm -f "$WORK/obs_router_port"
# compressed burn windows so the drill fires/clears in seconds, not hours
cat > "$WORK/slo_drill.json" <<'JSON'
{"slos": [{"name": "availability", "series": "up", "threshold": 1.0,
           "bad_when": "lt", "objective": 0.9, "windows": [[20.0, 3.0, 2.0]]}]}
JSON
# replica 0's first incarnation is armed to os._exit mid-decode (the serving
# fault drill); env_overrides_respawn=False means its respawn comes back clean
python -m relora_tpu.serve.supervisor --replicas 2 --workdir "$OBS_FLEET" \
    --router-port 0 --router-port-file "$WORK/obs_router_port" \
    --backoff-base-s 0.2 --probe-interval-s 0.1 \
    --fleet-cadence-s 0.2 --slo-config "$WORK/slo_drill.json" \
    --replica-env "0:RELORA_TPU_FAULTS=serve_crash:at_token=6" -- \
    python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --max-batch 2 --max-queue 8 --cache-size 64 --eos-id -1 &
OBS_SUP_PID=$!
for _ in $(seq 600); do [ -s "$WORK/obs_router_port" ] && break; sleep 0.2; done
[ -s "$WORK/obs_router_port" ] || { echo "router never wrote its port"; kill "$OBS_SUP_PID"; exit 1; }
python - "$(cat "$WORK/obs_router_port")" "$OBS_FLEET" <<'EOF'
import json, sys, time, urllib.error, urllib.request

port, fleet = sys.argv[1], sys.argv[2]
base = f"http://127.0.0.1:{port}"
series_path = f"{fleet}/fleet_series.jsonl"

def healthz():
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            return json.load(r)
    except urllib.error.HTTPError as e:
        return json.loads(e.read().decode())

def wait_healthy(n, tries=600):
    h = {}
    for _ in range(tries):
        h = healthz()
        if h.get("healthy_replicas", 0) >= n:
            return
        time.sleep(0.2)
    raise SystemExit(f"fleet never reached {n} healthy replicas: {h}")

def availability_transitions():
    """(state, _time) of persisted r0 availability burn transitions."""
    out = []
    try:
        with open(series_path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail
                if (rec.get("_event") == "slo_burn_alert"
                        and rec.get("slo") == "availability"
                        and rec.get("_source") == "r0"):
                    out.append((rec["state"], rec["_time"]))
    except OSError:
        pass
    return out

def stream(max_new_tokens):
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": max_new_tokens}).encode(),
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            resp.read()
            return True
    except (urllib.error.URLError, ConnectionError, OSError):
        return False  # mid-crash stream errors are the drill, not a failure

wait_healthy(2)
time.sleep(6)  # boot-time burn (replicas down while compiling) must clear
fires0 = sum(1 for s, _ in availability_transitions() if s == "fire")

# drive tokens until replica 0's armed crash lands (at_token=6)
t_crash = None
for _ in range(100):
    stream(4)
    if healthz().get("healthy_replicas", 2) < 2:
        t_crash = time.time()
        break
    time.sleep(0.1)
assert t_crash is not None, "armed replica never crashed"

# the burn alert must FIRE while the replica is down...
for _ in range(200):
    fires = [(s, t) for s, t in availability_transitions() if s == "fire"]
    if len(fires) > fires0:
        break
    time.sleep(0.2)
else:
    raise SystemExit(f"SLO burn alert never fired after the crash: {availability_transitions()}")

# ...and CLEAR once the supervisor's respawn is healthy again
wait_healthy(2)
for _ in range(300):
    trans = availability_transitions()
    if trans and trans[-1][0] == "clear":
        break
    time.sleep(0.2)
else:
    raise SystemExit(f"SLO burn alert never cleared after recovery: {availability_transitions()}")

# the collector's plane is mounted on the router front-end
fm = urllib.request.urlopen(f"{base}/fleet/metrics", timeout=30).read().decode()
assert "relora_fleet_scrape_rounds_total" in fm, fm[:400]
assert "relora_fleet_source_r0_up 1" in fm, fm[:400]
fs = json.load(urllib.request.urlopen(f"{base}/fleet/series?source=r0&series=up", timeout=30))
assert fs["sources"]["r0"]["up"], fs
assert any(o["slo"] == "availability" for o in fs["slo"]["objectives"]), fs["slo"]
print("fleet drill OK: burn alert fired on crash, cleared after respawn")
EOF
kill -TERM "$OBS_SUP_PID"
wait "$OBS_SUP_PID"
# post-mortem: rebuild the fleet picture from the persisted store alone
python tools/fleet_report.py "$OBS_FLEET/fleet_series.jsonl" --window-s 60 > "$WORK/fleet_report.txt"
grep -q "== fleet health ==" "$WORK/fleet_report.txt"
grep -q "== SLO / error budget ==" "$WORK/fleet_report.txt"
grep -q "slo_burn_alert" "$WORK/fleet_report.txt"
grep -q "supervisor_" "$WORK/fleet_report.txt"   # lifecycle events on the timeline
head -40 "$WORK/fleet_report.txt"

echo "=== 14. continuous deployment: watcher hot-swap, corrupt reject, canary rollback ==="
DEPLOY_FLEET="$WORK/deploy_fleet"
rm -rf "$DEPLOY_FLEET"; mkdir -p "$DEPLOY_FLEET"
rm -f "$WORK/deploy_router_port"
# the trainer's manifest commit already published latest -> model_40; prove
# that, then re-pin to model_32 so the fleet boots one version behind and the
# watcher has a verified newer checkpoint to roll forward to
python - "$WORK/relora" <<'EOF'
import json, sys
with open(f"{sys.argv[1]}/latest") as f:
    rec = json.load(f)
assert rec["path"] == "model_40", f"trainer did not publish latest: {rec}"
print(f"trainer published latest -> {rec['path']} (step {rec['step']})")
EOF
python -m relora_tpu.serve.deploy publish "$WORK/relora/model_32"
# drill artifacts: a corrupt copy (the watcher must refuse it) and a valid
# checkpoint shipping a deliberately wrong canary baseline (the canary gate
# must yank the fleet back)
rm -rf "$WORK/relora/model_48" "$WORK/relora/model_9924"
cp -r "$WORK/relora/model_40" "$WORK/relora/model_48"
cp -r "$WORK/relora/model_24" "$WORK/relora/model_9924"
python - "$WORK/relora/model_48" "$WORK/relora/model_9924" <<'EOF'
import json, os, sys
corrupt, bad_canary = sys.argv[1], sys.argv[2]
for dirpath, _, names in os.walk(os.path.join(corrupt, "state")):
    for name in sorted(names):
        p = os.path.join(dirpath, name)
        if os.path.getsize(p):
            with open(p, "r+b") as f:
                b = f.read(1)
                f.seek(0)
                f.write(bytes([b[0] ^ 0xFF]))
            break
    else:
        continue
    break
else:
    raise SystemExit(f"no state file to corrupt under {corrupt}")
with open(os.path.join(bad_canary, "canary.json"), "w") as f:
    json.dump({"prompts": [[1, 2, 3]], "tokens": [[255, 255, 255, 255]],
               "max_new_tokens": 4}, f)
EOF
python -m relora_tpu.serve.supervisor --replicas 2 --workdir "$DEPLOY_FLEET" \
    --router-port 0 --router-port-file "$WORK/deploy_router_port" \
    --backoff-base-s 0.2 --probe-interval-s 0.1 --fleet-cadence-s 0.2 \
    --watch-checkpoints "$WORK/relora" --watch-interval-s 0.3 \
    --canary-max-new-tokens 4 -- \
    python serve.py --checkpoint "$WORK/relora/model_32" --model_config llama_9m \
    --max-batch 2 --max-queue 16 --cache-size 64 --eos-id -1 &
DEPLOY_SUP_PID=$!
for _ in $(seq 600); do [ -s "$WORK/deploy_router_port" ] && break; sleep 0.2; done
[ -s "$WORK/deploy_router_port" ] || { echo "router never wrote its port"; kill "$DEPLOY_SUP_PID"; exit 1; }
python - "$(cat "$WORK/deploy_router_port")" "$DEPLOY_FLEET" "$WORK/relora" <<'EOF'
import json, subprocess, sys, threading, time, urllib.error, urllib.request

port, fleet, save_dir = sys.argv[1], sys.argv[2], sys.argv[3]
base = f"http://127.0.0.1:{port}"
series_path = f"{fleet}/fleet_series.jsonl"

def healthz():
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            return json.load(r)
    except urllib.error.HTTPError as e:
        return json.loads(e.read().decode())

def wait_healthy(n, tries=600):
    h = {}
    for _ in range(tries):
        h = healthz()
        if h.get("healthy_replicas", 0) >= n:
            return
        time.sleep(0.2)
    raise SystemExit(f"fleet never reached {n} healthy replicas: {h}")

def deploy_events():
    out = []
    try:
        with open(series_path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail
                if rec.get("_event", "").startswith("deploy_"):
                    out.append(rec)
    except OSError:
        pass
    return out

def wait_event(name, want_detail="", tries=600):
    for _ in range(tries):
        evs = [e for e in deploy_events()
               if e["_event"] == name and want_detail in e.get("detail", "")]
        if evs:
            return evs
        time.sleep(0.2)
    raise SystemExit(f"never saw {name} ({want_detail!r}) in the fleet store")

def publish(ckpt, force=False):
    cmd = [sys.executable, "-m", "relora_tpu.serve.deploy", "publish", ckpt]
    if force:
        cmd.append("--force")
    subprocess.run(cmd, check=True)

# continuous 8-way load for the whole drill; EVERY request must finish
dropped, lock = [], threading.Lock()
last_weights = {}  # replica rid -> last X-Relora-Weights it answered with
stop = threading.Event()

def worker(wid):
    while not stop.is_set():
        req = urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 4,
                             "temperature": 0.0, "stream": False}).encode(),
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                body = json.load(resp)
                rid = resp.headers.get("X-Relora-Replica")
                weights = resp.headers.get("X-Relora-Weights")
                if body.get("finish_reason") not in ("eos", "length"):
                    raise ValueError(f"bad finish: {body}")
                with lock:
                    if rid and weights:
                        last_weights[rid] = weights
        except Exception as e:
            with lock:
                dropped.append(f"worker {wid}: {e!r}")
            return

def wait_fleet_on(version, tries=600):
    for _ in range(tries):
        with lock:
            vals = dict(last_weights)
        if len(vals) >= 2 and all(v == str(version) for v in vals.values()):
            return
        time.sleep(0.2)
    raise SystemExit(f"fleet never converged on weights {version}: {last_weights}")

wait_healthy(2)
workers = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
for t in workers:
    t.start()

# 1. rolling hot-swap under load: publish model_40; the watcher verifies it
#    and walks the fleet one replica at a time behind the canary gate
publish(f"{save_dir}/model_40")
wait_event("deploy_complete", "model_40")
wait_fleet_on(40)
assert not dropped, dropped
print("rolling hot-swap 32 -> 40 complete, zero dropped requests")

# 2. corrupt publish: the watcher must refuse it and the fleet must hold 40
publish(f"{save_dir}/model_48", force=True)
wait_event("deploy_reject", "model_48")
assert not any("model_48" in e.get("detail", "")
               for e in deploy_events() if e["_event"] == "deploy_begin"), \
    "corrupt checkpoint reached the fleet"
wait_fleet_on(40)
print("corrupt publish rejected at the watcher, fleet held version 40")

# 3. canary rollback: model_9924 verifies clean but ships a wrong canary
#    baseline -- the gate must roll the whole fleet back to model_40
publish(f"{save_dir}/model_9924")
wait_event("deploy_canary_fail")
wait_event("deploy_rollback")
publish(f"{save_dir}/model_40")  # re-pin: end the (by-design) retry loop
wait_fleet_on(40)
print("canary mismatch rolled the fleet back to 40")

stop.set()
for t in workers:
    t.join()
assert not dropped, dropped
h = healthz()
assert h.get("healthy_replicas", 0) == 2, h
# crosscheck through the collector: both replicas' scraped healthz agree
fs = json.load(urllib.request.urlopen(
    f"{base}/fleet/series?series=healthz_weights_version", timeout=30))
for rid in ("r0", "r1"):
    pts = fs["sources"].get(rid, {}).get("healthz_weights_version") or []
    assert pts and pts[-1][1] == 40.0, (rid, pts[-2:])
print("deploy drill OK: hot-swap, corrupt reject, and canary rollback "
      "all converged on one healthy version")
EOF
kill -TERM "$DEPLOY_SUP_PID"
wait "$DEPLOY_SUP_PID"
# post-mortem: the whole deployment story must be reconstructible from the
# persisted fleet store alone
python tools/fleet_report.py "$DEPLOY_FLEET/fleet_series.jsonl" --window-s 600 \
    --events 200 > "$WORK/deploy_report.txt"
grep -q "deploy_complete" "$WORK/deploy_report.txt"
grep -q "deploy_reject" "$WORK/deploy_report.txt"
grep -q "deploy_canary_fail" "$WORK/deploy_report.txt"
grep -q "deploy_rollback" "$WORK/deploy_report.txt"
grep "deploy_" "$WORK/deploy_report.txt" | head -20

echo "=== 15. elastic fleet: SLO-driven 1->2->1 autoscale under load ==="
AS_FLEET="$WORK/as_fleet"
rm -rf "$AS_FLEET"; mkdir -p "$AS_FLEET"
rm -f "$WORK/as_router_port"
# one replica to start; the autoscaler reads the collector's store and may
# grow to 2 under sustained queue burn, shrinking back after the idle window
python -m relora_tpu.serve.supervisor --replicas 1 --workdir "$AS_FLEET" \
    --router-port 0 --router-port-file "$WORK/as_router_port" \
    --backoff-base-s 0.2 --probe-interval-s 0.1 \
    --fleet-cadence-s 0.2 \
    --autoscale --min-replicas 1 --max-replicas 2 \
    --queue-depth-high 2 --burn-window-s 1.5 --idle-window-s 6 \
    --cooldown-s 3 --autoscale-interval-s 0.25 -- \
    python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --max-batch 2 --max-queue 16 --cache-size 64 --eos-id -1 &
AS_SUP_PID=$!
for _ in $(seq 600); do [ -s "$WORK/as_router_port" ] && break; sleep 0.2; done
[ -s "$WORK/as_router_port" ] || { echo "router never wrote its port"; kill "$AS_SUP_PID"; exit 1; }
python - "$(cat "$WORK/as_router_port")" "$AS_FLEET" <<'EOF'
import json, sys, threading, time, urllib.error, urllib.request

port, fleet = sys.argv[1], sys.argv[2]
base = f"http://127.0.0.1:{port}"
series_path = f"{fleet}/fleet_series.jsonl"

def healthz():
    try:
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            return json.load(r)
    except urllib.error.HTTPError as e:
        return json.loads(e.read().decode())

def wait_healthy(n, tries=1500):
    h = {}
    for _ in range(tries):
        h = healthz()
        if h.get("healthy_replicas", 0) >= n:
            return
        time.sleep(0.2)
    raise SystemExit(f"fleet never reached {n} healthy replicas: {h}")

def autoscale_events():
    out = []
    try:
        with open(series_path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail
                if str(rec.get("_event", "")).startswith("autoscale_"):
                    out.append(rec)
    except OSError:
        pass
    return out

wait_healthy(1)

# burst: enough concurrent streams to hold queue_depth over the burn window
stop = threading.Event()
dropped = []
def worker(wid):
    while not stop.is_set():
        req = urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 8}).encode(),
        )
        try:
            with urllib.request.urlopen(req, timeout=120) as resp:
                resp.read()
        except urllib.error.HTTPError:
            pass  # 429/503 is typed backpressure, not a drop
        except Exception as e:
            dropped.append(f"worker {wid}: {e!r}")
            return

workers = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
for t in workers:
    t.start()

deadline = time.time() + 120
while time.time() < deadline:
    if any(e.get("_event") == "autoscale_up" for e in autoscale_events()):
        break
    time.sleep(0.2)
else:
    raise SystemExit(f"autoscaler never scaled up: {autoscale_events()[-5:]}")
# the new replica pays its compile warmup (healthz "warming", unroutable)
# before the router counts it healthy
wait_healthy(2)
print("burst scaled the fleet 1 -> 2 (new replica warmed and routable)")

# quiet tail: idle window + cooldown must bring the fleet back to the floor
stop.set()
for t in workers:
    t.join()
assert not dropped, dropped
deadline = time.time() + 180
while time.time() < deadline:
    if any(e.get("_event") == "autoscale_down_complete" for e in autoscale_events()):
        break
    time.sleep(0.2)
else:
    raise SystemExit(
        f"autoscaler never scaled back down: {autoscale_events()[-5:]}")
for _ in range(300):
    h = healthz()
    if h.get("healthy_replicas", 0) == 1:
        break
    time.sleep(0.2)
else:
    raise SystemExit(f"fleet never settled back to 1 replica: {healthz()}")
kinds = [e.get("_event") for e in autoscale_events()]
assert "autoscale_decision" in kinds, kinds
print("idle scaled the fleet 2 -> 1; zero dropped requests across the resize")
EOF
kill -TERM "$AS_SUP_PID"
wait "$AS_SUP_PID"   # exit 0 = rolling drain wins over any pending scale-up
# the elastic history must be reconstructible from the persisted store
python tools/fleet_report.py "$AS_FLEET/fleet_series.jsonl" --window-s 600 \
    --events 200 > "$WORK/as_report.txt"
grep -q "== autoscale ==" "$WORK/as_report.txt"
grep -q "autoscale_up" "$WORK/as_report.txt"
grep -q "autoscale_down_complete" "$WORK/as_report.txt"
grep -q "replicas:" "$WORK/as_report.txt"
grep "autoscale_" "$WORK/as_report.txt" | head -12

echo "=== 16. disaggregated fleet: prefill/decode roles, KV page migration, prefix directory ==="
# reference first: one *mixed* paged replica records the greedy tokens the
# disaggregated fleet must reproduce exactly (same checkpoint, same pool)
rm -f "$WORK/dg_ref_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/dg_ref_port" --max-batch 2 --max-queue 8 \
    --cache-size 64 --eos-id -1 \
    --paged --page-size 8 --chunk-size 16 --kv-dtype int8 &
DG_REF_PID=$!
for _ in $(seq 300); do [ -s "$WORK/dg_ref_port" ] && break; sleep 0.2; done
[ -s "$WORK/dg_ref_port" ] || { echo "reference server never wrote its port"; kill "$DG_REF_PID"; exit 1; }
python - "$(cat "$WORK/dg_ref_port")" "$WORK/dg_ref.json" <<'EOF'
import json, sys, time, urllib.error, urllib.request
port = sys.argv[1]
deadline = time.time() + 600
while True:
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
assert health["role"] == "mixed", health

def generate(prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": 8}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [
            line[len(b"data: "):].strip()
            for line in resp
            if line.startswith(b"data: ")
        ]
    assert events[-1] == b"[DONE]", events[-3:]
    return json.loads(events[-2])["tokens"]

short = [(i % 100) + 1 for i in range(8)]
long1 = [(i % 100) + 1 for i in range(40)]
long2 = long1[:32] + [7, 8, 9, 10, 11, 12, 13, 14]  # shared 4-page prefix
json.dump(
    {"short": generate(short), "long1": generate(long1), "long2": generate(long2)},
    open(sys.argv[2], "w"),
)
print("disagg reference tokens recorded")
EOF
kill -TERM "$DG_REF_PID"
wait "$DG_REF_PID"

# the disaggregated fleet: replica 0 prefill, replica 1 decode, replica 2
# mixed (the fallback pool), router classifying at 24 prompt tokens, the
# collector (0.2s cadence) feeding the fleet prefix-page directory
DG_FLEET="$WORK/dg_fleet"
rm -rf "$DG_FLEET"; mkdir -p "$DG_FLEET"
rm -f "$WORK/dg_router_port"
python -m relora_tpu.serve.supervisor --replicas 3 \
    --prefill-replicas 1 --decode-replicas 1 --classify-threshold 24 \
    --workdir "$DG_FLEET" \
    --router-port 0 --router-port-file "$WORK/dg_router_port" \
    --backoff-base-s 0.2 --probe-interval-s 0.1 --fleet-cadence-s 0.2 -- \
    python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --max-batch 2 --max-queue 8 --cache-size 64 --eos-id -1 \
    --paged --page-size 8 --chunk-size 16 --kv-dtype int8 &
DG_SUP_PID=$!
for _ in $(seq 600); do [ -s "$WORK/dg_router_port" ] && break; sleep 0.2; done
[ -s "$WORK/dg_router_port" ] || { echo "router never wrote its port"; kill "$DG_SUP_PID"; exit 1; }
python - "$(cat "$WORK/dg_router_port")" "$DG_FLEET" "$WORK/dg_ref.json" <<'EOF'
import json, os, signal, sys, time, urllib.error, urllib.request

port, fleet, want = sys.argv[1], sys.argv[2], json.load(open(sys.argv[3]))
base = f"http://127.0.0.1:{port}"

def healthz(p=None, b=None):
    url = b or (f"http://127.0.0.1:{p}" if p else base)
    try:
        with urllib.request.urlopen(f"{url}/healthz", timeout=10) as r:
            return json.load(r)
    except urllib.error.HTTPError as e:
        return json.loads(e.read().decode())

def wait_healthy(n, tries=1500):
    h = {}
    for _ in range(tries):
        h = healthz()
        if h.get("healthy_replicas", 0) >= n:
            return h
        time.sleep(0.2)
    raise SystemExit(f"fleet never reached {n} healthy replicas: {h}")

def stream(prompt, kill_mid_stream=False):
    req = urllib.request.Request(
        f"{base}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": 8}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        rid = resp.headers["X-Relora-Replica"]
        events = []
        for line in resp:
            if not line.startswith(b"data: "):
                continue
            events.append(line[len(b"data: "):].strip())
            if kill_mid_stream and len(events) == 1:
                pid = int(open(os.path.join(fleet, f"replica_{rid[1:]}.pid")).read())
                os.kill(pid, signal.SIGKILL)
    return rid, events

wait_healthy(3)
# replica roles come up exactly as assigned (healthz is the role advertisement)
role_of = {}
for i in range(3):
    rp = open(os.path.join(fleet, f"replica_{i}.port")).read().strip()
    role_of[f"r{i}"] = healthz(p=rp)["role"]
assert sorted(role_of.values()) == ["decode", "mixed", "prefill"], role_of
assert role_of["r0"] == "prefill" and role_of["r1"] == "decode", role_of

short, long1, long2 = (
    [(i % 100) + 1 for i in range(8)],
    [(i % 100) + 1 for i in range(40)],
    [(i % 100) + 1 for i in range(32)] + [7, 8, 9, 10, 11, 12, 13, 14],
)

def final(events):
    assert events[-1] == b"[DONE]", events[-3:]
    return json.loads(events[-2])["tokens"]

# short prompt -> decode pool; long prompt -> prefill pool, whose finished
# page run migrates to the decode peer mid-stream.  Either way the tokens
# must be exactly what the single mixed replica produced.
rid, events = stream(short)
assert role_of[rid] == "decode", (rid, role_of)
assert final(events) == want["short"], (final(events), want["short"])
rid, events = stream(long1)
assert role_of[rid] == "prefill", (rid, role_of)
assert final(events) == want["long1"], (final(events), want["long1"])
rid, events = stream(long2)
assert final(events) == want["long2"], (final(events), want["long2"])

# the long streams really were handed off: donor-side migration counters
prefill_port = open(os.path.join(fleet, "replica_0.port")).read().strip()
for _ in range(100):
    m = urllib.request.urlopen(f"http://127.0.0.1:{prefill_port}/metrics", timeout=10).read().decode()
    migrated = [l for l in m.splitlines() if l.startswith("relora_serve_pages_migrated_total")]
    if migrated and float(migrated[0].split()[-1]) > 0:
        break
    time.sleep(0.2)
else:
    raise SystemExit(f"prefill replica never migrated a page run: {migrated}")
router_metrics = urllib.request.urlopen(f"{base}/metrics", timeout=10).read().decode()
assert "relora_router_routed_prefill_total" in router_metrics, router_metrics
assert "relora_router_routed_decode_total" in router_metrics, router_metrics

# fleet prefix-page directory: the collector scraped the prefill replica's
# digest advertisement; the router resolves a digest to its holder
digests = healthz(p=prefill_port).get("prefix_digests") or []
assert digests, "prefill replica advertises no prefix digests after long prompts"
holder = None
for _ in range(100):  # collector cadence: the next scrape feeds the directory
    try:
        with urllib.request.urlopen(f"{base}/fleet/prefix?d={digests[0]}", timeout=10) as r:
            holder = json.load(r)
            break
    except urllib.error.HTTPError:
        time.sleep(0.2)
assert holder and holder["digest"] == digests[0] and holder["port"], holder
print(f"prefix directory resolves {digests[0][:12]}... -> {holder['replica']}")

# SIGKILL the prefill replica mid-stream: bytes already reached the client,
# so the stream must end with a typed error (never a hang, never a replay)
victim, events = stream(long1, kill_mid_stream=True)
assert role_of[victim] == "prefill", (victim, role_of)
if events[-1] == b"[DONE]":
    print("note: victim finished its stream before the SIGKILL landed")
else:
    err = json.loads(events[-1]).get("error", {})
    assert err.get("type") == "stream_interrupted", events[-3:]
    assert err.get("retryable") is False, err

# with the prefill pool empty the router falls back to the mixed replica —
# same tokens, zero dropped requests
rid, events = stream(long1)
assert role_of[rid] == "mixed", (rid, role_of)
assert final(events) == want["long1"], (final(events), want["long1"])

# the supervisor restarts the victim; the rearmed prefill pool serves again
wait_healthy(3)
for _ in range(60):
    rid, events = stream(long2)
    assert final(events) == want["long2"], (final(events), want["long2"])
    if rid == victim:
        break
else:
    raise SystemExit(f"restarted prefill replica {victim} never served traffic again")
print("disagg fleet OK: role routing, token-identical migration, typed SIGKILL fallback")
EOF
kill -TERM "$DG_SUP_PID"
wait "$DG_SUP_PID"   # exit 0 = rolling drain across all three roles

echo "=== 17. compression: prune-retrain, draft export, --spec model parity vs 9b ==="
# (a) prune mid-training: ReLoRA from the stage-1 warmup fixes the keep-mask
# at the first merge past prune_start_step, then every later cycle re-zeroes
# the holes before requant and retrains the fresh factors around them
python main.py "${common[@]}" --lr 5e-3 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --warmed_up_model "$WORK/full/model_8" \
    --prune_sparsity 0.5 --prune_scope per_matrix --prune_start_step 2 \
    --reset_init magnitude \
    --num_training_steps 24 --save_every 8 --save_dir "$WORK/prune"
grep -q "prune_mask_computed" "$WORK/prune/metrics.jsonl"
[ -f "$WORK/prune/model_24/prune_mask.npz" ]   # sidecar rides the checkpoint
[ -f "$WORK/prune/model_24/prune_meta.json" ]

# (b) resume the retrain cycle: autoresume restores the sidecar mask (no
# recompute — the event count stays 1) and training continues through
# another merge with the holes intact
python main.py "${common[@]}" --lr 5e-3 --use_peft true --relora 8 --cycle_length 8 \
    --scheduler cosine_restarts --restart_warmup_steps 2 \
    --prune_sparsity 0.5 --prune_scope per_matrix --prune_start_step 2 \
    --reset_init magnitude \
    --num_training_steps 32 --save_every 8 --save_dir "$WORK/prune" \
    --autoresume true
[ "$(grep -c prune_mask_computed "$WORK/prune/metrics.jsonl")" = 1 ]
[ -f "$WORK/prune/model_32/prune_mask.npz" ]
python - "$WORK/prune/model_32" <<'EOF'
# the stored base kernels stay exactly zero on the pruned positions across
# prune -> retrain -> resume -> merge (the factors are dense, the base is not)
import sys
import numpy as np
from relora_tpu.compress import prune
from relora_tpu.train.checkpoint import restore_serving_params
mask, meta = prune.load_mask(sys.argv[1])
assert mask is not None and meta["sparsity"] > 0.4, meta
# draft-export the resumed checkpoint: the sidecar mask is reused verbatim
out = __import__("relora_tpu.compress.draft", fromlist=["export_draft_checkpoint"])
path = out.export_draft_checkpoint(sys.argv[1], sys.argv[1] + "_draft")
params = restore_serving_params(path)
checked = 0
for mpath, keep in prune._mask_items(mask):
    mod = prune._module_at(params, mpath)
    w = np.asarray(mod["kernel"], np.float32)
    assert not np.any(w[~np.asarray(keep)]), mpath
    checked += 1
assert checked > 0
print(f"prune-retrain OK: {meta['sparsity']*100:.1f}% sparsity exact-zero in {checked} modules")
EOF

# (c) export a light draft from the 9b checkpoint and serve it as the
# --spec model drafter: greedy output must replay the 9b tokens exactly
# (the parity contract — a pruned draft can only lower acceptance, never
# change what the server says)
python -m relora_tpu.compress.draft "$WORK/relora/model_40" "$WORK/draft" \
    --sparsity 0.3 --scope per_matrix
rm -f "$WORK/mspec_port"
python serve.py --checkpoint "$WORK/relora/model_40" --model_config llama_9m \
    --port 0 --port-file "$WORK/mspec_port" --max-batch 2 --max-queue 4 \
    --cache-size 64 --max-new-tokens 6 --eos-id -1 \
    --paged --page-size 8 --chunk-size 16 --spec model --spec-k 4 \
    --draft-checkpoint "$WORK/draft/model_40" --run-dir "$WORK/mspec_run" &
MSPEC_PID=$!
for _ in $(seq 300); do [ -s "$WORK/mspec_port" ] && break; sleep 0.2; done
[ -s "$WORK/mspec_port" ] || { echo "model-spec server never wrote its port"; kill "$MSPEC_PID"; exit 1; }
python - "$(cat "$WORK/mspec_port")" "$WORK/paged_tokens.json" <<'EOF'
import json, sys, urllib.request
port = sys.argv[1]
import time, urllib.error
deadline = time.time() + 600
while True:  # cold replica: healthz is 503 "warming" until compile warmup completes
    try:
        health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
    except urllib.error.HTTPError as e:
        health = json.load(e)
    if health["status"] == "ok":
        break
    assert health["status"] == "warming" and time.time() < deadline, health
    time.sleep(0.5)
spec = health["paging"]["spec"]
assert spec["mode"] == "model" and spec["k"] == 4, spec

def generate(prompt):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": 6}).encode(),
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        events = [line[len(b"data: "):] for line in resp if line.startswith(b"data: ")]
    final = json.loads(events[-2])
    assert final["finish_reason"] == "length" and len(final["tokens"]) == 6, final
    return final["tokens"]

# the 9b prompts again: greedy model-drafted decode must produce exactly
# the tokens the non-speculative paged server produced
want = json.load(open(sys.argv[2]))
long_prompt = [(i % 100) + 1 for i in range(40)]
got = generate(long_prompt)
assert got == want, f"model-drafted decode diverged: {got} != {want}"
generate([1, 2, 3])
health = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=30))
spec = health["paging"]["spec"]
assert spec["drafted"] > 0, spec  # the model drafter always proposes
metrics = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
assert "relora_serve_spec_mode_model 1" in metrics, metrics
assert "relora_serve_spec_drafted_total" in metrics, metrics
print("model-spec HTTP OK:", got, "| spec:", spec)
EOF
kill -TERM "$MSPEC_PID"
wait "$MSPEC_PID"
grep -q "serve/spec_mode_model" "$WORK/mspec_run/metrics.jsonl"

echo "SMOKE OK"
