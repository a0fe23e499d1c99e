#!/usr/bin/env bash
# Loss-parity experiment (BASELINE.json quality target): ReLoRA vs full-rank
# at matched tokens, llama_35m on a ~100M-token local corpus.
#
# Mirrors the reference recipe structure (README.md:69-89): a shared
# full-rank warmup, then two branches from the same checkpoint —
#   A) full-rank continuation, lr 1e-3 cosine
#   B) ReLoRA r=128, merge+reset every 1000 steps, lr 2e-3 cosine_restarts
#      (the "2x full-rank lr" rule, README.md:19-20)
# Both train to the same total step count / token count; compare eval loss.
#
# Prereq: python tools/build_text_corpus.py --out $CORPUS ... (see README)
set -euo pipefail
cd "$(dirname "$0")/.."

CORPUS="${CORPUS:-/tmp/corpus/local400}"
WORK="${WORK:-/tmp/loss_parity}"
STEPS_WARMUP="${STEPS_WARMUP:-1000}"
STEPS_TOTAL="${STEPS_TOTAL:-8000}"
BATCH="${BATCH:-24}"
SEQ="${SEQ:-512}"
MODEL="${MODEL:-llama_35m}"
LORA_R="${LORA_R:-128}"
CYCLE="${CYCLE:-1000}"
EVAL_EVERY="${EVAL_EVERY:-500}"
EVAL_TOKENS="${EVAL_TOKENS:-500000}"
FINAL_EVAL_TOKENS="${FINAL_EVAL_TOKENS:-100000000}"
# SEED seeds init, data order, and LoRA re-inits — run a second seed (with
# its own WORK dir) to check the parity gap is robust, not a seed artifact
SEED="${SEED:-0}"
LR_WARMUP="${LR_WARMUP:-250}"
RESTART_WARMUP="${RESTART_WARMUP:-100}"
# OPT_PRUNE: empty or 0 = zero reset (reference default); a ratio in
# (0, 1) switches the ReLoRA branch to magnitude-pruning resets.  "0" is
# folded into the default so it cannot silently select a third behavior
# (no reset at all) via --reset_optimizer_on_relora false.
OPT_PRUNE="${OPT_PRUNE:-}"
[ "$OPT_PRUNE" = "0" ] && OPT_PRUNE=""
# run dirs are keyed by $MODEL (and by seed for SEED!=0) so re-runs with a
# different MODEL or SEED never reuse an incompatible warmup checkpoint or
# silently autoresume another run's finished branches — without the seed
# key, `SEED=1` in a reused WORK dir would skip every stage and relabel
# the seed-0 result as a replication
KEY="$MODEL"
[ "$SEED" != "0" ] && KEY="${MODEL}_s${SEED}"
# The ReLoRA branch (and the comparison output) additionally key on every
# knob that changes that branch's trajectory — reset mode, LoRA rank,
# cycle length: a re-run with any of these changed in a reused WORK dir
# must not autoresume the previous variant's checkpoints and relabel its
# curve.  The warmup and full-rank branches are independent of all three
# and stay shared across variants.
SUFFIX=""
[ "$LORA_R" != "128" ] && SUFFIX="${SUFFIX}_r${LORA_R}"
[ "$CYCLE" != "1000" ] && SUFFIX="${SUFFIX}_c${CYCLE}"
[ "$RESTART_WARMUP" != "100" ] && SUFFIX="${SUFFIX}_rw${RESTART_WARMUP}"
[ -n "$OPT_PRUNE" ] && SUFFIX="${SUFFIX}_mag${OPT_PRUNE}"
# The corpus build (tools/build_text_corpus.py) writes <out>.meta.json as
# its final act.  Default is fail-fast: a missing corpus usually means a
# wrong CORPUS path, and silently sleeping 90 minutes on a typo wastes the
# whole queue window.  Launchers that intentionally race a fresh-sandbox
# corpus rebuild opt in with e.g. WAIT_CORPUS_SECS=5400.
WAIT_CORPUS_SECS="${WAIT_CORPUS_SECS:-0}"
waited=0
while [ ! -f "${CORPUS}.meta.json" ] && [ "$waited" -lt "$WAIT_CORPUS_SECS" ]; do
  [ "$waited" -eq 0 ] && echo "waiting for corpus ${CORPUS}.meta.json (up to ${WAIT_CORPUS_SECS}s) ..."
  # periodic progress so a tailed log shows the wait is alive, not hung
  [ "$waited" -gt 0 ] && [ $((waited % 300)) -eq 0 ] && \
    echo "still waiting for corpus ${CORPUS}.meta.json (${waited}/${WAIT_CORPUS_SECS}s) ..."
  sleep 60; waited=$((waited + 60))
done
if [ ! -f "${CORPUS}.meta.json" ]; then
  echo "corpus ${CORPUS} not ready after ${waited}s — aborting" >&2
  exit 3
fi

RKEY="${KEY}${SUFFIX}"
# keyed by RKEY (MODEL/SEED + variant suffix), not SUFFIX alone: runs that
# share a WORK dir across models/seeds must not overwrite each other's
# comparison output
COMPARE_OUT="$WORK/compare_${RKEY}.json"
WARMUP_DIR="$WORK/warmup_$KEY"
FULL_DIR="$WORK/full_rank_$KEY"
RELORA_DIR="$WORK/relora_$RKEY"
mkdir -p "$WORK"

cat > "$WORK/data.yaml" <<EOF
data_path: $CORPUS
split: "95,4,1"
seq_length: $SEQ
seed: $SEED
data_impl: mmap
EOF

common=(--megatron_dataset_config "$WORK/data.yaml" --model_config "$MODEL"
        --batch_size "$BATCH" --total_batch_size "$BATCH" --max_length "$SEQ"
        --dtype bfloat16 --eval_every "$EVAL_EVERY" --eval_tokens_during_training "$EVAL_TOKENS"
        --final_eval_tokens "$FINAL_EVAL_TOKENS"
        --keep_checkpoints 2 --seed "$SEED")

if [ ! -d "$WARMUP_DIR/model_$STEPS_WARMUP" ]; then
  echo "=== stage 1: shared full-rank warmup ($STEPS_WARMUP steps) ==="
  python main.py "${common[@]}" --lr 1e-3 --scheduler cosine \
      --warmup_steps "$LR_WARMUP" --cycle_length "$STEPS_WARMUP" --min_lr_ratio 0.9 \
      --num_training_steps "$STEPS_WARMUP" --save_every "$STEPS_WARMUP" \
      --save_dir "$WARMUP_DIR"
fi

echo "=== stage 2a: full-rank branch (to $STEPS_TOTAL steps) ==="
# warm-started schedules run over the REMAINING steps (trainer.py:242-251)
python main.py "${common[@]}" --lr 1e-3 --scheduler cosine \
    --warmup_steps "$LR_WARMUP" --cycle_length "$((STEPS_TOTAL - STEPS_WARMUP))" \
    --warmed_up_model "$WARMUP_DIR/model_$STEPS_WARMUP" \
    --num_training_steps "$STEPS_TOTAL" --save_every 4000 \
    --save_dir "$FULL_DIR" --autoresume true

echo "=== stage 2b: ReLoRA branch (to $STEPS_TOTAL steps) ==="
if [ -n "$OPT_PRUNE" ]; then
  reset_flags=(--reset_optimizer_on_relora false --optimizer_magnitude_pruning "$OPT_PRUNE")
else
  reset_flags=(--reset_optimizer_on_relora true)
fi
python main.py "${common[@]}" --lr 2e-3 --use_peft true --lora_r "$LORA_R" \
    --relora "$CYCLE" --cycle_length "$CYCLE" --scheduler cosine_restarts \
    --warmup_steps "$LR_WARMUP" --restart_warmup_steps "$RESTART_WARMUP" \
    "${reset_flags[@]}" \
    --warmed_up_model "$WARMUP_DIR/model_$STEPS_WARMUP" \
    --num_training_steps "$STEPS_TOTAL" --save_every 4000 \
    --save_dir "$RELORA_DIR" --autoresume true

echo "=== results ==="
python tools/compare_runs.py full_rank="$FULL_DIR" relora="$RELORA_DIR" \
    --out "$COMPARE_OUT"
