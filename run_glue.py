"""GLUE / text-classification fine-tuning CLI — the reference run_glue.py
equivalent (reference run_glue.py:209-623).

Fine-tunes a (ReLoRA-)pretrained checkpoint on a GLUE task — or any custom
csv/json classification dataset — and reports the task metrics.  Knob parity
with the reference's HfArgumentParser surface: task or custom files, sample
caps for train/eval/predict, padding strategy, do_train/do_eval/do_predict,
label remapping inferred from the training split, regression (stsb), and an
output dir holding ``all_results.json`` + ``predict_results_{task}.txt``.
(The reference forces ``save_strategy="no"`` — GLUE runs don't checkpoint —
so there is deliberately no resume path here either.)

Examples::

    # a GLUE task from the hub (network required)
    python run_glue.py --task_name sst2 --model_config llama_250m \
        --checkpoint ckpts/relora/model_20000 --tokenizer t5-base \
        --batch_size 32 --num_epochs 3 --max_seq_length 128

    # a custom csv (columns: sentence[,sentence2],label) with a local
    # tokenizer.json (air-gapped hosts)
    python run_glue.py --task_name myset --train_file train.csv \
        --validation_file dev.csv --test_file test.csv --do_predict true \
        --model_config llama_35m --checkpoint ckpts/relora/model_8000 \
        --tokenizer /data/corpus.tokenizer.json --output_dir glue_out
"""

from __future__ import annotations

import argparse
import csv
import json
import os


def _flag(x) -> bool:
    return str(x).lower() == "true"


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--task_name", required=True,
                   help="GLUE task, or a name for a custom-file dataset")
    p.add_argument("--train_file", default=None, help="custom csv/json train split")
    p.add_argument("--validation_file", default=None, help="custom csv/json validation split")
    p.add_argument("--test_file", default=None, help="custom csv/json test split (do_predict)")
    p.add_argument("--model_config", required=True)
    p.add_argument("--checkpoint", default=None, help="relora-tpu checkpoint dir (model_N)")
    p.add_argument("--tokenizer", required=True,
                   help="HF tokenizer name/dir, or a local tokenizers-json file")
    # reference HF-Trainer flag names accepted as aliases (run_glue.py parity)
    p.add_argument("--lr", "--learning_rate", type=float, default=2e-5)
    p.add_argument(
        "--batch_size", "--per_device_train_batch_size", type=int, default=32
    )
    p.add_argument("--num_epochs", "--num_train_epochs", type=int, default=3)
    p.add_argument("--max_seq_length", "--max_length", dest="max_seq_length",
                   type=int, default=128)
    p.add_argument("--pad_to_max_length", type=_flag, default=True,
                   help="false = dynamic padding to the batch max (rounded up "
                        "to 32 to bound recompiles)")
    p.add_argument("--weight_decay", type=float, default=0.01)
    p.add_argument("--use_lora", type=_flag, default=False)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max_train_samples", type=int, default=None)
    p.add_argument("--max_eval_samples", type=int, default=None)
    p.add_argument("--max_predict_samples", type=int, default=None)
    p.add_argument("--do_train", type=_flag, default=True)
    p.add_argument("--do_eval", type=_flag, default=True)
    p.add_argument("--do_predict", type=_flag, default=False)
    p.add_argument("--output_dir", default=None)
    p.add_argument("--overwrite_output_dir", type=_flag, default=False)
    return p.parse_args(argv)


def load_tokenizer(name_or_path: str):
    """HF tokenizer by name/dir, or a raw ``tokenizers`` JSON file (the
    air-gapped path — e.g. tools/build_text_corpus.py output)."""
    from transformers import AutoTokenizer, PreTrainedTokenizerFast

    if name_or_path.endswith(".json") and os.path.exists(name_or_path):
        tok = PreTrainedTokenizerFast(tokenizer_file=name_or_path)
        if tok.pad_token_id is None:
            tok.add_special_tokens({"pad_token": "<pad>"})
        return tok
    tok = AutoTokenizer.from_pretrained(name_or_path)
    if tok.pad_token_id is None:
        tok.pad_token = tok.eos_token
    return tok


def read_split(path: str):
    """csv or json-lines split -> list of dicts (parity: data_files loading,
    run_glue.py:342-367)."""
    rows = []
    if path.endswith(".csv"):
        with open(path, newline="") as f:
            rows = list(csv.DictReader(f))
    else:
        with open(path) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    if not rows:
        raise ValueError(f"{path} is empty")
    return rows


def main(argv=None):
    args = parse_args(argv)

    import numpy as np

    from relora_tpu.config.model import load_model_config
    from relora_tpu.eval.glue import GlueConfig, TASK_NUM_LABELS, TASK_TO_KEYS, finetune

    model_cfg = load_model_config(args.model_config)
    tokenizer = load_tokenizer(args.tokenizer)
    is_custom = any(
        f is not None for f in (args.train_file, args.validation_file, args.test_file)
    )
    is_regression = args.task_name == "stsb"

    # ---- load splits ------------------------------------------------------
    if is_custom:
        needed = []
        if args.do_train and not args.train_file:
            needed.append("--train_file (do_train)")
        if args.do_eval and not args.validation_file:
            needed.append("--validation_file (do_eval)")
        if args.do_predict and not args.test_file:
            needed.append("--test_file (do_predict)")
        if needed:
            raise ValueError(
                "custom-file mode is missing required splits: " + ", ".join(needed)
                + " — pass the file or disable the stage"
            )
        raw = {}
        if args.train_file:
            raw["train"] = read_split(args.train_file)
        if args.validation_file:
            raw["validation"] = read_split(args.validation_file)
        if args.test_file:
            raw["test"] = read_split(args.test_file)
        cols = [c for c in raw[next(iter(raw))][0] if c != "label"]
        key1, key2 = cols[0], (cols[1] if len(cols) > 1 else None)
        # infer regression from float-typed labels, the reference's
        # behavior for user datasets (run_glue.py:392-398 checks the label
        # feature dtype).  CSV labels are strings, so "float-typed" means
        # every label parses as a float and at least one is not an integer
        # literal — {"0","1"} stays classification, {"0.0","3.3"} is
        # regression.
        if not is_regression:
            # empty label cells (an unlabeled CSV test split reads as "")
            # are skipped per-row, not allowed to void the inference
            seen = [
                s
                for split in raw.values()
                for r in split
                if (s := str(r.get("label", "")).strip())
            ]

            def _as_float(s: str):
                try:
                    return float(s)
                except ValueError:
                    return None

            vals = [_as_float(s) for s in seen]
            # decimal-literal check (not int(v) comparison: "inf"/"nan"
            # would overflow or false-positive) — {"0","1"} stays
            # classification, {"0.0","3.3","1e-1"} is regression
            is_regression = bool(seen) and all(v is not None for v in vals) and any(
                "." in s or "e" in s.lower() for s in seen
            )
    else:
        import datasets

        hub = datasets.load_dataset("glue", args.task_name)
        eval_split = "validation_matched" if args.task_name == "mnli" else "validation"
        raw = {"train": hub["train"], "validation": hub[eval_split]}
        if args.do_predict:
            raw["test"] = hub["test_matched" if args.task_name == "mnli" else "test"]
        key1, key2 = TASK_TO_KEYS[args.task_name]

    # ---- label remapping (parity: run_glue.py:392-411, 466-470) -----------
    if is_regression:
        num_labels, label2id, id2label = 1, None, None
    elif is_custom:
        # infer the label set from a split that actually carries labels
        # (predict-only runs may load just an unlabeled test file)
        labeled = next(
            (
                raw[name]
                for name in ("train", "validation", "test")
                if raw.get(name) and "label" in raw[name][0]
            ),
            None,
        )
        if labeled is None:
            raise SystemExit(
                "custom task needs at least one split with a 'label' column "
                "to infer the label set (got only unlabeled files)"
            )
        label_list = sorted({str(r["label"]) for r in labeled})
        label2id = {l: i for i, l in enumerate(label_list)}
        id2label = {i: l for l, i in label2id.items()}
        num_labels = len(label_list)
    else:
        num_labels, label2id = TASK_NUM_LABELS[args.task_name], None
        # hub tasks: predictions are written as label NAMES (parity:
        # label_list[item], run_glue.py:601-614)
        feat = raw["train"].features["label"]
        names = getattr(feat, "names", None)
        id2label = dict(enumerate(names)) if names else None

    # ---- tokenize ---------------------------------------------------------
    def encode(split, limit=None, with_labels=True):
        rows = raw[split]
        if limit is not None:
            rows = rows[: min(limit, len(rows))] if is_custom else rows.select(
                range(min(limit, len(rows)))
            )
        texts1 = [r[key1] for r in rows] if is_custom else rows[key1]
        pair = ([r[key2] for r in rows] if is_custom else rows[key2]) if key2 else None
        enc = tokenizer(
            texts1, pair,
            truncation=True,
            max_length=args.max_seq_length,
            padding="max_length" if args.pad_to_max_length else "longest",
        )
        ids = np.asarray(enc["input_ids"], dtype=np.int32)
        if not with_labels:
            return ids, None
        rl = [r["label"] for r in rows] if is_custom else rows["label"]
        if is_regression:
            labels = np.asarray(rl, dtype=np.float32)
        elif label2id is not None:
            labels = np.asarray([label2id[str(l)] for l in rl])
        else:
            labels = np.asarray(rl)
        return ids, labels

    bs = args.batch_size

    def pad_bucket(batch_ids):
        """Dynamic padding: trim to the longest row, rounded up to 32 so the
        jitted step sees a handful of shapes, not one per batch."""
        if args.pad_to_max_length:
            return batch_ids
        pad_id = tokenizer.pad_token_id or 0
        lengths = (batch_ids != pad_id).sum(axis=1)
        width = min(args.max_seq_length, max(32, int(-(-lengths.max() // 32) * 32)))
        return batch_ids[:, :width]

    train_ids, train_labels = (None, None)
    steps_per_epoch = 1
    if args.do_train:
        train_ids, train_labels = encode("train", args.max_train_samples)
        steps_per_epoch = max(1, len(train_ids) // bs)

    eval_ids, eval_labels = (None, None)
    if args.do_eval:
        eval_ids, eval_labels = encode("validation", args.max_eval_samples)

    epoch_counter = iter(range(10**9))

    def train_batches():
        # fresh shuffle each epoch (finetune() calls this once per epoch;
        # HF-Trainer parity — a fixed seed would replay epoch 1's order)
        rs = np.random.RandomState(args.seed + next(epoch_counter))
        order = rs.permutation(len(train_ids))
        for i in range(steps_per_epoch):
            sel = order[i * bs : (i + 1) * bs]
            yield pad_bucket(train_ids[sel]), train_labels[sel]

    def eval_batches():
        for i in range(0, len(eval_ids), bs):
            sel = slice(i, min(i + bs, len(eval_ids)))
            yield pad_bucket(eval_ids[sel]), eval_labels[sel]

    predict_batches = None
    if args.do_predict:
        test_ids, _ = encode("test", args.max_predict_samples, with_labels=False)

        def predict_batches():
            for i in range(0, len(test_ids), bs):
                yield pad_bucket(test_ids[i : i + bs])

    # fail on a dirty output dir BEFORE the (possibly hours-long) finetune
    # (parity: HF TrainingArguments errors at startup)
    if args.output_dir and os.path.isdir(args.output_dir) and os.listdir(args.output_dir):
        if not args.overwrite_output_dir:
            raise ValueError(
                f"output_dir {args.output_dir} exists and is not empty "
                "(use --overwrite_output_dir true)"
            )

    # ---- checkpoint backbone (merge LoRA first if present) ----------------
    pretrained = None
    if args.checkpoint:
        from relora_tpu.core.relora import merged_params
        from relora_tpu.train.checkpoint import load_lora_spec, restore_params_host

        pretrained = restore_params_host(args.checkpoint)
        spec = load_lora_spec(args.checkpoint)
        if spec is not None:
            # an unmerged ReLoRA checkpoint: fold A@B*scale into the base so
            # the classifier starts from the equivalent full-rank model
            pretrained = merged_params(pretrained, spec)

    gcfg = GlueConfig(
        task=args.task_name,
        lr=args.lr,
        batch_size=bs,
        num_epochs=args.num_epochs,
        max_length=args.max_seq_length,
        weight_decay=args.weight_decay,
        use_lora=args.use_lora,
        seed=args.seed,
        num_labels=num_labels,
    )
    metrics, predictions = finetune(
        model_cfg,
        gcfg,
        train_batches,
        eval_batches,
        steps_per_epoch,
        pad_token_id=tokenizer.pad_token_id or 0,
        pretrained_backbone=pretrained,
        predict_batches=predict_batches,
        do_train=args.do_train,
        do_eval=args.do_eval,
    )

    # parity: HF Trainer prefixes evaluation metrics with eval_ in
    # all_results.json (trainer.evaluate -> eval_accuracy etc.)
    result = {"task": args.task_name}
    for k, v in metrics.items():
        result[k if k.startswith(("eval_", "train_")) else f"eval_{k}"] = v
    print(json.dumps(result))
    if args.output_dir:
        os.makedirs(args.output_dir, exist_ok=True)
        with open(os.path.join(args.output_dir, "all_results.json"), "w") as f:
            json.dump(result, f, indent=2)
        if predictions is not None:
            # parity: predict_results_{task}.txt, run_glue.py:601-614
            out = os.path.join(args.output_dir, f"predict_results_{args.task_name}.txt")
            with open(out, "w") as f:
                f.write("index\tprediction\n")
                for i, pred in enumerate(predictions):
                    if is_regression:
                        f.write(f"{i}\t{float(pred):.3f}\n")
                    else:
                        label = id2label[int(pred)] if id2label else int(pred)
                        f.write(f"{i}\t{label}\n")
    return result


if __name__ == "__main__":
    main()
