"""Pre-rank the MFU levers OFFLINE (no chip needed) via lowered-HLO analysis.

Chip time should execute a pre-sorted top-2 list instead of a sweep.  This tool
traces + lowers the EXACT train-step program benchlib would run for each
candidate config (same model/step construction — reuses benchlib's builder
via jax.eval_shape-free lowering) and extracts, per config:

- ``dots``     — number of dot_general ops in the lowered (pre-XLA-fusion)
  module: the remat recompute tax shows up here, because jax.checkpoint
  duplicates the forward dots it re-materializes in the backward.
- ``dot_gflops`` — analytic FLOPs summed over every dot_general's shapes
  (parsed from the StableHLO text), i.e. what the MXU must actually
  execute per micro-batch step — recompute included.
- ``bytes_hbm``  — total parameter + activation operand footprint proxy.

Ranking metric: dot_gflops relative to the measured round-2 baseline
config (remat=full); assuming the step stays MXU-bound (26.7% MFU with a
~33% recompute tax supports this), predicted step-time scales ~linearly
with executed dot FLOPs.

    python scripts/rank_levers.py --model llama_1b --out bench_results/r4_lever_rank.json

Writes a ranking table (JSON) and prints a markdown table.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# offline analysis tool: always CPU (lowering needs no chip)
os.environ["JAX_PLATFORMS"] = "cpu"

CONFIGS = [
    # label, kwargs for the step builder — the measurable set: every entry
    # here fits a 16 GB v5e per tools/plan_memory (the dots-family needs
    # small micro-batches; per-token FLOPs are mb-invariant so the ranking
    # transfers, but MXU efficiency at small mb is what the on-chip sweep
    # actually settles)
    ("remat full (r2 baseline)", dict(remat=True, remat_policy="full")),
    ("remat dots_narrow chunked mb8", dict(remat=True, remat_policy="dots_narrow", loss_impl="chunked", micro_batch=8)),
    ("remat dots chunked mb4", dict(remat=True, remat_policy="dots", loss_impl="chunked", micro_batch=4)),
    ("remat dots chunked mb2", dict(remat=True, remat_policy="dots", loss_impl="chunked", micro_batch=2)),
    ("remat dots_all chunked mb2", dict(remat=True, remat_policy="dots_all", loss_impl="chunked", micro_batch=2)),
    ("remat full chunked mb32", dict(remat=True, loss_impl="chunked", micro_batch=32)),
    ("remat full chunked mb16", dict(remat=True, loss_impl="chunked", micro_batch=16)),
    ("remat full dropout0", dict(remat=True, dropout=0.0)),
    ("remat full bf16-logits", dict(remat=True, logits_dtype="bf16")),
]


def lower_step(model_name: str, *, layers: int, micro_batch=8, seq=1024,
               remat=True, remat_policy="full", loss_impl="dense",
               vocab_chunk=8192, logits_dtype="f32", dropout=0.1, rank=128):
    """Build the same train step benchlib benches — but UNROLLED at a reduced
    layer count — and lower it (no compile).

    scan_layers=False on purpose: a scanned body appears once in the lowered
    text but executes num_layers times, which would make text-level FLOP
    counting blind to the per-layer remat structure.  Unrolled at 2 and 4
    layers, the per-layer cost falls out as a linear difference and
    extrapolates exactly to full depth (every layer is identical).
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from relora_tpu.config.model import MODEL_ZOO
    from relora_tpu.core.optim import build_optimizer
    from relora_tpu.core.partition import partition
    from relora_tpu.core.relora import LoraSpec, trainable_param_mask
    from relora_tpu.models.llama import LlamaForCausalLM
    from relora_tpu.models.params_util import init_params
    from relora_tpu.train.state import TrainState
    from relora_tpu.train.step import make_train_step

    cfg = dataclasses.replace(MODEL_ZOO[model_name], num_hidden_layers=layers)
    spec = LoraSpec(r=rank, alpha=32, dropout=dropout)
    model = LlamaForCausalLM(
        cfg,
        lora=spec,
        dtype=jnp.bfloat16,
        scan_layers=False,
        remat=remat,
        remat_policy=remat_policy,
        logits_dtype=jnp.bfloat16 if logits_dtype == "bf16" else jnp.float32,
    )
    sample = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(lambda k: init_params(model, k, sample), jax.random.PRNGKey(0))
    mask = trainable_param_mask(params)
    tx = build_optimizer(schedule=lambda s: 1e-3)
    opt_state = jax.eval_shape(tx.init, partition(params, mask)[0])
    state = jax.eval_shape(lambda p, o: TrainState.create(p, o), params, opt_state)
    step = make_train_step(model, tx, mask, loss_impl=loss_impl, vocab_chunk=vocab_chunk)
    batch = jax.ShapeDtypeStruct((1, micro_batch, seq), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    lowered = jax.jit(step, donate_argnums=0).lower(state, batch, rng)
    return lowered, cfg


_DOT_RE = re.compile(
    r"stablehlo\.dot_general.*?:\s*\(tensor<([^>]+)>,\s*tensor<([^>]+)>\)\s*->\s*tensor<([^>]+)>"
)
_DIMS_RE = re.compile(
    r"contracting_dims\s*=\s*\[([\d, ]*)\]\s*x\s*\[([\d, ]*)\]"
)


def _shape(t: str):
    parts = t.split("x")
    dims = [int(p) for p in parts[:-1]]
    return dims, parts[-1]


def analyze(hlo_text: str) -> dict:
    """Count dot_generals and sum their FLOPs from the StableHLO text.

    The contracting-dims attribute is parsed from the pretty-printed
    StableHLO line; that format is jax-version-sensitive (the generic form
    prints ``#stablehlo.dot<lhs_contracting_dimensions=...>``).  A parse
    miss silently defaulting K to 1 would undercount matmul FLOPs
    massively and skew the lever ranking, so the analysis fails loudly if
    any dot_general line lacks a parseable contracting-dims attribute (an
    empty-list match is a legal outer product, priced k=1, not a miss)."""
    n = 0
    flops = 0.0
    unparsed = 0
    for m in _DOT_RE.finditer(hlo_text):
        lhs, _rhs, out = _shape(m.group(1))[0], _shape(m.group(2))[0], _shape(m.group(3))
        out_dims, _ = out
        # find the contracting dims on the same line for the K factor
        line = m.group(0)
        dm = _DIMS_RE.search(line)
        if dm:
            # an empty matched list is a legal zero-contracting-dim dot
            # (outer product): K=1 is exactly right, not a parse miss
            k = 1
            for idx in (int(x) for x in dm.group(1).split(",") if x.strip()):
                k *= lhs[idx]
        else:
            k = 1
            unparsed += 1
        size_out = 1
        for d in out_dims:
            size_out *= d
        n += 1
        flops += 2.0 * size_out * k
    if unparsed:
        raise RuntimeError(
            f"{unparsed}/{n} dot_general lines had no parseable "
            "contracting_dims (StableHLO print format changed?) — FLOP "
            "counts would be bogus; update _DIMS_RE for this jax version"
        )
    return {"dots": n, "dot_gflops": round(flops / 1e9, 2)}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama_1b")
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--out", default="bench_results/r4_lever_rank.json")
    p.add_argument("--base-tok-s", type=float, default=6884.5,
                   help="measured tok/s of the baseline config (r2 on-chip)")
    p.add_argument("--base-mfu", type=float, default=0.267)
    args = p.parse_args(argv)

    from relora_tpu.config.model import MODEL_ZOO

    full_depth = MODEL_ZOO[args.model].num_hidden_layers
    rows = []
    base = None
    for label, kw in CONFIGS:
        kw = dict(kw)  # don't mutate the module-level config table
        mb = kw.pop("micro_batch", 8)
        per_depth = {}
        for L in (2, 4):
            lowered, _cfg = lower_step(
                args.model, layers=L, micro_batch=mb, seq=args.seq, **kw
            )
            per_depth[L] = analyze(lowered.as_text())
            del lowered
        # linear depth model: cost(L) = fixed (embed/head/loss) + L*per_layer
        per_layer = (per_depth[4]["dot_gflops"] - per_depth[2]["dot_gflops"]) / 2
        fixed = per_depth[2]["dot_gflops"] - 2 * per_layer
        gflops_full = fixed + full_depth * per_layer
        dots_per_layer = (per_depth[4]["dots"] - per_depth[2]["dots"]) // 2
        stats = {
            "dots_per_layer": dots_per_layer,
            "dot_gflops_fixed": round(fixed, 2),
            "dot_gflops_per_layer": round(per_layer, 2),
            "dot_gflops": round(gflops_full, 2),
        }
        # per-token dot FLOPs: mb scales both tokens and FLOPs, so normalize
        stats["dot_gflops_per_token"] = round(gflops_full / (mb * args.seq), 4)
        row = {"label": label, "micro_batch": mb, **stats}
        rows.append(row)
        if base is None:
            base = row
        print(f"lowered {label}: {stats}", flush=True)

    for row in rows:
        ratio = row["dot_gflops_per_token"] / base["dot_gflops_per_token"]
        row["dot_flops_vs_base"] = round(ratio, 4)
        # MXU-bound prediction: step time ~ executed dot FLOPs
        row["predicted_tok_s"] = round(args.base_tok_s / ratio, 1)
        row["predicted_mfu"] = round(args.base_mfu / ratio, 4)

    rows.sort(key=lambda r: r["predicted_mfu"], reverse=True)
    out = {
        "model": args.model,
        "seq": args.seq,
        "method": "lowered-StableHLO dot_general FLOP count (pre-XLA-fusion); "
                  "prediction assumes the step is MXU-bound at the r2 baseline's "
                  "measured 6884.5 tok/s (26.7% MFU)",
        "rows": rows,
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)

    print("\n| config | mb | dots/layer | dot GF/token | vs base | predicted tok/s | predicted MFU |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['label']} | {r['micro_batch']} | {r['dots_per_layer']} | "
            f"{r['dot_gflops_per_token']} | {r['dot_flops_vs_base']}x | "
            f"{r['predicted_tok_s']} | {r['predicted_mfu']*100:.1f}% |"
        )
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
