"""Training-run visualization from metrics.jsonl (the wandb-dashboard view,
offline).  Three modes, covering the reference's plotting notebooks:

``curves`` (default — notebook 07_plotting): loss/LR/throughput curves for
one or more runs with merge/reset markers and optional smoothing::

    python tools/plot_metrics.py ckpts/relora [more_run_dirs...] --out curves.png
    python tools/plot_metrics.py curves ckpts/relora ckpts/full --ema 0.98

``scaling`` (notebook 03_scaling_laws_plotting): final loss vs trainable
params — or vs training compute C=6·N·D with ``--x compute`` — log-log per
run group, with a least-squares power-law fit ``loss = a * x^b`` per group
(full-rank vs ReLoRA, split on use_peft from each run's run_config.json).
Inputs are run dirs, or ``metrics.jsonl:model_config:group`` triplets for
committed sweep artifacts that carry no run_config.json; ``--fit-out``
writes the fits as JSON::

    python tools/plot_metrics.py scaling ckpts/run_* --out scaling.png
    python tools/plot_metrics.py scaling \
        bench_results/r3_loss_parity_cpu_metrics/full_rank.jsonl:llama_9m:full_rank \
        ... --x compute --fit-out scaling_fit.json

``lr`` (notebook 04_plot_lr): preview any supported schedule's LR curve
without running anything — the schedules are the real ones from
core/schedules.py, not a re-derivation::

    python tools/plot_metrics.py lr --scheduler cosine_restarts --lr 2e-3 \
        --num-training-steps 8000 --warmup-steps 250 --cycle-length 1000 \
        --restart-warmup-steps 100 --out lr.png
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODES = ("curves", "scaling", "lr")


def load_metrics(run_dir: str):
    path = os.path.join(run_dir, "metrics.jsonl")
    rows = [json.loads(l) for l in open(path)]
    return [r for r in rows if "loss" in r and "update_step" in r]


def load_run_config(run_dir: str) -> dict:
    path = os.path.join(run_dir, "run_config.json")
    if os.path.exists(path):
        return json.load(open(path))
    return {}


def _mpl():
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    return plt


def cmd_curves(argv) -> None:
    p = argparse.ArgumentParser(prog="plot_metrics.py curves")
    p.add_argument("run_dirs", nargs="+")
    p.add_argument("--out", default="curves.png")
    p.add_argument("--ema", type=float, default=0.0, help="EMA smoothing factor (0 = off)")
    args = p.parse_args(argv)
    plt = _mpl()

    fig, axes = plt.subplots(1, 3, figsize=(15, 4))
    for run_dir in args.run_dirs:
        rows = load_metrics(run_dir)
        if not rows:
            print(f"no metrics in {run_dir}")
            continue
        name = os.path.basename(os.path.normpath(run_dir))
        steps = [r["update_step"] for r in rows]
        loss = [r["loss"] for r in rows]
        if args.ema > 0:
            sm, out = None, []
            for v in loss:
                sm = v if sm is None else args.ema * sm + (1 - args.ema) * v
                out.append(sm)
            loss = out
        axes[0].plot(steps, loss, label=name)
        axes[1].plot(steps, [r.get("lr", 0) for r in rows], label=name)
        axes[2].plot(steps, [r.get("throughput_tokens", 0) for r in rows], label=name)
        # merge markers: steps where n_lora_restarts increments
        prev = 0
        for r in rows:
            n = r.get("n_lora_restarts", 0)
            if n > prev:
                axes[0].axvline(r["update_step"], color="gray", alpha=0.4, linestyle="--")
                prev = n

    for ax, title, ylab in zip(
        axes,
        ("loss (merges dashed)", "learning rate", "throughput"),
        ("loss", "lr", "tokens/s"),
    ):
        ax.set_title(title)
        ax.set_xlabel("update step")
        ax.set_ylabel(ylab)
        ax.legend(fontsize=8)
    fig.tight_layout()
    fig.savefig(args.out, dpi=120)
    print(f"wrote {args.out}")


def fit_power_law(xs, ys):
    """Least-squares fit of loss = a * x^b in log-log space (no scipy in the
    image; for positive data this is the standard linearization)."""
    lx = [math.log(x) for x in xs]
    ly = [math.log(y) for y in ys]
    n = len(lx)
    mx, my = sum(lx) / n, sum(ly) / n
    sxx = sum((v - mx) ** 2 for v in lx)
    if sxx == 0:
        return math.exp(my), 0.0
    b = sum((u - mx) * (v - my) for u, v in zip(lx, ly)) / sxx
    a = math.exp(my - b * mx)
    return a, b


def final_eval_loss(rows) -> float:
    """The run's final_eval_loss if recorded, else the last eval_loss, else
    the mean of the last 20 train losses."""
    finals = [r for r in rows if r.get("final_eval_loss") is not None]
    if finals:
        return float(finals[-1]["final_eval_loss"])
    evals = [r for r in rows if r.get("eval_loss") is not None]
    if evals:
        return float(evals[-1]["eval_loss"])
    tail = [r["loss"] for r in rows if "loss" in r][-20:]
    return float(sum(tail) / len(tail))


def _zoo_param_count_m(model_name: str) -> float:
    """Exact full-rank parameter count for a MODEL_ZOO entry, in millions.

    Shape-only (jax.eval_shape) — no weights are materialized, so this is
    cheap even for the 1B/7B entries.  Used for metrics files recorded
    without a run_config.json sidecar (e.g. the committed loss-parity
    sweeps): compute-axis scaling needs N, and the 6·N·D FLOP estimate uses
    the same total-N for full-rank and ReLoRA runs (frozen weights still
    do forward+backward work)."""
    import jax
    import jax.numpy as jnp

    from relora_tpu.config.model import MODEL_ZOO
    from relora_tpu.models import LlamaForCausalLM
    from relora_tpu.models.pythia import GPTNeoXForCausalLM

    mc = MODEL_ZOO[model_name]
    cls = GPTNeoXForCausalLM if mc.family == "neox" else LlamaForCausalLM
    model = cls(config=mc, scan_layers=False)
    shapes = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    return sum(
        math.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes)
    ) / 1e6


def _parse_scaling_entry(entry: str):
    """A scaling input is a run dir, or ``metrics.jsonl:model_config:group``
    for bare metrics files (committed sweep artifacts carry no
    run_config.json).  Returns (rows, trainable_M, total_M, group, label)
    or None when the entry lacks what the fit needs."""
    if ":" in entry and entry.split(":", 1)[0].endswith(".jsonl"):
        parts = entry.split(":")
        if len(parts) != 3:
            print(f"skipping {entry}: expected metrics.jsonl:model_config:group")
            return None
        path, model_name, group = parts
        rows = [json.loads(l) for l in open(path)]
        rows = [
            r for r in rows
            if ("loss" in r and "update_step" in r)
            or r.get("final_eval_loss") is not None
        ]
        if not rows:
            print(f"skipping {entry}: no usable loss rows")
            return None
        try:
            n = _zoo_param_count_m(model_name)
        except KeyError:
            print(f"skipping {entry}: unknown model config {model_name!r}")
            return None
        # bare files carry no LoRA breakdown: N is the base model count
        # (exact for full-rank; for ReLoRA entries use --x compute, where
        # base-N is the right N anyway)
        return rows, n, n, group, path
    rows = load_metrics(entry)
    cfg = load_run_config(entry)
    if not rows or "trainable_params" not in cfg:
        print(f"skipping {entry}: missing metrics or run_config.json trainable_params")
        return None
    group = "relora" if cfg.get("use_peft") else "full_rank"
    # run_config.json stores param counts already in millions
    # (trainer.py writes counts / 1e6), matching the axis label and the
    # printed params_M fit — no further scaling.  Compute-axis N is
    # equivalent_params (base model, LoRA folded out) so run dirs and bare
    # triplets put identical compute at identical x.
    total_m = float(cfg.get("equivalent_params") or cfg["total_params"])
    return rows, float(cfg["trainable_params"]), total_m, group, entry


def _final_tokens(rows) -> float:
    toks = [r["tokens_seen"] for r in rows if r.get("tokens_seen")]
    return float(toks[-1]) if toks else 0.0


def cmd_scaling(argv) -> None:
    p = argparse.ArgumentParser(prog="plot_metrics.py scaling")
    p.add_argument("run_dirs", nargs="+",
                   help="run dirs, or metrics.jsonl:model_config:group triplets")
    p.add_argument("--out", default="scaling.png")
    p.add_argument("--x", choices=("params", "compute"), default="params",
                   help="x axis: trainable params (M) or training compute "
                        "C = 6*N*D FLOPs (notebook 03's loss-vs-compute view)")
    p.add_argument("--fit-out", default=None,
                   help="write the per-group power-law fits as JSON")
    args = p.parse_args(argv)
    plt = _mpl()

    groups: dict = {}
    for entry in args.run_dirs:
        parsed = _parse_scaling_entry(entry)
        if parsed is None:  # reason already printed by the parser
            continue
        rows, trainable_m, total_m, group, label = parsed
        if args.x == "compute":
            d = _final_tokens(rows)
            if d == 0:
                print(f"skipping {label}: no tokens_seen recorded")
                continue
            x = 6.0 * total_m * 1e6 * d  # FLOPs
        else:
            x = trainable_m
        groups.setdefault(group, []).append((x, final_eval_loss(rows), label))

    xname = "compute C=6·N·D (FLOPs)" if args.x == "compute" else "params_M"
    fits = {}
    fig, ax = plt.subplots(figsize=(5.5, 5.5))
    for group, pts in sorted(groups.items()):
        xs = [p[0] for p in pts]
        ys = [p[1] for p in pts]
        ax.scatter(xs, ys, label=group)
        if len(pts) >= 2:
            a, b = fit_power_law(xs, ys)
            grid = [min(xs) * (max(xs) / min(xs)) ** (i / 99) for i in range(100)]
            ax.plot(grid, [a * x**b for x in grid], linestyle="--", alpha=0.7,
                    label=f"{group}: {a:.2f}·x^{b:.3f}")
            print(f"{group}: loss = {a:.4g} * x^{b:.4f}  (x = {xname}, {len(pts)} runs)")
            fits[group] = {
                "a": a,
                "b": b,
                "x_axis": args.x,
                "points": [
                    {"x": x, "loss": y, "run": lbl} for x, y, lbl in pts
                ],
            }
    ax.set_xscale("log")
    ax.set_yscale("log")
    ax.set_xlabel("Training compute (FLOPs)" if args.x == "compute"
                  else "Trainable parameters (M)")
    ax.set_ylabel("Loss")
    ax.set_title(f"Scaling: loss vs {'compute' if args.x == 'compute' else 'trainable params'}")
    ax.legend(fontsize=8)
    fig.tight_layout()
    fig.savefig(args.out, dpi=150)
    print(f"wrote {args.out}")
    if args.fit_out:
        with open(args.fit_out, "w") as f:
            json.dump({"model": "loss = a * x^b", "fits": fits}, f, indent=2)
        print(f"wrote {args.fit_out}")


def cmd_lr(argv) -> None:
    p = argparse.ArgumentParser(prog="plot_metrics.py lr")
    p.add_argument("--scheduler", default="cosine_restarts")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--num-training-steps", type=int, default=8000)
    p.add_argument("--warmup-steps", type=int, default=250)
    p.add_argument("--min-lr-ratio", type=float, default=0.1)
    p.add_argument("--cycle-length", type=int, default=1000)
    p.add_argument("--restart-warmup-steps", type=int, default=100)
    p.add_argument("--adjust-step", type=int, default=0)
    p.add_argument("--out", default="lr.png")
    args = p.parse_args(argv)

    # analysis-only tool: always CPU (evaluating a schedule needs no chip)
    os.environ["JAX_PLATFORMS"] = "cpu"
    from relora_tpu.core.schedules import make_schedule

    sched = make_schedule(
        args.scheduler,
        lr=args.lr,
        num_training_steps=args.num_training_steps,
        warmup_steps=args.warmup_steps,
        min_lr_ratio=args.min_lr_ratio,
        cycle_length=args.cycle_length,
        restart_warmup_steps=args.restart_warmup_steps,
        adjust_step=args.adjust_step,
    )
    steps = list(range(args.num_training_steps))
    values = [float(sched(s)) for s in steps]
    plt = _mpl()
    fig, ax = plt.subplots(figsize=(8, 4))
    ax.plot(steps, values)
    ax.set_xlabel("update step")
    ax.set_ylabel("learning rate")
    ax.set_title(f"{args.scheduler} lr={args.lr}")
    fig.tight_layout()
    fig.savefig(args.out, dpi=120)
    print(f"wrote {args.out}")


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    mode = "curves"
    if argv and argv[0] in MODES:
        mode = argv.pop(0)
    {"curves": cmd_curves, "scaling": cmd_scaling, "lr": cmd_lr}[mode](argv)


if __name__ == "__main__":
    main()
