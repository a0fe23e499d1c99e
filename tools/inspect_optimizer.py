"""Optimizer-state inspection (parity: notebook 13_zero_optimizer_resets +
training_utils.print_optimizer_state_size :367-388).

Reports, per checkpoint: the number of floats in the Adam first/second
moments, the fraction currently zero (the reset signature), and a breakdown
of LoRA vs other trainables.

Usage::

    python tools/inspect_optimizer.py ckpts/relora/model_16000
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("checkpoint", help="checkpoint dir (model_N)")
    args = p.parse_args(argv)

    sys.path.insert(0, ".")
    import jax

    # offline tool: host CPU is all we need, and it must not hold a chip
    jax.config.update("jax_platforms", "cpu")
    from relora_tpu.train.checkpoint import restore_state_host

    state = restore_state_host(args.checkpoint)

    opt_state = state["opt_state"]

    def walk(node, path=""):
        if isinstance(node, dict):
            for k, v in node.items():
                yield from walk(v, f"{path}/{k}")
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                yield from walk(v, f"{path}[{i}]")
        elif isinstance(node, np.ndarray):
            yield path, node

    moments = {"mu": [], "nu": []}
    for path, arr in walk(opt_state):
        for m in moments:
            if f"/{m}/" in path or path.endswith(f"/{m}"):
                moments[m].append((path, arr))

    for m, entries in moments.items():
        total = sum(a.size for _, a in entries)
        zeros = sum(int((a == 0).sum()) for _, a in entries)
        lora = sum(a.size for p, a in entries if "/lora_" in p)
        name = {"mu": "first moment", "nu": "second moment"}[m]
        print(
            f"{name}: {total/1e6:.2f}M floats "
            f"({lora/1e6:.2f}M in LoRA factors), {zeros/max(total,1)*100:.2f}% zero"
        )
    step = state.get("step")
    n_skipped = state.get("n_skipped")
    print(f"update_step={step} n_skipped={n_skipped}")


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        # stdout piped into `head` that already exited (smoke_test.sh does
        # this); the truncated output is what the reader asked for
        import os

        os.dup2(os.open(os.devnull, os.O_WRONLY), 1)
