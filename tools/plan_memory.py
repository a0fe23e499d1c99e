"""Per-device HBM budget planner for a model/mesh/recipe combination.

Answers "does this fit?" before burning pod time — entirely via
``jax.eval_shape`` (abstract shapes, zero allocation), so 7B-scale plans run
on a laptop.  Accounts for:

- frozen base params (bf16/f32, int8 or NF4+double-quant footprints),
- LoRA factors + their Adam moments (the only optimizer state ReLoRA keeps),
- full-rank Adam moments when --rank 0 (the comparison case),
- gradients for trainables,
- activation residuals at the chosen microbatch/seq under the remat policy
  ('full' keeps per-layer boundaries; 'dots' adds the saved matmul outputs;
  'none' estimates the dense residuals incl. the S^2 attention scores XLA
  keeps for backward — seen once on a chip at llama_1b, before PR 1),
- the logits buffer (or its absence with --loss chunked).

Sharding: each param leaf divides by the product of mesh axes its logical
spec maps to (parallel/mesh.LOGICAL_RULES); activations divide by
data*fsdp (batch) and sequence (seq axis).

    python tools/plan_memory.py --model llama_7b --rank 256 --mesh fsdp=32,tensor=2 \
        --micro-batch 8 --seq 2048 --chip v5p
    python tools/plan_memory.py --model llama_1b --rank 128 --micro-batch 8 --seq 1024

``plan()`` is importable (tools/dryrun_at_shape.py asserts live sharded-array
sizes against it at real hidden/vocab dims).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CHIP_HBM = {"v5e": 16e9, "v5p": 95e9, "v4": 32e9}


def parse_mesh(mesh: str) -> dict:
    factors = {}
    if mesh:
        for part in mesh.split(","):
            k, v = part.split("=")
            factors[k.strip()] = int(v)
    return factors


def plan(
    model: str,
    *,
    rank: int = 128,
    mesh: str = "",
    micro_batch: int = 8,
    seq: int = 1024,
    dtype: str = "bf16",
    quantize=None,
    base_dtype=None,
    remat: str = "full",
    loss: str = "dense",
    chip: str = "v5e",
    layers: int = 0,
) -> dict:
    """Analytic per-device memory plan.  ``layers`` > 0 overrides the model's
    layer count (used by dryrun_at_shape to compare against a reduced-depth
    live run at real hidden/vocab dims).  Caller is responsible for the JAX
    platform (this only uses eval_shape — no device memory is touched)."""
    import jax
    import jax.numpy as jnp

    from relora_tpu.config.model import MODEL_ZOO, load_model_config
    from relora_tpu.core.relora import LoraSpec, frozen_param_mask
    from relora_tpu.models.llama import LlamaForCausalLM
    from relora_tpu.models.params_util import logical_partition_specs
    from relora_tpu.parallel.mesh import LOGICAL_RULES

    mesh_factors = parse_mesh(mesh)
    n_devices = math.prod(mesh_factors.values()) if mesh_factors else 1
    rules = dict(LOGICAL_RULES)

    def shard_div(logical_spec) -> int:
        """How many ways this leaf is split across the mesh."""
        div = 1
        for axis_name in logical_spec or ():
            mesh_axes = rules.get(axis_name)
            if mesh_axes is None:
                continue
            if isinstance(mesh_axes, str):
                mesh_axes = (mesh_axes,)
            for m in mesh_axes:
                div *= mesh_factors.get(m, 1)
        return div

    cfg = MODEL_ZOO[model] if model in MODEL_ZOO else load_model_config(model)
    if layers:
        cfg = dataclasses.replace(cfg, num_hidden_layers=layers)
    # build WITH quantize so the abstract tree carries the real quantized
    # leaves (codes / scales, incl. the odd-width int8 fallback): frozen
    # bytes are then computed exactly from leaf shapes+dtypes instead of an
    # approximate per-element factor model
    spec = (
        LoraSpec(r=rank, alpha=32, dropout=0.0, quantize=quantize, base_dtype=base_dtype)
        if rank
        else None
    )
    jdtype = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    mdl = LlamaForCausalLM(cfg, lora=spec, dtype=jdtype, scan_layers=True)
    sample = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(lambda: mdl.init(jax.random.PRNGKey(0), sample))["params"]
    specs = logical_partition_specs(mdl, sample)

    import flax.linen as nn

    abstract = nn.meta.unbox(abstract)

    # the REAL trainability rule (core/relora.py::trainable_param_mask):
    # everything trains except the frozen base kernels of LoRA-wrapped
    # Denses — embeddings/norms/head carry Adam state too, and only those
    # frozen kernels are ever quantized (ops/quant.py)
    frozen_mask = frozen_param_mask(abstract) if rank else None

    # --- params + optimizer + grads -----------------------------------
    frozen_bytes = trainable_bytes = opt_bytes = grad_bytes = 0.0
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    flat_specs = {
        tuple(str(getattr(k, "key", k)) for k in path): s
        for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]
    }
    flat_frozen = (
        {
            tuple(str(getattr(k, "key", k)) for k in path): f
            for path, f in jax.tree_util.tree_flatten_with_path(frozen_mask)[0]
        }
        if frozen_mask is not None
        else {}
    )
    for path, leaf in flat:
        key = tuple(str(getattr(k, "key", k)) for k in path)
        div = shard_div(flat_specs.get(key))
        n = leaf.size / div
        trainable = not flat_frozen.get(key, False) if rank else True
        # param storage dtype: params are stored f32 (master); the frozen
        # base's leaves are whatever the model actually declares (f32
        # kernels, or int8/nf4 codes + scales when quantize is set — the
        # abstract tree was built with the real quantize mode, so
        # size × itemsize is exact, replication of small scale leaves
        # included via their own sharding specs)
        if trainable:
            trainable_bytes += n * 4
            opt_bytes += n * 4 * 2  # adam mu+nu f32
            grad_bytes += n * 4
        else:
            frozen_bytes += n * leaf.dtype.itemsize
    # --- activations ---------------------------------------------------
    B, S, H, L = micro_batch, seq, cfg.hidden_size, cfg.num_hidden_layers
    batch_div = mesh_factors.get("data", 1) * mesh_factors.get("fsdp", 1)
    seq_div = mesh_factors.get("sequence", 1)
    bytes_el = 2 if dtype == "bf16" else 4
    tok = (B / batch_div) * (S / seq_div)
    heads = cfg.num_attention_heads / mesh_factors.get("tensor", 1)
    # Per-layer dot outputs saved by the 'dots' family of remat policies:
    # hidden-width — q, k, v, attn out-proj, mlp down-proj (its OUTPUT is
    # H-wide even though its input is inter-wide) plus the layer-boundary
    # residual = 6×H; inter-width — mlp gate and up projections = 2×inter.
    # 'dots_narrow' recomputes exactly those 2 inter-width dots
    # (params_util.remat_policy 'dots_narrow'), so both policies must share
    # one inter count for the predicted dots→dots_narrow saving
    # (2 × inter × tok × bytes_el per layer) to match the policy's true
    # delta.  (Earlier accounting charged dots 3×inter / dots_narrow 5×H,
    # which overstated the saving by inter−H per token per layer.)
    n_hidden_dots, n_inter_dots = 6, 2
    if remat == "full":
        act = L * tok * H * bytes_el  # layer-boundary residual per layer
    elif remat == "dots":
        inter = cfg.intermediate_size / mesh_factors.get("tensor", 1)
        per_layer = tok * (H * n_hidden_dots + inter * n_inter_dots) * bytes_el
        act = L * per_layer
    elif remat == "dots_narrow":
        per_layer = tok * (H * n_hidden_dots) * bytes_el
        act = L * per_layer
    elif remat == "dots_all":
        # dots_saveable additionally keeps the S^2-per-head attention
        # logits as residuals, in COMPUTE dtype (params_util.remat_policy)
        inter = cfg.intermediate_size / mesh_factors.get("tensor", 1)
        per_layer = tok * (H * n_hidden_dots + inter * n_inter_dots) * bytes_el + (
            (B / batch_div) * heads * (S / seq_div) * S * bytes_el
        )
        act = L * per_layer
    else:  # none: dense residuals incl. f32 S^2 attention probs (measured)
        inter = cfg.intermediate_size / mesh_factors.get("tensor", 1)
        per_layer = tok * (H * 8 + inter * 3) * bytes_el + (
            (B / batch_div) * heads * (S / seq_div) * S * 4
        )
        act = L * per_layer
    logits = 0 if loss == "chunked" else tok * cfg.vocab_size * 4
    total = frozen_bytes + trainable_bytes + opt_bytes + grad_bytes + act + logits
    hbm = CHIP_HBM[chip]
    return {
        "model": model,
        "devices": n_devices,
        # unrounded, for tools asserting live measurements against the plan
        # (the _gb fields are display-rounded to 1 MB and can carry >10%
        # relative rounding error on small components)
        "per_device_bytes": {
            "frozen_params": frozen_bytes,
            "trainable_params": trainable_bytes,
            "adam_moments": opt_bytes,
            "grads": grad_bytes,
            "activations": act,
            "logits": logits,
            "total": total,
        },
        "per_device_gb": {
            "frozen_params": round(frozen_bytes / 1e9, 3),
            "trainable_params": round(trainable_bytes / 1e9, 3),
            "adam_moments": round(opt_bytes / 1e9, 3),
            "grads": round(grad_bytes / 1e9, 3),
            "activations": round(act / 1e9, 3),
            "logits": round(logits / 1e9, 3),
            "total": round(total / 1e9, 3),
        },
        "chip": chip,
        "hbm_gb": hbm / 1e9,
        # budget = 0.9*HBM (10% reserved for XLA workspace); headroom is
        # against the same budget so fits=false never shows positive headroom
        "budget_gb": round(hbm * 0.9 / 1e9, 2),
        "fits": total < hbm * 0.9,
        "headroom_gb": round((hbm * 0.9 - total) / 1e9, 2),
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama_1b")
    p.add_argument("--rank", type=int, default=128, help="0 = full-rank training")
    p.add_argument("--mesh", default="", help="e.g. fsdp=8,tensor=2 (default: single chip)")
    p.add_argument("--micro-batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--quantize", default=None, choices=[None, "int8", "nf4"])
    p.add_argument("--base-dtype", default=None, choices=[None, "bf16"],
                   help="unquantized frozen-base storage dtype (default f32 master)")
    p.add_argument(
        "--remat", default="full", choices=["full", "dots", "dots_narrow", "dots_all", "none"]
    )
    p.add_argument("--loss", default="dense", choices=["dense", "chunked"])
    p.add_argument("--chip", default="v5e", choices=sorted(CHIP_HBM))
    p.add_argument("--layers", type=int, default=0, help="override layer count")
    args = p.parse_args()

    # abstract-only tool: always run on CPU (eval_shape never touches a
    # device, and a memory plan should not hold a chip)
    os.environ["JAX_PLATFORMS"] = "cpu"
    out = plan(
        args.model,
        rank=args.rank,
        mesh=args.mesh,
        micro_batch=args.micro_batch,
        seq=args.seq,
        dtype=args.dtype,
        quantize=args.quantize,
        base_dtype=args.base_dtype,
        remat=args.remat,
        loss=args.loss,
        chip=args.chip,
        layers=args.layers,
    )
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
