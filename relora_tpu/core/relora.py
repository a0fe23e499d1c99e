"""ReLoRA core: LoRA leaf classification and the pure merge-and-reinit update.

The reference mutates modules in place: ``ReLoRaLinear.merge_and_reinit``
does ``W += B @ A * scale`` then re-draws A (kaiming) and zeroes B under
``torch.no_grad`` (peft_pretraining/relora.py:269-307).  Here the same
operation is a **pure function** ``(params, rng) -> params``: the pytree
structure, dtypes and shardings are unchanged, so the already-compiled train
step keeps running after a merge with no retrace, and under a sharded mesh the
merge is just a (fully sharded) pytree update — the thing that made the
reference give up on FSDP (torchrun_main.py:611-613) is free by construction.

Naming convention (see relora_tpu.models.lora.LoRALinear): a LoRA-wrapped
Dense owns leaves ``kernel`` (frozen base), ``lora_a`` (in, r),
``lora_b`` (r, out) and optionally ``lora_s`` (trainable scaling).  A module
dict that contains ``lora_a`` marks its sibling ``kernel`` as frozen.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

PyTree = Any

LORA_A = "lora_a"
LORA_B = "lora_b"
LORA_S = "lora_s"


@dataclass(frozen=True)
class LoraSpec:
    """Static LoRA hyperparameters needed by merge/init math.

    Parity: ReLoRaConfig (relora.py:18-28); ``quantize`` selects int8 storage
    for the frozen base (the bitsandbytes replacement — see ops/quant.py).
    """

    r: int
    alpha: float = 32.0
    dropout: float = 0.1
    trainable_scaling: bool = False
    quantize: Optional[str] = None  # None | "int8" | "nf4"
    # Storage dtype of the unquantized frozen base: None keeps the module's
    # param_dtype (f32 master).  "bf16" stores the base in bfloat16 — the
    # base takes no optimizer updates between merges, so the f32 master buys
    # nothing per-step, while bf16 halves its HBM and (measured, round 5)
    # removes the all-layers f32->bf16 convert temps XLA hoists out of the
    # scan loop.  Merges still compute in f32 (lora_delta at HIGHEST) and
    # cast back to storage, same as the int8/nf4 dequant->add->requant flow.
    base_dtype: Optional[str] = None  # None | "bf16"
    # nf4 only: int8-quantize the per-block scales themselves (parity:
    # use_double_quant -> bnb_4bit_use_double_quant, relora.py:57-63)
    use_double_quant: bool = True
    # pure-LoRA layers with no base weight at all (parity: lora_only,
    # relora.py:209-211; selected when neither relora, force_keep_original
    # nor a warm start needs the full kernel, torchrun_main.py:531-553)
    lora_only: bool = False
    # How to execute the y = x@W + ((x@A)@B)*scale composite:
    #   False  — the historical unfused path (three matmuls + add)
    #   True   — always the fused Pallas kernel (ops/pallas_lora_matmul)
    #            where shapes tile; untileable shapes fall back unfused
    #   "auto" — per-shape choice between fused / unfused / merged via the
    #            ops/lora_dispatch roofline cost model
    # Replaces env-var gating: the value is part of the spec, read once at
    # construction, so traced code never touches os.environ.
    fused: Union[bool, str] = False
    # Serving hint set by serve/engine.build_decode_model: W/A/B are constant
    # across decode steps, so the dispatch cost model may treat the merged
    # W + scale·A@B as amortized (it decides decode-shaped calls toward the
    # merged arm).  Never set in training — W changes every update.
    weights_static: bool = False
    # Multi-tenant serving (serve/adapters.py): > 0 stacks every LoRA factor
    # as (num_slots, in, r)/(num_slots, r, out) HBM slabs and routes the
    # forward through the grouped kernel with a per-row adapter_idx.  Slot 0
    # is the identity (base-model) adapter: lora_b zero-init makes every
    # unloaded slot a no-op branch.  0 (the default, and what every training
    # sidecar on disk says implicitly) keeps the single-adapter layout.
    num_slots: int = 0

    def __post_init__(self):
        # validate HERE (not just TrainingConfig): tools/plan_memory.py and
        # the tests construct LoraSpec directly, and a typo'd or
        # quantize-shadowed base_dtype would otherwise run the f32 master
        # while the recorded measurement claims bf16
        if self.base_dtype not in (None, "bf16"):
            raise ValueError(f"base_dtype must be None or 'bf16', got {self.base_dtype!r}")
        if self.base_dtype and self.quantize:
            raise ValueError("base_dtype applies to the unquantized base; drop it or quantize")
        if self.fused not in (True, False, "auto"):
            raise ValueError(f"fused must be True, False or 'auto', got {self.fused!r}")
        if self.num_slots < 0:
            raise ValueError(f"num_slots must be >= 0, got {self.num_slots}")
        if self.num_slots > 0 and self.trainable_scaling:
            raise ValueError(
                "num_slots > 0 is a serving-only layout; trainable_scaling has no "
                "stacked equivalent (per-slot scales come from each adapter's sidecar)"
            )
        if self.num_slots > 0 and self.quantize:
            raise ValueError(
                "num_slots > 0 requires a dense base (the grouped kernel does not "
                "read quantized bases); drop quantize for multi-tenant serving"
            )

    @property
    def scale(self) -> float:
        return self.alpha / self.r


def kaiming_uniform(key: jax.Array, shape: Tuple[int, ...], dtype=jnp.float32) -> jax.Array:
    """torch's kaiming_uniform_(a=sqrt(5)) on a (out, in) weight = U(±1/sqrt(fan_in)).

    Our lora_a is stored (..., in, r) (flax kernel convention, with optional
    leading scan-layer axes), so fan_in is shape[-2].  Matches
    nn.init.kaiming_uniform_(lora_A.weight, a=math.sqrt(5)) at
    relora.py:251, 303.
    """
    bound = 1.0 / math.sqrt(shape[-2])
    return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)


def is_lora_path(path: Tuple) -> bool:
    """True if a tree path (from tree_map_with_path / tree_flatten_with_path)
    addresses a LoRA factor leaf (parity: the reference's "lora_" name match,
    torchrun_main.py:632)."""
    if not path:
        return False
    last = path[-1]
    name = getattr(last, "key", None) or getattr(last, "name", None) or str(last)
    return str(name).startswith("lora_")


def lora_param_mask(params: PyTree) -> PyTree:
    """Boolean pytree: True for LoRA factor leaves (lora_a/lora_b/lora_s)."""
    return jax.tree_util.tree_map_with_path(lambda p, _: is_lora_path(p), params)


def frozen_param_mask(params: PyTree) -> PyTree:
    """Boolean pytree: True for the frozen base kernels of LoRA-wrapped Denses.

    A ``kernel`` (or ``bias``-less quantized variants) is frozen iff its module
    dict also carries ``lora_a`` — mirroring ReLoRaLinear freezing only
    ``self.weight`` (relora.py:259-261) while biases stay trainable.
    """

    def walk(node):
        if isinstance(node, dict):
            has_lora = LORA_A in node
            out = {}
            for k, v in node.items():
                if isinstance(v, dict):
                    out[k] = walk(v)
                else:
                    # quantized codes/scales (int8 + nf4 leaves) are never
                    # trainable regardless of LoRA
                    out[k] = bool(
                        (has_lora and k == "kernel")
                        or k in ("kernel_q", "kernel_scale")
                        or k.startswith("kernel_codes")
                        or k.startswith("kernel_bscale")
                    )
            return out
        return False

    return walk(params)


def trainable_param_mask(params: PyTree, lora_only: bool = False) -> PyTree:
    """True for every trainable leaf.

    Reference semantics (torchrun_main.py:631-633): everything with
    requires_grad — i.e. all params except the frozen base kernels.  With
    ``lora_only`` only the LoRA factors train.
    """
    if lora_only:
        return lora_param_mask(params)
    frozen = frozen_param_mask(params)
    return jax.tree_util.tree_map(lambda f: not f, frozen)


def split_param_counts(params: PyTree) -> dict:
    """Param accounting for logging (parity: torchrun_main.py:585-594)."""
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(params)[0]
    total = trainable = lora = 0
    frozen_mask_leaves = jax.tree_util.tree_leaves(frozen_param_mask(params))
    for (path, leaf), is_frozen in zip(leaves_with_paths, frozen_mask_leaves):
        n = leaf.size
        total += n
        if is_lora_path(path):
            lora += n
            trainable += n
        elif not is_frozen:
            trainable += n
    return {
        "total_params": total,
        "trainable_params": trainable,
        "lora_params": lora,
        "equivalent_params": total - lora,  # params of the merged (base) model
    }


def _effective_scale(module: dict, spec: LoraSpec):
    if spec.trainable_scaling and LORA_S in module:
        # parity: trainable scaling passes through tanh (relora.py:263-267).
        # lora_s is (..., 1); reshape so it broadcasts over a (..., in, out)
        # delta whether or not there is a leading scan-layer axis.
        s = jnp.tanh(module[LORA_S].astype(jnp.float32))
        return s.reshape(s.shape[:-1] + (1, 1))
    return spec.scale


def lora_delta(module: dict, spec: LoraSpec) -> jax.Array:
    """The full-rank update this module's factors currently represent:
    ``lora_a @ lora_b * scale``, shaped like ``kernel``.

    Computed at HIGHEST matmul precision: on TPU, f32 matmuls default to
    bf16 MXU passes, and merge error would otherwise compound across every
    ReLoRA cycle.  This matmul runs once per ``relora`` steps, so the extra
    MXU passes are free in the training budget.
    """
    a = module[LORA_A].astype(jnp.float32)
    b = module[LORA_B].astype(jnp.float32)
    # einsum with ellipsis: supports both plain (in, r) @ (r, out) and
    # scan-stacked (layers, in, r) @ (layers, r, out) factors.
    delta = jnp.einsum("...ir,...ro->...io", a, b, precision=jax.lax.Precision.HIGHEST)
    return delta * _effective_scale(module, spec)


def merge_and_reinit(
    params: PyTree,
    rng: jax.Array,
    spec: LoraSpec,
    *,
    a_init=None,
    mask: Optional[PyTree] = None,
) -> PyTree:
    """Pure ReLoRA reset: fold every module's ``A @ B * scale`` into its frozen
    kernel, re-draw A (kaiming uniform), zero B (and scaling, if trainable).

    Parity: ReLoRaLinear.merge_and_reinit (relora.py:269-307) /
    merge_and_reinit_functional (relora.py:31-46), but jit-safe: accepts and
    returns the same pytree, merge math in f32, outputs cast back to stored
    dtypes.  Intended use::

        merged = jax.jit(partial(merge_and_reinit, spec=spec), donate_argnums=0)(params, rng)

    Compression hooks (relora_tpu/compress):

    - ``a_init`` — pluggable A re-init ``(key, a_shape, merged_f32) -> array``
      receiving the merged (and masked) base, so magnitude-informed inits can
      read the weight profile.  ``None`` is the historical kaiming path,
      byte-for-byte (identical key sequence, identical draw).
    - ``mask`` — a prune keep-mask tree (nested dict with a boolean
      ``kernel`` leaf per pruned module, see compress/prune.py) applied to
      the merged f32 values *before* requant/cast, so pruned positions land
      exactly zero in every storage format with a single quantization.
    """
    # Deterministic per-module keys: count lora modules in tree order first.
    modules = []

    def collect(node):
        if isinstance(node, dict):
            if LORA_A in node:
                modules.append(True)
            for v in node.values():
                collect(v)

    collect(params)
    keys = jax.random.split(rng, max(1, len(modules)))
    key_iter = iter(range(len(modules)))

    def walk(node, mask_node):
        if not isinstance(node, dict):
            return node
        sub = mask_node if isinstance(mask_node, dict) else {}
        if LORA_A not in node:
            return {k: walk(v, sub.get(k)) for k, v in node.items()}
        key = keys[next(key_iter)]
        if "kernel" not in node and "kernel_q" not in node and "kernel_codes" not in node:
            # lora_only module: nothing to merge into — skipped entirely,
            # like the reference's warning-and-return (relora.py:271-273)
            return dict(node)
        out = dict(node)
        if "kernel_q" in node:
            # int8 base: dequant -> add -> requant (parity with the 4-bit
            # merge flow, relora.py:277-287)
            from relora_tpu.ops.quant import dequantize_int8, quantize_int8

            merged = dequantize_int8(node["kernel_q"], node["kernel_scale"]) + lora_delta(node, spec)
            merged = _masked(merged, sub)
            out["kernel_q"], out["kernel_scale"] = quantize_int8(merged)
        elif "kernel_codes" in node:
            # nf4 base: dequant -> add -> requant, double-quant preserved
            # (the exact flow of the reference's 4-bit merge, relora.py:277-287)
            from relora_tpu.ops.quant import (
                dequantize_nf4,
                nf4_leaves_from_module,
                nf4_leaves_to_module,
                quantize_nf4,
            )

            merged = dequantize_nf4(nf4_leaves_from_module(node)) + lora_delta(node, spec)
            merged = _masked(merged, sub)
            requant = quantize_nf4(
                merged, double_quant=node["kernel_bscale_q"].dtype == jnp.int8
            )
            out.update(nf4_leaves_to_module(requant))
        else:
            kernel = node["kernel"]
            merged = kernel.astype(jnp.float32) + lora_delta(node, spec)
            merged = _masked(merged, sub)
            out["kernel"] = merged.astype(kernel.dtype)
        a_shape = node[LORA_A].shape
        fresh_a = kaiming_uniform(key, a_shape) if a_init is None else a_init(key, a_shape, merged)
        out[LORA_A] = fresh_a.astype(node[LORA_A].dtype)
        out[LORA_B] = jnp.zeros_like(node[LORA_B])
        if spec.trainable_scaling and LORA_S in node:
            out[LORA_S] = jnp.zeros_like(node[LORA_S])
        return out

    return walk(params, mask)


def _masked(merged: jax.Array, mask_node: dict) -> jax.Array:
    """Apply a module's prune keep-mask to its merged f32 kernel, if any."""
    keep = mask_node.get("kernel") if isinstance(mask_node, dict) else None
    if keep is None or isinstance(keep, dict):
        return merged
    return jnp.where(keep, merged, 0.0)


def merged_params(params: PyTree, spec: LoraSpec) -> PyTree:
    """Merge without reinit: returns params of the equivalent full-rank model
    (for export / saving an HF-compatible checkpoint), LoRA leaves dropped.

    Quantized bases (int8 / nf4) are dequantized into a plain f32 ``kernel``
    — the export target is the HF full-precision layout."""

    def walk(node):
        if not isinstance(node, dict):
            return node
        if LORA_A not in node or LORA_B not in node:
            # no factors (already-merged / full-rank tree — e.g. a serve-side
            # load of an exported checkpoint whose relora_config.json sidecar
            # survived the merge): pass through instead of KeyError-ing
            return {k: walk(v) for k, v in node.items()}
        from relora_tpu.ops.quant import NF4_MODULE_LEAVES

        quant_keys = ("kernel_q", "kernel_scale", *NF4_MODULE_LEAVES)
        out = {
            k: v
            for k, v in node.items()
            if k not in (LORA_A, LORA_B, LORA_S) and k not in quant_keys
        }
        if "kernel_q" in node:
            from relora_tpu.ops.quant import dequantize_int8

            base = dequantize_int8(node["kernel_q"], node["kernel_scale"])
            out["kernel"] = base + lora_delta(node, spec)
        elif "kernel_codes" in node:
            from relora_tpu.ops.quant import dequantize_nf4, nf4_leaves_from_module

            base = dequantize_nf4(nf4_leaves_from_module(node))
            out["kernel"] = base + lora_delta(node, spec)
        else:
            kernel = node["kernel"]
            out["kernel"] = (kernel.astype(jnp.float32) + lora_delta(node, spec)).astype(
                kernel.dtype
            )
        return out

    return walk(params)
