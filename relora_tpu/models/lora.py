"""LoRA-factored Dense layer: the TPU-native ReLoRaLinear.

The reference swaps ``nn.Linear`` modules for ``ReLoRaLinear`` objects after
model construction (relora.py:94-134) and tracks trainability with
``requires_grad`` flags (relora.py:259-261).  Here LoRA is a property of the
layer itself: when a ``LoraSpec`` is provided, the module owns extra pytree
leaves ``lora_a`` / ``lora_b`` (and optionally ``lora_s``) next to its frozen
``kernel``, and trainability is a *mask over the param tree*
(relora_tpu.core.relora) — no module surgery, no flags.

Forward (parity: relora.py:309-323)::

    y = x @ W  (+ bias)  +  ((dropout(x) @ A) @ B) * scale

Init: A ~ kaiming-uniform, B = 0 — so the wrapped model equals the base model
at init (B=0 ⇒ the LoRA branch contributes nothing), which is the reference's
own init-equivalence invariant (relora.py:120-124).  Deliberate deviation:
the reference *additionally* zeroes A when keep_original_weights=True, which
puts A/B at an exact saddle (both gradients identically zero) until the first
merge re-draws A; we keep A at kaiming so learning starts immediately, while
preserving the same init-equivalence guarantee.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from relora_tpu.core.relora import LoraSpec, kaiming_uniform

import logging

# (module name, width) pairs already warned about the nf4->int8 fallback —
# the warning should fire once per projection, not on every trace
_NF4_FALLBACK_WARNED: set = set()


def _env_pallas_quant() -> bool:
    """RELORA_TPU_PALLAS_QUANT=1 opt-in, read at module *construction* —
    never inside the traced ``__call__`` (the retrace footgun RTL1xx
    polices: an env flip between traces would silently split the cache)."""
    return os.environ.get("RELORA_TPU_PALLAS_QUANT") == "1"


class LoRALinear(nn.Module):
    """Dense layer with optional LoRA factors as first-class pytree leaves.

    ``kernel_axes`` are *logical* partitioning names resolved to mesh axes by
    relora_tpu.parallel's rules; the rank axis is named "lora" (replicated by
    default, shardable for very large models).
    """

    features: int
    use_bias: bool = False
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: nn.initializers.Initializer = nn.initializers.normal(stddev=0.02)
    kernel_axes: Tuple[Optional[str], Optional[str]] = (None, None)
    quantize: Optional[str] = None  # None | "int8" (frozen base only)
    # Pallas dequant-matmul opt-in for the int8 base.  None = consult the
    # RELORA_TPU_PALLAS_QUANT env var once, here at construction.
    pallas_quant: Optional[bool] = None

    def __post_init__(self):
        if self.pallas_quant is None:
            object.__setattr__(self, "pallas_quant", _env_pallas_quant())
        super().__post_init__()

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        deterministic: bool = True,
        adapter_idx: Optional[jax.Array] = None,
    ) -> jax.Array:
        in_features = x.shape[-1]
        if self.lora is not None and self.lora.num_slots > 0:
            # multi-tenant serving layout: factors stacked (num_slots, ...),
            # each activation row routed to its slot by adapter_idx
            return self._grouped(x, in_features, adapter_idx)
        if self.lora is not None and self.lora.lora_only:
            # pure-LoRA layer: no base weight, no bias (relora.py:209-211)
            return self._lora_branch(x, in_features, deterministic)
        # quantization follows the LoRA spec (parity: quantize lives in
        # ReLoRaConfig, relora.py:18-28) unless set explicitly
        quantize = self.quantize or (self.lora.quantize if self.lora else None)
        if quantize == "nf4" and in_features % 2:
            # nf4 packs two codes per byte along in_features; an odd width
            # (e.g. llama_1b's 5461-wide down_proj) can't pack, so this
            # projection falls back to int8 — the rest of the model stays
            # nf4, and the per-module merge dispatches on leaf names so a
            # mixed base merges correctly (bnb instead pads the flattened
            # tensor, reference relora.py:222-238)
            quantize = "int8"
            key = (self.name, in_features)
            if key not in _NF4_FALLBACK_WARNED:
                # once per module/width at trace time: the user asked for
                # nf4 but this projection stores int8 (2x the bytes) —
                # memory/accuracy comparisons against pure-nf4 expectations
                # would otherwise misattribute the difference
                _NF4_FALLBACK_WARNED.add(key)
                logging.getLogger(__name__).warning(
                    "nf4 requested but in_features=%d is odd for module %r; "
                    "storing this base as int8 (plan_memory accounts for it)",
                    in_features, self.name,
                )
        # Fused/dispatched composite: spec.fused routes the whole
        # y = x@W + ((x@A)@B)*scale through ops/lora_dispatch instead of the
        # three-matmul path below.  Dropout makes the branch input differ
        # from the base input and nf4 has no fused kernel — both keep the
        # historical path (the fallback matrix in docs/kernels.md).
        dropout_active = (
            self.lora is not None and self.lora.dropout > 0.0 and not deterministic
        )
        if (
            self.lora is not None
            and self.lora.fused in (True, "auto")
            and quantize in (None, "int8")
            and not dropout_active
        ):
            return self._dispatched(x, in_features, quantize)
        if quantize == "int8":
            kernel_q, kernel_scale = self._int8_params(in_features)
            y = self._int8_matmul(x, kernel_q, kernel_scale)
        elif quantize == "nf4":
            y = self._nf4_matmul(x, in_features)
        elif quantize is not None:
            raise ValueError(f"Unknown quantize mode {quantize!r}")
        else:
            kernel = self._dense_kernel(in_features)
            y = jnp.matmul(x.astype(self.dtype), kernel.astype(self.dtype))
        if self.use_bias:
            y = y + self._bias_param().astype(self.dtype)

        if self.lora is not None:
            y = y + self._lora_branch(x, in_features, deterministic)
        return y

    # -- param definitions (shared by the historical and dispatched paths;
    # flax params are name-keyed, so both paths see identical init values) --

    def _dense_kernel(self, in_features: int) -> jax.Array:
        # frozen-base storage dtype: spec.base_dtype == "bf16" drops the
        # f32 master for the base kernel (it takes no per-step optimizer
        # updates; merges cast back to storage dtype in core/relora.py).
        # Only applies when the kernel IS a frozen LoRA base — a plain
        # Dense (no LoRA spec) keeps the f32 master.
        base_dtype = (
            jnp.bfloat16
            if (self.lora is not None and self.lora.base_dtype == "bf16")
            else self.param_dtype
        )
        return self.param(
            "kernel",
            nn.with_logical_partitioning(self.kernel_init, self.kernel_axes),
            (in_features, self.features),
            base_dtype,
        )

    def _int8_params(self, in_features: int) -> Tuple[jax.Array, jax.Array]:
        # Fresh init is W=0 (codes zero, scales one): a quantized base is
        # only meaningful warm-started from real weights — exactly how the
        # reference uses bitsandbytes (it quantizes the wrapped module's
        # existing weight_data, relora.py:222-238).  Use
        # hf_compat.graft_base_weights, which quantizes f32 sources on
        # the fly.
        def q_init(key, shape, dtype):
            return jnp.zeros(shape, dtype)

        def s_init(key, shape, dtype):
            return jnp.ones(shape, dtype)

        kernel_q = self.param(
            "kernel_q",
            nn.with_logical_partitioning(q_init, self.kernel_axes),
            (in_features, self.features),
            jnp.int8,
        )
        kernel_scale = self.param(
            "kernel_scale",
            nn.with_logical_partitioning(s_init, (None, self.kernel_axes[1])),
            (1, self.features),
            jnp.float32,
        )
        return kernel_q, kernel_scale

    def _bias_param(self) -> jax.Array:
        return self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), (self.kernel_axes[1],)),
            (self.features,),
            self.param_dtype,
        )

    def _dispatched(self, x: jax.Array, in_features: int, quantize: Optional[str]) -> jax.Array:
        """The y = x@W + ((x@A)@B)*scale composite via ops/lora_dispatch.

        ``fused=True`` pins the fused Pallas kernel wherever this call's
        (rows, features) has a block plan — the module sees its shapes, and
        the dispatcher raises when a forced arm cannot tile — and leaves the
        rest to ``"auto"``, which lets the roofline cost model pick per
        shape.  The frozen base gets
        ``stop_gradient`` so every arm agrees its cotangent is zero — the
        optimizer mask already never applies base updates, this just keeps
        grads arm-independent.
        """
        from relora_tpu.ops.lora_dispatch import lora_matmul, plan_blocks

        rows = 1
        for d in x.shape[:-1]:
            rows *= d
        pin_fused = self.lora.fused is True and plan_blocks(rows, self.features) is not None
        if quantize == "int8":
            kernel_q, kernel_scale = self._int8_params(in_features)
            base = (kernel_q, kernel_scale)
        else:
            base = jax.lax.stop_gradient(
                self._dense_kernel(in_features).astype(self.dtype)
            )
        lora_a, lora_b, scale = self._lora_factors(in_features)
        y = lora_matmul(
            x.astype(self.dtype),
            base,
            lora_a.astype(self.dtype),
            lora_b.astype(self.dtype),
            scale,
            arm="fused" if pin_fused else "auto",
            dtype=self.dtype,
            weights_static=self.lora.weights_static,
        )
        if self.use_bias:
            y = y + self._bias_param().astype(self.dtype)
        return y

    def _grouped(
        self, x: jax.Array, in_features: int, adapter_idx: Optional[jax.Array]
    ) -> jax.Array:
        """Multi-tenant composite: stacked (num_slots, ...) factor leaves and
        the per-row slot map through ops/lora_dispatch.lora_matmul_grouped.

        Every slot zero-inits (lora_b = 0 ⇒ identity branch), so slot 0 is
        the base-model adapter by construction and unloaded slots are inert;
        serve/adapters.py overwrites slots in place as tenants load/evict —
        shapes are static, swaps are pure data movement.  ``adapter_idx`` may
        be per-row (M,) or per-batch (B,) (repeated across the row dim);
        ``None`` routes everything to slot 0.
        """
        from relora_tpu.ops.lora_dispatch import lora_matmul_grouped

        spec = self.lora
        base = jax.lax.stop_gradient(
            self._dense_kernel(in_features).astype(self.dtype)
        )
        a_stack = self.param(
            "lora_a",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), (None, self.kernel_axes[0], "lora")
            ),
            (spec.num_slots, in_features, spec.r),
            self.param_dtype,
        )
        b_stack = self.param(
            "lora_b",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), (None, "lora", self.kernel_axes[1])
            ),
            (spec.num_slots, spec.r, self.features),
            self.param_dtype,
        )
        # per-slot scale (each adapter's sidecar may carry its own alpha)
        s_stack = self.param(
            "lora_s",
            lambda key, shape, dtype: jnp.full(shape, spec.scale, dtype),
            (spec.num_slots,),
            jnp.float32,
        )
        rows = 1
        for d in x.shape[:-1]:
            rows *= d
        if adapter_idx is None:
            idx = jnp.zeros((rows,), jnp.int32)
        else:
            idx = adapter_idx.reshape(-1).astype(jnp.int32)
            if idx.shape[0] != rows:
                idx = jnp.repeat(idx, rows // idx.shape[0])
        y = lora_matmul_grouped(
            x.astype(self.dtype),
            base,
            a_stack.astype(self.dtype),
            b_stack.astype(self.dtype),
            s_stack,
            idx,
            arm="auto",
            dtype=self.dtype,
        )
        if self.use_bias:
            y = y + self._bias_param().astype(self.dtype)
        return y

    def _int8_matmul(self, x, kernel_q, kernel_scale) -> jax.Array:
        """x @ int8 base.  Default: dequantize then matmul (XLA fuses).
        ``pallas_quant`` (RELORA_TPU_PALLAS_QUANT=1, read at construction)
        opts into the custom pallas kernel that keeps the weight int8 into
        VMEM (ops/pallas_quant_matmul) when the shapes tile; falls back
        otherwise."""
        if self.pallas_quant:
            from relora_tpu.ops.lora_dispatch import plan_blocks
            from relora_tpu.ops.pallas_quant_matmul import dequant_matmul

            M = 1
            for d in x.shape[:-1]:
                M *= d
            planned = plan_blocks(M, self.features)
            if planned:
                bm, bn = planned
                lead = x.shape[:-1]
                out = dequant_matmul(
                    x.reshape(M, x.shape[-1]).astype(self.dtype),
                    kernel_q,
                    kernel_scale,
                    block_m=bm,
                    block_n=bn,
                    interpret=jax.default_backend() == "cpu",
                    out_dtype=self.dtype,
                )
                return out.reshape(*lead, self.features)
        from relora_tpu.ops.quant import dequantize_int8

        kernel = dequantize_int8(kernel_q, kernel_scale, self.dtype)
        return jnp.matmul(x.astype(self.dtype), kernel)

    def _nf4_matmul(self, x: jax.Array, in_features: int) -> jax.Array:
        """x @ nf4 base (~0.53 bytes/element in HBM; see ops/quant.py).

        Like int8, a fresh init is W=0 (all codes point at codebook entry 7
        == 0.0) — only meaningful warm-started via graft_base_weights, which
        nf4-quantizes f32 sources on the fly.  Double-quant is the LoraSpec's
        ``use_double_quant`` (it sets the bscale_q dtype at init)."""
        from relora_tpu.ops.quant import dequantize_nf4, nf4_block_for

        block = nf4_block_for(in_features)
        dq = self.lora.use_double_quant if self.lora else True
        leaves = {
            "codes": self.param(
                "kernel_codes",
                nn.with_logical_partitioning(
                    # codebook entry 7 is exactly 0.0 -> W=0 at fresh init
                    lambda key, shape, dtype: jnp.full(shape, 0x77, dtype),
                    self.kernel_axes,
                ),
                (in_features // 2, self.features),
                jnp.uint8,
            ),
            "bscale_q": self.param(
                "kernel_bscale_q",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init() if dq else nn.initializers.ones_init(),
                    (None, self.kernel_axes[1]),
                ),
                (in_features // block, self.features),
                jnp.int8 if dq else jnp.float32,
            ),
            "bscale_scale": self.param(
                "kernel_bscale_scale",
                nn.with_logical_partitioning(
                    nn.initializers.ones_init(), (None, self.kernel_axes[1])
                ),
                (1, self.features),
                jnp.float32,
            ),
            "bscale_offset": self.param(
                "kernel_bscale_offset",
                nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), (None, self.kernel_axes[1])
                ),
                (1, self.features),
                jnp.float32,
            ),
        }
        kernel = dequantize_nf4(leaves, self.dtype)
        return jnp.matmul(x.astype(self.dtype), kernel)

    def _lora_factors(self, in_features: int):
        """Define the LoRA leaves; returns (lora_a, lora_b, scale) where
        scale is either the static spec.scale float or the traced
        trainable-scaling ``tanh(lora_s)`` (parity: relora.py:263-267)."""
        spec = self.lora
        lora_a = self.param(
            "lora_a",
            nn.with_logical_partitioning(
                lambda key, shape, dtype: kaiming_uniform(key, shape, dtype),
                (self.kernel_axes[0], "lora"),
            ),
            (in_features, spec.r),
            self.param_dtype,
        )
        lora_b = self.param(
            "lora_b",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("lora", self.kernel_axes[1])
            ),
            (spec.r, self.features),
            self.param_dtype,
        )
        if spec.trainable_scaling:
            lora_s = self.param(
                "lora_s", nn.initializers.ones_init(), (1,), jnp.float32
            )
            # parity: trainable scaling passes through tanh (relora.py:263-267)
            scale = jnp.tanh(lora_s.astype(self.dtype))
        else:
            scale = spec.scale
        return lora_a, lora_b, scale

    def _lora_branch(self, x: jax.Array, in_features: int, deterministic: bool) -> jax.Array:
        """((dropout(x) @ A) @ B) * scale (parity: relora.py:309-323)."""
        spec = self.lora
        lora_a, lora_b, scale = self._lora_factors(in_features)
        h = x
        if spec.dropout > 0.0 and not deterministic:
            h = nn.Dropout(rate=spec.dropout, deterministic=False)(h)
        z = jnp.matmul(h.astype(self.dtype), lora_a.astype(self.dtype))
        z = jnp.matmul(z, lora_b.astype(self.dtype))
        return z * scale
