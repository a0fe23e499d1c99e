"""Custom pallas kernel tests (interpret mode on CPU; the TPU path shares
the exact same kernel body)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.ops.attention import dot_product_attention
from relora_tpu.ops.pallas_quant_matmul import dequant_matmul
from relora_tpu.ops.quant import dequantize_int8, quantize_int8


def test_dequant_matmul_matches_reference():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 192))
    w = jax.random.normal(jax.random.fold_in(key, 1), (192, 256)) * 0.1
    q, s = quantize_int8(w)
    want = x @ dequantize_int8(q, s)
    got = dequant_matmul(x, q, s, block_m=128, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_dequant_matmul_batched_and_blocks():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (2, 4, 128, 64))  # leading batch dims
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 128)) * 0.05
    q, s = quantize_int8(w)
    want = jnp.einsum("...mk,kn->...mn", x, dequantize_int8(q, s))
    got = dequant_matmul(x, q, s, block_m=256, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_dequant_matmul_grad_matches_reference():
    """jax.grad through the kernel (custom VJP) == grad of dequant-then-matmul."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (128, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 128)) * 0.1
    q, s = quantize_int8(w)

    def loss_kernel(x, s):
        return jnp.sum(dequant_matmul(x, q, s, block_m=128, block_n=128, interpret=True) ** 2)

    def loss_ref(x, s):
        return jnp.sum((x @ (q.astype(jnp.float32) * s)) ** 2)

    gx, gs = jax.grad(loss_kernel, argnums=(0, 1))(x, s)
    gx_ref, gs_ref = jax.grad(loss_ref, argnums=(0, 1))(x, s)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), rtol=1e-4, atol=1e-4)


def test_pallas_quant_train_step_traces(monkeypatch):
    """RELORA_TPU_PALLAS_QUANT=1 must survive jax.grad at trace time (the
    advertised opt-in crashed int8 ReLoRA training before the custom VJP)."""
    monkeypatch.setenv("RELORA_TPU_PALLAS_QUANT", "1")
    from relora_tpu.core.relora import LoraSpec
    from relora_tpu.models.lora import LoRALinear

    import flax.linen as nn

    model = LoRALinear(features=128, lora=LoraSpec(r=4, alpha=8), quantize="int8")
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(1), x, deterministic=True))

    frozen = dict(params["params"])
    lora = {k: frozen.pop(k) for k in ("lora_a", "lora_b")}

    def loss(lora_p):
        return jnp.sum(model.apply({"params": {**frozen, **lora_p}}, x, deterministic=True) ** 2)

    g = jax.jit(jax.grad(loss))(lora)
    assert jnp.isfinite(jnp.sum(g["lora_a"]))


def test_dequant_matmul_validation():
    x = jnp.zeros((100, 64))
    q = jnp.zeros((64, 128), jnp.int8)
    s = jnp.ones((1, 128))
    with pytest.raises(ValueError, match="tile"):
        dequant_matmul(x, q, s, block_m=64, block_n=128, interpret=True)
    with pytest.raises(ValueError, match="mismatch"):
        dequant_matmul(jnp.zeros((128, 32)), q, s, interpret=True)


@pytest.mark.parametrize("seq", [8, 200])
def test_pallas_impl_falls_back_below_tile(seq):
    """Sub-tile or unaligned lengths route to the XLA path instead of
    crashing in the kernel's block verifier (e.g. the (1, 8) init trace)."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, seq, 2, 16), jnp.float32)
    out_p = dot_product_attention(q, q, q, causal=True, impl="pallas")
    out_x = dot_product_attention(q, q, q, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), atol=1e-6)


def test_pallas_block_size_selection():
    """Block sizes must divide the sequence exactly: 768 is a 128-multiple
    where a naive min(512, S) would be rejected by the kernel; sub-tile or
    unaligned lengths return None (the XLA fallback)."""
    from relora_tpu.ops.attention import flash_block_size

    assert flash_block_size(1024, 1024) == 512
    assert flash_block_size(768, 768) == 256
    assert flash_block_size(640, 1024) == 128
    assert flash_block_size(128, 128) == 128
    assert flash_block_size(8, 8) is None
    assert flash_block_size(200, 200) is None
    assert flash_block_size(1024, 96) is None


@pytest.mark.usefixtures("devices")
def test_flash_partitionable_follows_the_current_mesh():
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device mesh
    the flash arm runs per shard: a candidate only where batch and heads
    split exactly (not the batch-1 init trace)."""
    from relora_tpu.ops.attention import flash_partitionable
    from relora_tpu.parallel.mesh import MeshSpec, current_mesh, make_mesh, set_current_mesh

    before = current_mesh()
    try:
        set_current_mesh(None)
        assert flash_partitionable(1, 8, 8)
        set_current_mesh(make_mesh(MeshSpec(data=1, fsdp=1), devices=jax.devices()[:1]))
        assert flash_partitionable(1, 8, 8)
        set_current_mesh(make_mesh(MeshSpec(data=1, fsdp=4, tensor=2)))
        assert flash_partitionable(4, 8, 8)
        assert not flash_partitionable(1, 8, 8)  # batch does not split over fsdp
        assert not flash_partitionable(4, 8, 1)  # kv heads do not split over tensor
        set_current_mesh(make_mesh(MeshSpec(data=1, fsdp=2, sequence=2), devices=jax.devices()[:4]))
        assert not flash_partitionable(4, 8, 8)  # sequence-sharded: ring/ulysses, not flash
    finally:
        set_current_mesh(before)
