"""The shared bench measurement core (relora_tpu/utils/benchlib.py) and the
attention-impl fallbacks the benches rely on."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.ops.attention import dot_product_attention


def test_benchlib_runs_and_reports():
    from relora_tpu.utils.benchlib import run_throughput_bench

    res = run_throughput_bench(
        "llama_9m", micro_batch=2, seq=32, remat=True, warmup_steps=1, measure_steps=2
    )
    assert res["tokens_per_sec"] > 0
    assert 0 <= res["mfu"] < 1  # rounds to 0.0 on CPU vs the TPU peak
    assert res["tokens_per_update"] == 64
    assert np.isfinite(res["loss"])


def test_benchlib_magnitude_reset_path():
    from relora_tpu.utils.benchlib import run_throughput_bench

    res = run_throughput_bench(
        "llama_9m",
        micro_batch=2,
        seq=32,
        remat=True,
        warmup_steps=1,
        measure_steps=1,
        magnitude_reset=True,
    )
    assert np.isfinite(res["loss"])


def test_remat_policy_dots_matches_full():
    """'dots' saves matmul outputs instead of recomputing the whole layer;
    it must be a pure scheduling change — same losses as 'full'."""
    from relora_tpu.utils.benchlib import run_throughput_bench

    losses = {}
    for policy in ("full", "dots", "dots_narrow"):
        res = run_throughput_bench(
            "llama_9m",
            micro_batch=2,
            seq=32,
            remat=True,
            remat_policy=policy,
            warmup_steps=2,
            measure_steps=1,
        )
        losses[policy] = res["loss"]
    assert np.isfinite(losses["full"])
    np.testing.assert_allclose(losses["full"], losses["dots"], rtol=1e-5)
    np.testing.assert_allclose(losses["full"], losses["dots_narrow"], rtol=1e-5)


def test_remat_policy_dots_narrow_predicate():
    """dots_narrow saves hidden-width dot outputs, recomputes wider ones and
    batched dots — checked directly against the policy callable."""
    from relora_tpu.models.params_util import remat_policy

    pol = remat_policy("dots_narrow", max_save_width=64)

    class P:
        name = "dot_general"

    class Aval:
        def __init__(self, shape):
            self.shape = shape

    dn = lambda rhs_c, batch=(): {"dimension_numbers": (((1,), rhs_c), (batch, batch))}
    # hidden-width projection (rhs 64x64): saved
    assert pol(P(), Aval((8, 64)), Aval((64, 64)), **dn((0,)))
    # wide MLP projection (rhs 64x171): recomputed
    assert not pol(P(), Aval((8, 64)), Aval((64, 171)), **dn((0,)))
    # down-projection back to hidden (rhs 171x64): saved
    assert pol(P(), Aval((8, 171)), Aval((171, 64)), **dn((0,)))
    # batched dot (attention QK^T shape): recomputed regardless of width
    assert not pol(P(), Aval((2, 8, 16)), Aval((2, 16, 8)), **dn((1,), (0,)))
    # non-dot primitives: never saved
    class Q:
        name = "exp"

    assert not pol(Q(), Aval((8, 64)))
    with pytest.raises(ValueError, match="max_save_width"):
        remat_policy("dots_narrow")


def test_remat_policy_unknown_raises():
    from relora_tpu.models.params_util import remat_policy

    with pytest.raises(ValueError, match="remat policy"):
        remat_policy("bogus")


def test_enable_compile_cache_env_control(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> JAX reads it itself and the code sets
    no directory; unset -> the one fixed path inside the checkout."""
    from relora_tpu.utils import logging as rlog

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
        assert rlog.enable_compile_cache() == "/somewhere/outside"
        assert jax.config.jax_compilation_cache_dir == "sentinel"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        fixed = os.path.join(repo, ".jax_compile_cache")
        assert rlog.enable_compile_cache() == fixed == rlog.COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_bench_configs_name_real_models():
    import bench

    from relora_tpu.config.model import MODEL_ZOO

    for name, cfg in bench.BENCH_CONFIGS.items():
        assert cfg["model_name"] in MODEL_ZOO, name


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
