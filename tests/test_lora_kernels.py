"""Fused LoRA kernel + dispatch tests (interpret mode on CPU).

Acceptance for ISSUE 4: the fused ``x@W + ((x@A)@B)*scale`` Pallas composite
must be numerically equivalent to the unfused reference — forward AND
gradients, per-dtype atol — for every tested shape, and dispatch
(``lora_matmul``'s arm selection) may change the compute graph but never the
numerics.  The TPU path shares the exact kernel bodies; only the
``interpret=True`` execution differs.
"""

import logging

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.core.relora import LoraSpec
from relora_tpu.models.lora import LoRALinear
from relora_tpu.ops.lora_dispatch import (
    ARMS,
    choose_arm,
    estimate_arm_times,
    lora_matmul,
    plan_blocks,
)
from relora_tpu.ops.pallas_lora_matmul import (
    fused_lora_matmul,
    fused_lora_matmul_int8,
)
from relora_tpu.ops.quant import dequantize_int8, quantize_int8

# Per-dtype forward/grad tolerance: both paths accumulate in f32, so f32 is
# near-exact; bf16 differs by the final output rounding (and the unfused
# arms' intermediate casts), which scales with sqrt(K)-magnitude outputs.
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 0.5}


def _operands(M, K, N, r, dtype=jnp.float32, seed=0):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(k, 1), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 2), (K, N), jnp.float32)
    a = jax.random.normal(jax.random.fold_in(k, 3), (K, r), jnp.float32) * 0.1
    b = jax.random.normal(jax.random.fold_in(k, 4), (r, N), jnp.float32) * 0.1
    return tuple(t.astype(dtype) for t in (x, w, a, b))


def _reference(x, w, a, b, scale):
    """The unfused ordered composite, computed in f32."""
    x32, w32, a32, b32 = (t.astype(jnp.float32) for t in (x, w, a, b))
    return x32 @ w32 + (x32 @ a32) @ b32 * scale


def _max_err(got, want):
    return float(jnp.abs(got.astype(jnp.float32) - jnp.asarray(want)).max())


# ---------------------------------------------------------------------------
# fused kernel: forward + backward parity vs the unfused reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("r", [8, 128])
def test_fused_forward_parity(dtype, r):
    M, K, N = 64, 256, 128
    x, w, a, b = _operands(M, K, N, r, dtype)
    got = fused_lora_matmul(x, w, a, b, 0.5, block_m=32, block_n=128, interpret=True)
    assert got.dtype == dtype
    assert _max_err(got, _reference(x, w, a, b, 0.5)) < TOL[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("r", [8, 128])
def test_fused_grad_parity(dtype, r):
    """dx, dA, dB from the fused custom_vjp == grads of the unfused
    reference; the frozen base W gets a symbolically-zero cotangent."""
    M, K, N = 32, 256, 128
    x, w, a, b = _operands(M, K, N, r, dtype)

    def loss_fused(x, w, a, b, s):
        y = fused_lora_matmul(x, w, a, b, s, block_m=32, block_n=128, interpret=True)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    def loss_ref(x, w, a, b, s):
        # round y through the output dtype like the kernel does — sin() is
        # nonlinear, so comparing cotangents of a bf16 y against an f32 y
        # would measure the dtype, not the kernel
        y = _reference(x, w, a, b, s).astype(dtype)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    s = jnp.float32(0.5)
    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, w, a, b, s)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, w, a, b, s)
    for name, f_, r_ in zip("xwabs", gf, gr):
        if name == "w":
            # frozen-base contract: fused returns exactly zero for W
            assert float(jnp.abs(f_).max()) == 0.0
            continue
        assert _max_err(f_, r_.astype(jnp.float32)) < TOL[dtype], f"d{name}"


@pytest.mark.parametrize("r", [8, 128])
def test_fused_int8_parity(r):
    """Int8-base variant: dequant folded into the kernel.  Forward, dx/dA/dB,
    and the true dqscale gradient all match dequantize-then-reference."""
    M, K, N = 32, 256, 128
    x, w, a, b = _operands(M, K, N, r)
    q, qs = quantize_int8(w * 0.1)

    def loss_fused(x, qs, a, b):
        y = fused_lora_matmul_int8(
            x, q, qs, a, b, 0.5, block_m=32, block_n=128, interpret=True
        )
        return jnp.sum(jnp.sin(y))

    def loss_ref(x, qs, a, b):
        return jnp.sum(jnp.sin(_reference(x, q.astype(jnp.float32) * qs, a, b, 0.5)))

    got = fused_lora_matmul_int8(x, q, qs, a, b, 0.5, block_m=32, block_n=128, interpret=True)
    want = _reference(x, q.astype(jnp.float32) * qs, a, b, 0.5)
    assert _max_err(got, want) < 1e-4

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, qs, a, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, qs, a, b)
    for name, f_, r_ in zip(("x", "qscale", "a", "b"), gf, gr):
        denom = max(1.0, float(jnp.abs(r_).max()))
        assert _max_err(f_, r_) / denom < 1e-4, f"d{name}"


def test_fused_trainable_scale_grad():
    """ds (the trainable-scaling cotangent) matches the reference."""
    M, K, N, r = 32, 256, 128, 8
    x, w, a, b = _operands(M, K, N, r)

    def loss(s, fn):
        return jnp.sum(jnp.sin(fn(s)))

    fused = lambda s: fused_lora_matmul(x, w, a, b, s, block_m=32, block_n=128, interpret=True)
    ref = lambda s: _reference(x, w, a, b, s)
    gs_f = jax.grad(loss)(jnp.float32(0.37), fused)
    gs_r = jax.grad(loss)(jnp.float32(0.37), ref)
    np.testing.assert_allclose(float(gs_f), float(gs_r), rtol=1e-5)


def test_fused_batched_leading_dims():
    """(B, T, K) activations flatten to (B*T, K) and reshape back."""
    B, T, K, N, r = 4, 16, 256, 128, 8
    x2, w, a, b = _operands(B * T, K, N, r)
    x = x2.reshape(B, T, K)
    got = lora_matmul(x, w, a, b, 0.5, arm="fused", interpret=True)
    assert got.shape == (B, T, N)
    want = _reference(x2, w, a, b, 0.5).reshape(B, T, N)
    assert _max_err(got, want) < 1e-4


def test_fused_validation_errors():
    x, w, a, b = _operands(32, 256, 128, 8)
    with pytest.raises(ValueError, match="tile"):
        fused_lora_matmul(x[:30], w, a, b, 1.0, block_m=8, block_n=128, interpret=True)
    with pytest.raises(ValueError, match="mismatch|shape"):
        fused_lora_matmul(x, w[:128], a, b, 1.0, block_m=32, block_n=128, interpret=True)


# ---------------------------------------------------------------------------
# dispatch: cost model + the never-changes-numerics property
# ---------------------------------------------------------------------------


def test_plan_blocks():
    assert plan_blocks(256, 256) == (256, 256)
    assert plan_blocks(40, 128) == (8, 128)  # sublane shrinks to keep tiling
    assert plan_blocks(7, 128) is None  # M has no candidate divisor
    assert plan_blocks(32, 100) is None  # N not lane-aligned


def test_choose_arm_regimes():
    """The selections the cost model exists for (docs/kernels.md)."""
    # decode-sized M with static (serving) weights: merged amortizes to a
    # bare matmul
    assert choose_arm(8, 2048, 2048, 128, weights_static=True) == "merged"
    # training-sized M on TPU: fused
    assert choose_arm(512, 2048, 2048, 128) == "fused"
    # very large M: merged wins on FLOPs alone (Run LoRA Run crossover
    # M > K*N/(K+N))
    assert choose_arm(65536, 2048, 2048, 128) == "merged"
    # fused unavailable (non-TPU backend): never fused
    assert choose_arm(512, 2048, 2048, 128, fused_available=False) != "fused"
    # untileable shape: fused struck even when nominally available
    assert choose_arm(7, 2048, 2048, 128) != "fused"
    # allow= restricts the candidate set
    assert choose_arm(512, 2048, 2048, 128, allow=("ordered",)) == "ordered"


def test_estimate_arm_times_sane():
    t = estimate_arm_times(512, 2048, 2048, 128)
    assert set(t) == set(ARMS)
    assert all(v > 0 for v in t.values())
    # fused reads strictly fewer bytes with fewer launches than ordered
    assert t["fused"] < t["ordered"]


@pytest.mark.parametrize("quantized", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("M", [8, 32, 4096])
def test_dispatch_never_changes_numerics(M, quantized):
    """The property the whole dispatcher rests on: every arm (and auto, and
    both weights_static settings) produces the same value within tolerance —
    dispatch changes the compute graph, never the result."""
    K, N, r = 256, 128, 8
    x, w, a, b = _operands(M, K, N, r, seed=M)
    base = quantize_int8(w * 0.1) if quantized else w
    wd = dequantize_int8(*base, jnp.float32) if quantized else w
    want = _reference(x, wd, a, b, 0.25)

    arms = list(ARMS) + ["auto"]
    for arm in arms:
        for ws in (False, True):
            got = lora_matmul(
                x, base, a, b, 0.25, arm=arm, weights_static=ws, interpret=True
            )
            assert _max_err(got, want) < 1e-4, f"arm={arm} weights_static={ws}"


def test_dispatch_grads_arm_independent():
    """d(x, a, b) agree across arms (the base is stop_gradient'd by the
    module caller; here we diff only the trainable operands)."""
    M, K, N, r = 32, 256, 128, 8
    x, w, a, b = _operands(M, K, N, r)

    def loss(x, a, b, arm):
        y = lora_matmul(x, jax.lax.stop_gradient(w), a, b, 0.25, arm=arm, interpret=True)
        return jnp.sum(jnp.sin(y))

    ref = jax.grad(loss, argnums=(0, 1, 2))(x, a, b, "ordered")
    for arm in ("fused", "merged", "auto"):
        got = jax.grad(loss, argnums=(0, 1, 2))(x, a, b, arm)
        for name, g_, r_ in zip("xab", got, ref):
            denom = max(1.0, float(jnp.abs(r_).max()))
            assert _max_err(g_, r_) / denom < 1e-4, f"arm={arm} d{name}"


def test_dispatch_untileable_forced_arm_raises():
    """Forcing arm="fused" on a shape with no block plan raises — a forced
    arm is never quietly swapped for another — while "auto" strikes the
    fused arm for that shape and agrees with the ordered path."""
    M, K, N, r = 7, 256, 100, 8  # neither M nor N tiles
    x, w, a, b = _operands(M, K, N, r)
    with pytest.raises(ValueError, match="no block plan"):
        lora_matmul(x, w, a, b, 0.25, arm="fused", interpret=True)
    auto = lora_matmul(x, w, a, b, 0.25, arm="auto")
    ordered = lora_matmul(x, w, a, b, 0.25, arm="ordered")
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ordered), rtol=1e-3, atol=1e-3)


def test_dispatch_rejects_unknown_arm():
    x, w, a, b = _operands(8, 256, 128, 8)
    with pytest.raises(ValueError, match="unknown arm"):
        lora_matmul(x, w, a, b, arm="bogus")


def test_auto_never_interprets_on_cpu():
    """On a non-TPU backend, arm="auto" must not pick the fused interpreter."""
    M, K, N, r = 512, 256, 128, 8
    assert jax.default_backend() != "tpu"
    arm = choose_arm(M, K, N, r, fused_available=jax.default_backend() == "tpu")
    assert arm != "fused"


# ---------------------------------------------------------------------------
# module integration: LoRALinear with spec.fused
# ---------------------------------------------------------------------------


def _init(model, x, seed=0):
    return nn.meta.unbox(model.init(jax.random.PRNGKey(seed), x, deterministic=True))


def _perturb_lora_b(params, seed=9):
    """lora_b is zeros at init (init-equivalence invariant); perturb it so
    the LoRA branch actually contributes and parity tests bite."""
    p = jax.tree_util.tree_map(lambda t: t, params)
    b = p["params"]["lora_b"]
    p["params"]["lora_b"] = jax.random.normal(jax.random.PRNGKey(seed), b.shape, b.dtype) * 0.1
    return p


@pytest.mark.parametrize("quantize", [None, "int8"], ids=["dense", "int8"])
@pytest.mark.parametrize("fused", [True, "auto"], ids=["fused", "auto"])
@pytest.mark.parametrize("trainable_scaling", [False, True], ids=["static-s", "tanh-s"])
def test_module_fused_matches_unfused(quantize, fused, trainable_scaling):
    """LoRALinear(spec.fused) == LoRALinear(historical) — same param tree,
    same forward — for dense and int8 bases, with bias, both scale modes."""
    spec_kw = dict(r=8, alpha=16, trainable_scaling=trainable_scaling)
    m_ref = LoRALinear(
        features=128, use_bias=True, lora=LoraSpec(**spec_kw),
        dtype=jnp.float32, quantize=quantize,
    )
    m_fused = LoRALinear(
        features=128, use_bias=True, lora=LoraSpec(fused=fused, **spec_kw),
        dtype=jnp.float32, quantize=quantize,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    p = _perturb_lora_b(_init(m_ref, x))
    # identical param trees: both paths define the same name-keyed leaves
    p_fused = _init(m_fused, x)
    assert jax.tree_util.tree_structure(p) == jax.tree_util.tree_structure(p_fused)

    want = m_ref.apply(p, x, deterministic=True)
    got = m_fused.apply(p, x, deterministic=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_module_fused_grads_match_unfused():
    """Training-relevant parity: d(lora_a, lora_b) identical across paths;
    the frozen kernel gets zero grad under dispatch (stop_gradient contract —
    the optimizer mask never applies base updates either way)."""
    spec = dict(r=8, alpha=16)
    m_ref = LoRALinear(features=128, lora=LoraSpec(**spec), dtype=jnp.float32)
    m_fused = LoRALinear(features=128, lora=LoraSpec(fused=True, **spec), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    p = _perturb_lora_b(_init(m_ref, x))

    def loss(params, model):
        return jnp.sum(model.apply(params, x, deterministic=True) ** 2)

    g_ref = jax.grad(loss)(p, m_ref)["params"]
    g_fused = jax.grad(loss)(p, m_fused)["params"]
    for leaf in ("lora_a", "lora_b"):
        np.testing.assert_allclose(
            np.asarray(g_fused[leaf]), np.asarray(g_ref[leaf]), atol=1e-4
        )
    assert float(jnp.abs(g_fused["kernel"]).max()) == 0.0


def test_module_dropout_keeps_historical_path():
    """Dropout-active calls can't fuse (branch input differs from base
    input): spec.fused must still produce the historical dropout forward."""
    spec = LoraSpec(r=8, alpha=16, dropout=0.5, fused=True)
    m = LoRALinear(features=128, lora=spec, dtype=jnp.float32)
    m_ref = LoRALinear(
        features=128, lora=LoraSpec(r=8, alpha=16, dropout=0.5), dtype=jnp.float32
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    p = _perturb_lora_b(_init(m, x))
    rng = {"dropout": jax.random.PRNGKey(3)}
    got = m.apply(p, x, deterministic=False, rngs=rng)
    want = m_ref.apply(p, x, deterministic=False, rngs=rng)
    # same dropout mask (same rng), same math -> identical outputs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    # and the deterministic call dispatches without dropout
    det = m.apply(p, x, deterministic=True)
    assert bool(jnp.isfinite(det).all())


def test_module_untileable_width_falls_back():
    """features=100 never lane-aligns: the module pins the fused arm only
    where a block plan exists, so the dispatched path must still be correct."""
    m_ref = LoRALinear(features=100, lora=LoraSpec(r=8, alpha=16), dtype=jnp.float32)
    m_fused = LoRALinear(
        features=100, lora=LoraSpec(r=8, alpha=16, fused=True), dtype=jnp.float32
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 64))
    p = _perturb_lora_b(_init(m_ref, x))
    np.testing.assert_allclose(
        np.asarray(m_fused.apply(p, x, deterministic=True)),
        np.asarray(m_ref.apply(p, x, deterministic=True)),
        atol=1e-5,
    )


def test_lora_spec_validates_fused():
    with pytest.raises(ValueError, match="fused"):
        LoraSpec(r=8, alpha=16, fused="sometimes")
    for ok in (True, False, "auto"):
        LoraSpec(r=8, alpha=16, fused=ok)


def test_pallas_quant_env_hoisted_to_construction(monkeypatch):
    """RELORA_TPU_PALLAS_QUANT is read once at module construction, never in
    the traced __call__ (the RTL1xx retrace footgun).  Flipping the env after
    construction must not change behavior; the explicit field wins over env."""
    monkeypatch.delenv("RELORA_TPU_PALLAS_QUANT", raising=False)
    m_off = LoRALinear(features=128, quantize="int8", lora=LoraSpec(r=4, alpha=8))
    assert m_off.pallas_quant is False
    monkeypatch.setenv("RELORA_TPU_PALLAS_QUANT", "1")
    m_on = LoRALinear(features=128, quantize="int8", lora=LoraSpec(r=4, alpha=8))
    assert m_on.pallas_quant is True
    # flipping the env after construction does not retro-affect the module
    monkeypatch.delenv("RELORA_TPU_PALLAS_QUANT", raising=False)
    assert m_on.pallas_quant is True
    # explicit field beats env
    assert LoRALinear(features=8, pallas_quant=False).pallas_quant is False


def test_dequant_matmul_bwd_warns_once():
    """Satellite fix: the standalone int8 kernel's backward fallback
    (dequantize-then-matmul) logs once per shape at trace time instead of
    silently misattributing backward cost in kernel benchmarks."""
    from relora_tpu.ops.pallas_quant_matmul import _BWD_FALLBACK_WARNED, dequant_matmul

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128)) * 0.1
    q, s = quantize_int8(w)
    _BWD_FALLBACK_WARNED.discard((8, 64, 128))  # isolate from suite ordering

    def loss(x):
        return jnp.sum(dequant_matmul(x, q, s, block_m=8, block_n=128, interpret=True))

    # capture on the module logger directly: utils/logging.get_logger sets
    # propagate=False on the "relora_tpu" parent, so caplog's root handler
    # would miss these records once any other test has configured logging
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    module_logger = logging.getLogger("relora_tpu.ops.pallas_quant_matmul")
    handler = _Capture(level=logging.INFO)
    old_level = module_logger.level
    module_logger.addHandler(handler)
    module_logger.setLevel(logging.INFO)
    try:
        jax.grad(loss)(x)
        jax.grad(loss)(x)
    finally:
        module_logger.removeHandler(handler)
        module_logger.setLevel(old_level)
    hits = [r for r in records if "fallback" in r.getMessage()]
    assert len(hits) == 1
