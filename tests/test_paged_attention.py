"""Fused paged-decode attention kernel + attention dispatch.

The kernel (ops/attention.paged_decode_attention) reads the page pool
directly through the block table — no gathered cache copy, no
``(B, heads, 1, S_kv)`` score matrix in HBM — so its only oracle is the
naive gather arm (ops/attention.paged_cached_attention), which these tests
hold it to in Pallas interpret mode on CPU, for bf16-stored and
int8-quantized pools.  The dispatcher tests mirror tests/test_lora_kernels:
dispatch changes the compute graph, never the result, and never picks the
interpreter on a non-TPU backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.ops.attention import (
    decode_pages_per_step,
    dot_product_attention,
    paged_cached_attention,
    paged_decode_attention,
)
from relora_tpu.ops.attention_dispatch import (
    ARMS,
    TRAIN_ARMS,
    choose_arm,
    choose_training_arm,
    estimate_arm_times,
    estimate_training_arm_times,
    paged_attention,
)
from relora_tpu.ops.quant import quantize_kv_page


def _max_err(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def _pool_case(seed, *, B=2, heads=4, kv_heads=2, head_dim=8, page_size=4, W=3):
    """A decode step against a shared pool: every row owns W pages, rows sit
    at staggered positions (ragged visibility), and unallocated pool pages
    hold garbage that only the mask keeps out of the result."""
    key = jax.random.PRNGKey(seed)
    num_pages = B * W + 3  # + null page + 2 never-referenced garbage pages
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, 1, heads, head_dim), jnp.float32)
    pool_k = jax.random.normal(ks[1], (num_pages, page_size, kv_heads, head_dim))
    pool_v = jax.random.normal(ks[2], (num_pages, page_size, kv_heads, head_dim))
    # rows own disjoint pages, deliberately not in pool order
    perm = np.random.default_rng(seed).permutation(B * W) + 1
    bt = jnp.asarray(perm.reshape(B, W), jnp.int32)
    # staggered positions: row 0 has a single visible token, last row is full
    pos = jnp.linspace(0, W * page_size - 1, B).astype(jnp.int32).reshape(B, 1)
    return q, pool_k, pool_v, bt, pos


@pytest.mark.parametrize("seed", [0, 1])
def test_fused_decode_matches_naive_bf16_pool(seed):
    q, pk, pv, bt, pos = _pool_case(seed)
    want = paged_cached_attention(q, pk, pv, bt, pos)
    got = paged_decode_attention(q, pk, pv, bt, pos, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _max_err(got, want) < 1e-5


@pytest.mark.parametrize("seed", [0, 1])
def test_fused_decode_matches_naive_int8_pool(seed):
    q, pk, pv, bt, pos = _pool_case(seed)
    qk, k_scale = quantize_kv_page(pk)
    qv, v_scale = quantize_kv_page(pv)
    want = paged_cached_attention(q, qk, qv, bt, pos, k_scale=k_scale, v_scale=v_scale)
    got = paged_decode_attention(
        q, qk, qv, bt, pos, k_scale=k_scale, v_scale=v_scale, interpret=True
    )
    assert _max_err(got, want) < 1e-5
    # and the int8 arm sits near the float result (quantization error only)
    ref = paged_cached_attention(q, pk, pv, bt, pos)
    assert _max_err(got, ref) < 0.05


def test_fused_decode_gqa_and_custom_scale():
    """Grouped heads (heads > kv_heads) with an explicit softmax scale."""
    q, pk, pv, bt, pos = _pool_case(3, heads=8, kv_heads=2, head_dim=16)
    want = paged_cached_attention(q, pk, pv, bt, pos, scale=0.5)
    got = paged_decode_attention(q, pk, pv, bt, pos, scale=0.5, interpret=True)
    assert _max_err(got, want) < 1e-5


def test_fused_decode_position_zero_row():
    """A row at position 0 (one visible token) must not NaN — the online
    softmax sees exactly one unmasked entry at w=0."""
    q, pk, pv, bt, pos = _pool_case(4)
    pos = jnp.zeros_like(pos)
    got = paged_decode_attention(q, pk, pv, bt, pos, interpret=True)
    want = paged_cached_attention(q, pk, pv, bt, pos)
    assert np.isfinite(np.asarray(got)).all()
    assert _max_err(got, want) < 1e-5


def _verify_case(seed, S, *, B=2, heads=4, kv_heads=2, head_dim=8, page_size=4, W=3):
    """A speculative verify window: S query tokens per row at consecutive
    positions, each row staggered so the visibility frontier lands at
    different page offsets (mid-page, page boundary, last page)."""
    q1, pk, pv, bt, base = _pool_case(seed, B=B, heads=heads, kv_heads=kv_heads,
                                      head_dim=head_dim, page_size=page_size, W=W)
    q = jax.random.normal(
        jax.random.PRNGKey(seed + 100), (B, S, heads, head_dim), jnp.float32
    )
    # per-token positions p..p+S-1, capped inside the table's capacity
    pos = jnp.minimum(base + jnp.arange(S)[None, :], W * page_size - 1)
    return q, pk, pv, bt, pos.astype(jnp.int32)


@pytest.mark.parametrize("S", [2, 4])
@pytest.mark.parametrize("seed", [0, 1])
def test_fused_verify_small_s_matches_naive(seed, S):
    """The speculative verify window: (B, S) queries at per-token positions
    must match the naive gather arm — each query row's visibility mask is
    independent, garbage beyond its own position stays masked."""
    q, pk, pv, bt, pos = _verify_case(seed, S)
    want = paged_cached_attention(q, pk, pv, bt, pos)
    got = paged_decode_attention(q, pk, pv, bt, pos, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _max_err(got, want) < 1e-5


def test_fused_verify_small_s_int8_pool():
    q, pk, pv, bt, pos = _verify_case(2, 4)
    qk, k_scale = quantize_kv_page(pk)
    qv, v_scale = quantize_kv_page(pv)
    want = paged_cached_attention(q, qk, qv, bt, pos, k_scale=k_scale, v_scale=v_scale)
    got = paged_decode_attention(
        q, qk, qv, bt, pos, k_scale=k_scale, v_scale=v_scale, interpret=True
    )
    assert _max_err(got, want) < 1e-5


def test_fused_verify_broadcast_positions():
    """(B,) / (B, 1) positions broadcast over the S query tokens — every
    token sees the same frontier, matching the naive arm fed (B, S)."""
    q, pk, pv, bt, pos1 = _pool_case(7)
    q = jnp.concatenate([q, q * 0.5, q * 2.0], axis=1)  # S=3
    want = paged_cached_attention(q, pk, pv, bt, jnp.broadcast_to(pos1, (q.shape[0], 3)))
    got_flat = paged_decode_attention(q, pk, pv, bt, pos1.reshape(-1), interpret=True)
    got_col = paged_decode_attention(q, pk, pv, bt, pos1, interpret=True)
    assert _max_err(got_flat, want) < 1e-5
    assert _max_err(got_col, want) < 1e-5


def test_fused_decode_requires_both_scales():
    q, pk, pv, bt, pos = _pool_case(6)
    qk, k_scale = quantize_kv_page(pk)
    with pytest.raises(ValueError, match="k_scale"):
        paged_decode_attention(q, qk, pv, bt, pos, k_scale=k_scale, interpret=True)


# ---------------------------------------------------------------------------
# the walk: only a row's live pages, P table entries a step
# ---------------------------------------------------------------------------

PAGE = 16  # with 128 tokens a step: P = 8 table entries


def _walk_case(
    seed, last_positions, *, W, S=1, heads=2, kv_heads=2, head_dim=32, kv="f32",
    stray_tail=False,
):
    """Rows of the given last positions over one pool of 16-token pages.  A
    row owns the pages its queries can see, in scrambled pool order; a row at
    position 0 with ``None`` in ``last_positions`` rides on an all-null
    table, as an idle slot does.  Every page no row owns (the null page
    among them) holds garbage — with ``stray_tail`` the table entries past a
    row's last live one point at such pages instead of the null page.
    Returns the kernel's arguments, its scales (``{}`` unless ``kv`` is
    int8) and the mask of owned pool pages."""
    B = len(last_positions)
    rng = np.random.default_rng(seed)
    need = [0 if p is None else p // PAGE + 1 for p in last_positions]
    num_pages = 1 + sum(need) + 4
    perm = rng.permutation(np.arange(1, num_pages))
    owned = np.zeros(num_pages, bool)
    bt = np.zeros((B, W), np.int32)
    strays = perm[sum(need):]
    at = 0
    for b, n in enumerate(need):
        bt[b, :n] = perm[at : at + n]
        owned[perm[at : at + n]] = True
        at += n
        if stray_tail and n:
            bt[b, n:] = strays[np.arange(W - n) % len(strays)]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, heads, head_dim), jnp.float32)
    pool = [
        jax.random.normal(k, (num_pages, PAGE, kv_heads, head_dim), jnp.float32)
        for k in ks[1:]
    ]
    # per-token positions p-S+1 .. p: the window ends at the row's last position
    last = np.array([0 if p is None else p for p in last_positions])
    pos = np.maximum(last[:, None] - (S - 1) + np.arange(S)[None, :], 0)
    scales = {}
    if kv == "int8":
        (pool[0], scales["k_scale"]), (pool[1], scales["v_scale"]) = (
            quantize_kv_page(x) for x in pool
        )
    elif kv == "bf16":
        pool = [x.astype(jnp.bfloat16) for x in pool]
    return (q, pool[0], pool[1], jnp.asarray(bt), jnp.asarray(pos, jnp.int32)), scales, owned


#: beside each other in one batch: an idle slot on an all-null table, a row
#: that fills all 20 table entries (three steps, the last of 4 entries: the
#: table is no multiple of P), one that ends mid-page, one that ends on the
#: last token of a step's last page, and one on the first token of the next
RAGGED = [None, 20 * PAGE - 1, 37, 8 * PAGE - 1, 8 * PAGE]


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("S", [1, 5, 16])
@pytest.mark.parametrize("g", [1, 4])
def test_fused_walk_matches_naive(g, S, kv):
    args, scales, _ = _walk_case(g * 10 + S, RAGGED, W=20, S=S, heads=2 * g, kv=kv)
    want = paged_cached_attention(*args, **scales)
    got = paged_decode_attention(*args, **scales, interpret=True)
    assert got.shape == want.shape and np.isfinite(np.asarray(got)).all()
    assert _max_err(got, want) < 2e-5


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kv_heads, head_dim", [(16, 128), (8, 256)])
def test_fused_walk_at_real_head_shapes(kv_heads, head_dim, kv):
    """The serving cell's heads (16 x 128) and the trainer's (8 x 256)."""
    args, scales, _ = _walk_case(
        5, RAGGED, W=20, heads=kv_heads, kv_heads=kv_heads, head_dim=head_dim, kv=kv
    )
    want = paged_cached_attention(*args, **scales)
    got = paged_decode_attention(*args, **scales, interpret=True)
    assert _max_err(got, want) < 2e-5


@pytest.mark.parametrize("W", [3, 8, 16, 20, 27])
def test_fused_walk_any_table_width(W):
    """Narrower than a step, exactly one and two steps, and widths that
    leave a last step of 4 and of 3 entries; a full row beside a short one."""
    args, scales, _ = _walk_case(W, [W * PAGE - 1, PAGE + 2, None], W=W)
    want = paged_cached_attention(*args, **scales)
    got = paged_decode_attention(*args, **scales, interpret=True)
    assert _max_err(got, want) < 2e-5


@pytest.mark.parametrize("S", [1, 5])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_fused_walk_never_reads_a_page_the_row_does_not_own(kv, S):
    """Poison every pool page no row owns — the null page, and the pages the
    tables' tails point at: NaN in a bf16 pool, +-127 codes under huge scales
    in an int8 one.  The gather oracle would carry the poison into its sums
    (0 x NaN); the walk never copies such a page, so its output is finite and
    bit for bit what the clean pool gives."""
    # (an idle slot attends token 0 of the null page by design: here the row
    # at position 0 owns its page)
    args, scales, owned = _walk_case(
        11, [0] + RAGGED[1:], W=20, S=S, heads=4, kv=kv, stray_tail=True
    )
    q, pk, pv, bt, pos = args
    clean = paged_decode_attention(*args, **scales, interpret=True)
    assert _max_err(clean, paged_cached_attention(*args, **scales)) < 2e-5
    foreign = jnp.asarray(~owned)[:, None, None, None]
    if kv == "int8":
        sign = jnp.where(jnp.arange(pk.shape[1]) % 2 == 0, 127, -127).astype(jnp.int8)
        pk, pv = (jnp.where(foreign, sign[None, :, None, None], x) for x in (pk, pv))
        scales = {
            name: jnp.where(foreign[:, 0, :, 0], 1e30, x) for name, x in scales.items()
        }
    else:
        pk, pv = (jnp.where(foreign, jnp.nan, x) for x in (pk, pv))
    got = paged_decode_attention(q, pk, pv, bt, pos, **scales, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize(
    "page_size, n_kv, head_dim, itemsize, W, want",
    [
        (16, 16, 128, 2, 128, 8),  # the serving cell: 128 tokens a step
        (16, 8, 256, 2, 128, 8),  # pythia_1b's heads: the same bytes a page
        (16, 16, 128, 1, 128, 8),  # int8 codes
        (16, 8, 128, 2, 5, 5),  # never more entries than the table has
        (32, 8, 128, 2, 64, 4),
        (256, 8, 128, 2, 8, 1),  # a page longer than a step: one page a step
        (16, 32, 256, 4, 128, 2),  # f32, 512 KiB a page: the VMEM budget binds
    ],
)
def test_decode_pages_per_step_follows_the_shapes(page_size, n_kv, head_dim, itemsize, W, want):
    assert decode_pages_per_step(page_size, n_kv, head_dim, itemsize, W) == want


# ---------------------------------------------------------------------------
# dispatch (ops/attention_dispatch) — lora_dispatch mold
# ---------------------------------------------------------------------------


def test_estimate_arm_times_sane():
    t = estimate_arm_times(4, 1, 2048, 32, 8, 128, 16)
    assert set(t) == set(ARMS)
    assert all(v > 0 for v in t.values())
    # the fused arm moves strictly fewer bytes with fewer launches
    assert t["paged_decode"] < t["naive"]
    # int8 halves the cache traffic, so the fused estimate drops further
    t8 = estimate_arm_times(4, 1, 2048, 32, 8, 128, 16, kv_bytes=1)
    assert t8["paged_decode"] < t["paged_decode"]


def test_choose_arm_regimes():
    # single-token decode on TPU -> fused kernel
    assert choose_arm(4, 1, 2048, 32, 8, 128, 16) == "paged_decode"
    # same shape, fused unavailable (CPU) -> naive
    assert choose_arm(4, 1, 2048, 32, 8, 128, 16, fused_available=False) == "naive"
    # pure causal prefill, 128-aligned -> flash
    assert choose_arm(1, 512, 512, 32, 8, 128, 16) == "flash"
    # speculative verify window (small S) on TPU -> fused kernel
    assert choose_arm(4, 5, 2048, 32, 8, 128, 16) == "paged_decode"
    # chunked prefill (S beyond the verify cap): neither pallas arm applies
    assert choose_arm(1, 64, 512, 32, 8, 128, 16) == "naive"
    # allow= restricts the candidate set (the paged entry point never
    # considers flash — it is not servable from a pool)
    assert choose_arm(1, 512, 512, 32, 8, 128, 16, allow=("naive", "paged_decode")) == "naive"


def test_auto_never_interprets_on_cpu():
    """On a non-TPU backend, arm="auto" must not pick the fused interpreter."""
    assert jax.default_backend() != "tpu"
    arm = choose_arm(
        4, 1, 2048, 32, 8, 128, 16, fused_available=jax.default_backend() == "tpu"
    )
    assert arm != "paged_decode"


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_dispatch_never_changes_numerics(quantized):
    """Every servable arm (and auto) produces the same value within
    tolerance — dispatch changes the compute graph, never the result."""
    q, pk, pv, bt, pos = _pool_case(7)
    kw = {}
    if quantized:
        pk, k_scale = quantize_kv_page(pk)
        pv, v_scale = quantize_kv_page(pv)
        kw = {"k_scale": k_scale, "v_scale": v_scale}
    want = paged_cached_attention(q, pk, pv, bt, pos, **kw)
    for arm in ("naive", "paged_decode", "auto"):
        got = paged_attention(q, pk, pv, bt, pos, arm=arm, interpret=True, **kw)
        assert _max_err(got, want) < 1e-5, f"arm={arm}"
    # auto on CPU resolves to the naive arm: bitwise-identical, no interpreter
    auto = paged_attention(q, pk, pv, bt, pos, arm="auto", **kw)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(want))


def test_dispatch_rejects_unknown_arm():
    q, pk, pv, bt, pos = _pool_case(8)
    with pytest.raises(ValueError, match="unknown/unservable"):
        paged_attention(q, pk, pv, bt, pos, arm="flash")


# ---------------------------------------------------------------------------
# training dispatch (choose_training_arm) — replaces the old
# RELORA_TPU_PALLAS_MIN_SEQ threshold with a fwd+bwd roofline ranking
# ---------------------------------------------------------------------------


def test_estimate_training_arm_times_ranking():
    """At the flagship training shape (B=4, S=1024, 16 heads, d=64) the
    fwd+bwd model must rank flash < xla < naive: flash skips masked causal
    blocks and never materializes the S² score matrix; naive pays f32 score
    traffic four ways."""
    t = estimate_training_arm_times(4, 1024, 16, 16, 64, act_bytes=2)
    assert set(t) == set(TRAIN_ARMS)
    assert all(v > 0 for v in t.values())
    assert t["flash"] < t["xla"] < t["naive"]
    # the backward roughly triples every arm's cost, preserving the order
    fwd = estimate_training_arm_times(4, 1024, 16, 16, 64, act_bytes=2, with_backward=False)
    assert all(t[a] > fwd[a] for a in TRAIN_ARMS)
    assert fwd["flash"] < fwd["xla"] < fwd["naive"]


def test_choose_training_arm_regimes():
    # flagship shape on TPU -> flash kernel
    assert choose_training_arm(4, 1024, 16, 16, 64) == "flash"
    # same shape off-TPU: flash struck, xla wins (never naive)
    assert choose_training_arm(4, 1024, 16, 16, 64, fused_available=False) == "xla"
    # non-128-tileable S strikes flash even with the kernel available
    assert choose_training_arm(4, 96, 16, 16, 64) != "flash"
    # allow= restricts the candidate set
    assert choose_training_arm(4, 1024, 16, 16, 64, allow=("naive",)) == "naive"
    # empty candidate set degrades to the safe default
    assert (
        choose_training_arm(4, 1024, 16, 16, 64, fused_available=False, allow=("flash",))
        == "xla"
    )


def _train_qkv(seed, *, B=2, S=64, heads=4, kv_heads=2, head_dim=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, heads, head_dim), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, kv_heads, head_dim), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, kv_heads, head_dim), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("impl", ["xla", "pallas", "auto"])
def test_training_forced_arm_parity(impl):
    """Every CPU-runnable training arm matches the naive f32 oracle in value
    AND gradient — dispatch changes the compute graph, never the result.
    (``pallas`` at this sub-tile S exercises the kernel's fused-XLA fallback;
    the on-TPU kernel itself is held to the same oracle by the bench's
    attention mode.)"""
    q, k, v = _train_qkv(0)
    want = dot_product_attention(q, k, v, impl="naive")
    got = dot_product_attention(q, k, v, impl=impl)
    assert got.shape == want.shape
    assert _max_err(got, want) < 1e-5, f"impl={impl}"

    def loss(fn_impl):
        return lambda qq: jnp.sum(dot_product_attention(qq, k, v, impl=fn_impl) ** 2)

    g_want = jax.grad(loss("naive"))(q)
    g_got = jax.grad(loss(impl))(q)
    assert _max_err(g_got, g_want) < 1e-4, f"impl={impl} (backward)"


def test_training_auto_on_cpu_is_xla_bitwise():
    """Off-TPU the dispatcher must resolve auto to the xla arm (flash is
    struck, and the model ranks xla under naive) — bitwise, no interpreter."""
    assert jax.default_backend() != "tpu"
    q, k, v = _train_qkv(1)
    auto = dot_product_attention(q, k, v, impl="auto")
    forced = dot_product_attention(q, k, v, impl="xla")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))


# ---------------------------------------------------------------------------
# K heads wider than V heads, a window through a ring table, a sink score
# ---------------------------------------------------------------------------


def _ring_case(seed, positions, *, ring, window, heads, kv_heads, dk, dv, sink):
    """Rows at ``positions`` over ring tables of ``ring`` pages (``None``: a
    table as wide as the row needs): logical page ``p`` of a row lives in
    entry ``p % ring``, older pages overwritten, so the pool holds only what a
    window of ``window`` can still see.  Returns the kernel's arguments and an
    oracle computed in numpy float64 from the row's own token list."""
    rng = np.random.default_rng(seed)
    B = len(positions)
    W = ring or max(p // PAGE + 1 for p in positions)
    num_pages = 1 + B * W
    bt = 1 + np.arange(B * W, dtype=np.int32).reshape(B, W)
    q = rng.standard_normal((B, 1, heads, dk)).astype(np.float32)
    pool_k = rng.standard_normal((num_pages, PAGE, kv_heads, dk)).astype(np.float32)  # garbage
    pool_v = rng.standard_normal((num_pages, PAGE, kv_heads, dv)).astype(np.float32)
    s = rng.standard_normal(heads).astype(np.float32) if sink else None
    want = np.zeros((B, 1, heads, dv))
    g = heads // kv_heads
    for b, p in enumerate(positions):
        k = rng.standard_normal((p + 1, kv_heads, dk))
        v = rng.standard_normal((p + 1, kv_heads, dv))
        for t in range(p + 1):  # written in order: a ring keeps the newest
            pool_k[bt[b, (t // PAGE) % W], t % PAGE] = k[t]
            pool_v[bt[b, (t // PAGE) % W], t % PAGE] = v[t]
        lo = max(0, p - window + 1) if window else 0
        for n in range(heads):
            scores = k[lo:, n // g] @ q[b, 0, n].astype(np.float64) * dk**-0.5
            top = max(scores.max(), s[n]) if sink else scores.max()
            e = np.exp(scores - top)
            denom = e.sum() + (np.exp(s[n] - top) if sink else 0.0)
            want[b, 0, n] = (e / denom) @ v[lo:, n // g]
    args = (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(bt), jnp.asarray(positions, jnp.int32)[:, None])
    kw = dict(window=window, sink=None if s is None else jnp.asarray(s))
    return args, kw, want


@pytest.mark.parametrize(
    "ring, window, sink, heads, kv_heads",
    [
        (None, None, False, 8, 2),  # a global layer: K 24 wide, V 16, grouped queries
        (None, None, True, 8, 2),  # ... with a sink
        (5, 40, True, 8, 2),  # a window layer through a 5-page ring, wrapped many times over
        (5, 40, False, 4, 4),  # every head at once (g*S == 1), no sink
        (9, 128, True, 4, 4),  # the published window: 9 pages seen, two steps of 8
    ],
)
def test_fused_walk_unlike_head_sizes_window_ring_and_sink(ring, window, sink, heads, kv_heads):
    """Against an f64 oracle that never saw a page: the first live page is
    where the window starts, found through the ring, and the sink joins the
    denominator only.  Rows before the window fills, mid-page, and far past
    several wraps of the ring."""
    positions = [0, 7, 38, 131, 16 * 23 + 5]
    args, kw, want = _ring_case(3, positions, ring=ring, window=window, heads=heads, kv_heads=kv_heads, dk=24, dv=16, sink=sink)
    got = paged_decode_attention(*args, **kw, interpret=True)
    assert got.shape == want.shape
    assert np.abs(np.asarray(got, np.float64) - want).max() < 2e-5
    # the gather arm computes the same through ring_key_positions
    naive = paged_cached_attention(*args, **kw)
    assert np.abs(np.asarray(naive, np.float64) - want).max() < 2e-5


def test_sink_and_window_change_the_result():
    args, kw, want = _ring_case(4, [50, 90], ring=5, window=40, heads=4, kv_heads=2, dk=24, dv=16, sink=True)
    for drop in ({"sink": None}, {"window": 39}):
        got = paged_decode_attention(*args, **{**kw, **drop}, interpret=True)
        assert np.abs(np.asarray(got, np.float64) - want).max() > 1e-3
