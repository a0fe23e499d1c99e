"""Compiles for a described TPU v5e: what the chip's compiler accepts.

The Pallas interpreter accepts kernels that Mosaic refuses (a block not
aligned to the 8x128 tiling, more VMEM than a kernel may use).  libtpu is
installed here, and it compiles for a chip that is described and not attached
— nothing runs, so these say nothing about numbers or times (chip_smoke.py
does, on the chip); they guard every later PR against a kernel of the main
path that no longer lowers, at no chip time.  At ``pythia_1b`` widths (8 heads
of 256, hidden 2048, FFN 8192, page 16) unless a case says otherwise.

The one file with such compiles, and the topology is described inside a
fixture (on-chip-measurement guide, section 2): only one process may load the
TPU's library, so nothing here touches it at import or collection, and only
the worker that is given this file ever does.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_HEADS, HEAD_DIM, HIDDEN, FFN = 8, 256, 2048, 8192
PAGE, N_PAGES, TABLE_W, BATCH = 16, 512, 128, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """ShapeDtypeStruct factory on the first described chip; the persistent
    cache stays off around the compiles (an entry written for a described
    chip cannot be read back without one), and so does the process's current
    mesh: a Trainer built by an earlier test file of this worker leaves its
    CPU mesh registered, and the flash arm would shard_map over it."""
    from jax.experimental.compilation_cache import compilation_cache

    from relora_tpu.parallel.mesh import current_mesh, set_current_mesh

    was = jax.config.jax_enable_compilation_cache
    mesh_was = current_mesh()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    set_current_mesh(None)
    sharding = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    set_current_mesh(mesh_was)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool(S, dtype, n_pages=N_PAGES, n_kv=N_HEADS, head_dim=HEAD_DIM):
    return S((n_pages, PAGE, n_kv, head_dim), dtype)


@pytest.mark.parametrize(
    "batch, heads, n_kv, head_dim, n_pages, q_len, kv_dtype",
    [
        # pythia_1b's heads: decode, the speculative verify window, the int8 pool
        (BATCH, N_HEADS, N_HEADS, HEAD_DIM, N_PAGES, 1, "bfloat16"),
        (BATCH, N_HEADS, N_HEADS, HEAD_DIM, N_PAGES, 5, "bfloat16"),
        (BATCH, N_HEADS, N_HEADS, HEAD_DIM, N_PAGES, 1, "int8"),
        # serve.pythia_1.4b.chat_c32 as the benchmark runs it: 32 rows, 16 heads
        # of 128, a 1,025-page pool
        (32, 16, 16, 128, 1025, 1, "bfloat16"),
        (32, 16, 16, 128, 1025, 1, "int8"),
        # grouped queries (Llama): decode, and a verify window over an int8 pool
        (32, 32, 8, 128, 1025, 1, "bfloat16"),
        (BATCH, 32, 8, 128, N_PAGES, 5, "int8"),
    ],
)
def test_paged_decode_attention_compiles(
    one_chip, batch, heads, n_kv, head_dim, n_pages, q_len, kv_dtype
):
    from relora_tpu.ops.attention import paged_decode_attention

    S = one_chip
    pool = _pool(S, kv_dtype, n_pages, n_kv, head_dim)
    args = [
        S((batch, q_len, heads, head_dim), jnp.bfloat16),
        pool,
        pool,
        S((batch, TABLE_W), jnp.int32),
        S((batch, q_len), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [S((n_pages, n_kv), jnp.float32)] * 2

    def fn(q, k, v, bt, pos, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return paged_decode_attention(q, k, v, bt, pos, **kw)

    assert "tpu_custom_call" in compiled_text(fn, *args)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_packed_paged_attention_compiles(one_chip, kv_dtype):
    from relora_tpu.ops.attention import packed_paged_attention

    S, T = one_chip, 64
    args = [
        S((1, T, N_HEADS, HEAD_DIM), jnp.bfloat16),
        _pool(S, kv_dtype),
        _pool(S, kv_dtype),
        S((BATCH + 1, TABLE_W), jnp.int32),
        S((T,), jnp.int32),
        S((T,), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [S((N_PAGES, N_HEADS), jnp.float32)] * 2

    def fn(q, k, v, bt, rm, pos, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return packed_paged_attention(q, k, v, bt, rm, pos, **kw)

    assert "tpu_custom_call" in compiled_text(fn, *args)


def test_flash_attention_forward_backward_compiles(one_chip):
    """The trainer's attention at seq 2048, head 256, block 512."""
    from relora_tpu.ops.attention import _pallas_attention

    def fn(q, k, v):
        def loss(q, k, v):
            out = _pallas_attention(q, k, v, causal=True, scale=HEAD_DIM**-0.5)
            return out.astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    qkv = [one_chip((2, 2048, N_HEADS, HEAD_DIM), jnp.bfloat16)] * 3
    assert "tpu_custom_call" in compiled_text(fn, *qkv)


def _lora_args(S, M, K, N, r=128):
    bf = jnp.bfloat16
    return S((M, K), bf), S((K, N), bf), S((K, r), bf), S((r, N), bf)


def test_fused_lora_matmul_forward_compiles_at_decode_m(one_chip):
    """x @ W_qkv + LoRA at decode M=8 (hidden 2048 -> 3 x 2048)."""
    from relora_tpu.ops.lora_dispatch import lora_matmul

    fn = functools.partial(lora_matmul, scale=0.25, arm="fused", interpret=False)
    args = _lora_args(one_chip, BATCH, HIDDEN, 3 * HIDDEN)
    assert "tpu_custom_call" in compiled_text(lambda x, w, a, b: fn(x, w, a, b), *args)


@pytest.mark.xfail(
    strict=True,
    reason="Mosaic: 'Ran out of memory in memory space vmem ... Scoped allocation "
    "with size 16.25M and limit 16.00M' — _backward_dx holds whole-N blocks of g "
    "and W (ops/pallas_lora_matmul.py); ROADMAP A4",
)
def test_fused_lora_matmul_backward_compiles_at_ffn_width(one_chip):
    """The training shape: 4096 tokens through h_to_4h (2048 -> 8192)."""
    from relora_tpu.ops.lora_dispatch import lora_matmul

    def fn(x, w, a, b):
        def loss(x, a, b):
            y = lora_matmul(x, w, a, b, 0.25, arm="fused", interpret=False)
            return y.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(x, a, b)

    assert "tpu_custom_call" in compiled_text(fn, *_lora_args(one_chip, 4096, HIDDEN, FFN))


@pytest.mark.xfail(
    strict=True,
    reason="Pallas TPU lowering: 'the last two dimensions of your block shape are "
    "divisible by 8 and 128' — one activation row per program, block (1, K) over "
    "(T, K) (ops/pallas_lora_matmul.py _grouped_forward); ROADMAP A4",
)
def test_grouped_lora_matmul_compiles(one_chip):
    """The multi-tenant decode composite: 8 rows over 4 adapter slots."""
    from relora_tpu.ops.lora_dispatch import lora_matmul_grouped

    S, bf, slots, r = one_chip, jnp.bfloat16, 4, 128
    args = (
        S((BATCH, HIDDEN), bf),
        S((HIDDEN, 3 * HIDDEN), bf),
        S((slots, HIDDEN, r), bf),
        S((slots, r, 3 * HIDDEN), bf),
        S((slots,), jnp.float32),
        S((BATCH,), jnp.int32),
    )

    def fn(x, w, a, b, s, idx):
        return lora_matmul_grouped(x, w, a, b, s, idx, arm="grouped", interpret=False)

    assert "tpu_custom_call" in compiled_text(fn, *args)


def test_mimo_decode_program_compiles_at_the_cells_shapes(one_chip, monkeypatch):
    """``serve.mimo_v2.5.reason_c64``'s decode: 64 rows, published widths,
    seven layers of three kinds, both cache kinds at the cell's sizes.  The
    paged kernel takes K pages of 192 features stored in 256 lanes beside V
    pages of 128, walks a 25-page ring in the window layers and starts their
    softmax from the sink; the experts' grouped product is Mosaic's; and the
    plan holds the bf16 weights once (an f32 copy would be 13.7 GB)."""
    import json
    import os

    from benchmark import weights_mimo
    from relora_tpu.config.model import load_model_config
    from relora_tpu.models import step as model_step
    from relora_tpu.models.mimo import MimoForCausalLM
    from relora_tpu.models.step import StepContext
    from relora_tpu.serve.engine import _forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the dispatchers take their TPU branch
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "benchmark", "configs", "mimo_v2.5.json")
    with open(path) as f:
        raw = json.load(f)
    with open(os.path.join(root, "benchmark", "workloads", "serve.mimo_v2.5.reason_c64.json")) as f:
        w = json.load(f)
    cfg = load_model_config(path)
    B, ps = w["max_batch"], w["page_size"]
    model = MimoForCausalLM(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, decode=True, page_size=ps)
    specs = model_step.cache_specs(
        cfg, page_size=ps, num_pages=w["num_pages"], cache_size=w["cache_size"], chunk_size=w["chunk_size"],
        max_batch=B, itemsize=2,
    )
    pool = jax.tree_util.tree_map(lambda s: one_chip(s.shape, s.dtype), model.pool_shapes(specs, jnp.bfloat16))

    def leaves(shapes, name=""):
        if isinstance(shapes, dict):
            return {k: leaves(v, k) for k, v in shapes.items()}
        return one_chip(shapes, jnp.float32 if name in ("scale", "sink", "select_bias") else jnp.bfloat16)

    params = leaves(weights_mimo.param_shapes(raw))
    tables = {c.kind: one_chip((B, c.table_width), jnp.int32) for c in specs}

    def decode(p, pool, tok, pos, tables):
        logits, pool, counts = _forward(model, p, pool, tok, StepContext(positions=pos, tables=tables))
        return logits[:, -1, :], pool, counts

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, pool, one_chip((B, 1), jnp.int32), one_chip((B, 1), jnp.int32), tables
    ).compile()
    text = compiled.as_text()
    assert text.count("paged_decode_attention") >= 7 and "%ragged-dot-none" in text
    plan = compiled.memory_analysis()
    held = sum(int(jnp.prod(jnp.asarray(x.shape))) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    assert 6.8e9 < held < 6.9e9
    assert plan.temp_size_in_bytes < 1e9  # no second copy of the weights, no copy of a pool
    live = plan.argument_size_in_bytes + plan.output_size_in_bytes - plan.alias_size_in_bytes + plan.temp_size_in_bytes
    assert live < 11e9


def test_serving_cell_programs_hold_no_copy_of_the_pool(serving_cell):
    """``serve.pythia_1.4b.chat_c32``'s two programs at the cell's shapes (32
    rows, a 64-token chunk, 1,025 pages): the 3.22 GB pool rides the layer
    loop as a carry, so each program aliases it to its result, slices no
    layer's 134 MB out of it, writes none back and copies none of it.  The
    decode model declares in bf16 what the forward multiplies in bf16 (the
    engine holds the tree so: 2.83 GB), so neither program converts a weight
    and its temporaries are activations, under 0.2 GB.  The parent of PR 35
    planned 5.78 GB for each, the parent of PR 37 2.42 GB: a bf16 copy of the
    f32 kernels, made anew by every dispatch (PERF.md §6)."""
    import math

    from test_paging import pool_sized_moves

    cfg, pool, params = serving_cell.cfg, serving_cell.pool, serving_cell.params
    pool_bytes = sum(c.pool_bytes for c in serving_cell.specs)
    assert 3.2e9 < pool_bytes < 3.3e9
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        used_in_f32 = "norm" in jax.tree_util.keystr(path)  # LayerNorm scales and offsets
        assert x.dtype == (jnp.float32 if used_in_f32 else jnp.bfloat16), jax.tree_util.keystr(path)
    held = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    assert 2.8e9 < held < 2.9e9
    a_stacked_kernel = cfg.num_hidden_layers * cfg.hidden_size**2  # the smallest: the attention output's

    for name, compiled in serving_cell.programs.items():
        text = compiled.as_text()
        assert ("paged_decode_attention" in text) == (name == "decode_paged")
        assert not pool_sized_moves(text, pool["layers"]["attention"]["k"].shape)
        plan = compiled.memory_analysis()
        assert plan.alias_size_in_bytes == pool_bytes
        assert plan.temp_size_in_bytes < 0.2e9
        converted = [math.prod(map(int, dims.split(","))) for dims in re.findall(r"= \w+\[([\d,]+)\]\S* convert\(", text)]
        assert converted and max(converted) < a_stacked_kernel  # activations change type, no weight does


@pytest.fixture(scope="module")
def serving_cell(one_chip):
    """``serve.pythia_1.4b.chat_c32``'s decode model, parameters and pool at
    the cell's shapes (32 rows, a 64-token chunk, 1,025 pages), and its two
    programs compiled once for the tests that read them."""
    import json
    import os
    from types import SimpleNamespace

    from relora_tpu.config.model import load_model_config
    from relora_tpu.models.step import PAGED, StepContext, cache_specs
    from relora_tpu.serve.engine import _forward, build_decode_model

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_model_config(os.path.join(root, "benchmark", "configs", "pythia_1.4b.json"))
    with open(os.path.join(root, "benchmark", "workloads", "serve.pythia_1.4b.chat_c32.json")) as f:
        w = json.load(f)
    B, ps, chunk, width = w["max_batch"], w["page_size"], w["chunk_size"], w["cache_size"] // w["page_size"]
    model = build_decode_model(
        cfg, cache_size=w["cache_size"], dtype=jnp.bfloat16, page_size=ps, num_pages=w["num_pages"]
    )
    specs = cache_specs(
        cfg, page_size=ps, num_pages=w["num_pages"], cache_size=w["cache_size"], chunk_size=chunk,
        max_batch=B, itemsize=2,
    )

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda s: one_chip(s.shape, s.dtype), tree)

    pool = on_chip(model.pool_shapes(specs, jnp.bfloat16))
    ids = jnp.zeros((1, 8), jnp.int32)
    params = on_chip(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, block_tables=ids)["params"]))

    def step(p, pool, tokens, pos, table):
        ctx = StepContext(positions=pos, tables={PAGED: table})
        logits, pool, _ = _forward(model, p, pool, tokens, ctx)
        return logits, pool

    def ints(*shape):
        return one_chip(shape, jnp.int32)

    programs = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")  # the dispatchers take their TPU branch
        for name, rows, tokens in (("decode_paged", B, 1), ("prefill_chunk", 1, chunk)):
            programs[name] = jax.jit(step, donate_argnums=(1,)).lower(
                params, pool, ints(rows, tokens), ints(rows, tokens), ints(rows, width)
            ).compile()
    return SimpleNamespace(cfg=cfg, specs=specs, pool=pool, params=params, programs=programs)


def layer_sized_results(text, shapes):
    """``(name, op)`` of every ``fusion`` or ``copy`` of a compiled program
    that is not itself inside a fused computation and makes an array of one
    of ``shapes``: what the program materialises, where a slice fused into
    its consumer makes nothing."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    made, inside = [], False
    for line in text.splitlines():
        if not line.startswith(" "):  # a computation's header (or the module's)
            head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
            inside = bool(head) and head.group(1) in fused
            continue
        m = re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* (fusion|copy)\(", line)
        if m and not inside and tuple(int(d) for d in m.group(2).split(",") if d) in shapes:
            made.append((m.group(1), m.group(3)))
    return made


@pytest.mark.parametrize("program", ["decode_paged", "prefill_chunk"])
def test_serving_cell_programs_read_each_layers_kernels_in_place(serving_cell, program):
    """Every kernel of a layer is read from its stack where it lies: no
    program of ``serve.pythia_1.4b.chat_c32`` makes one layer of a stacked
    kernel (``[2048, 6144]`` QKV, ``[2048, 2048]`` attention output,
    ``[2048, 8192]`` and ``[8192, 2048]`` FFN), with or without the leading
    layer dimension.  Were NeoX's head split folded into the QKV product,
    each layer of both programs would slice its 25 MB QKV kernel into VMEM and
    copy it transposed before multiplying."""
    stacked = [x.shape[1:] for x in jax.tree_util.tree_leaves(serving_cell.params["layers"]) if x.ndim == 3]
    shapes = {s for shape in stacked for s in (shape, (1, *shape))}
    assert {(2048, 6144), (2048, 2048), (2048, 8192), (8192, 2048)} <= shapes
    assert layer_sized_results(serving_cell.programs[program].as_text(), shapes) == []


@pytest.mark.parametrize("program", ["decode_paged", "prefill_chunk"])
def test_trinity_programs_compile_at_the_cells_shapes(one_chip, monkeypatch, program):
    """``serve.trinity_large_preview.agent_c32``'s two programs: 32 rows or a
    256-token chunk, published widths, five layers (a dense sliding one,
    three routed sliding ones, a routed full one), a 273-page ring a slot in
    the window layers beside a 22,529-page pool in the full one.  The decode's
    window launches carry their own name; the chunk's arm gathers a row's
    whole table — 11,264 keys in the full layer, a ``(1, 48, 256, 11264)`` f32
    score matrix of 553 MB — and must fit beside 8.64 GB of bf16 weights held
    once and 3.77 GB of cache: the plan (arguments + temporaries) stays under
    the chip's 15.75 GB."""
    import json
    import os

    from benchmark import weights_afmoe
    from relora_tpu.config.model import load_model_config
    from relora_tpu.models import step as model_step
    from relora_tpu.models.afmoe import AfmoeForCausalLM
    from relora_tpu.models.step import StepContext
    from relora_tpu.serve.engine import _forward

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the dispatchers take their TPU branch
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "benchmark", "configs", "trinity_large_preview.json")
    with open(path) as f:
        raw = json.load(f)
    with open(os.path.join(root, "benchmark", "workloads", "serve.trinity_large_preview.agent_c32.json")) as f:
        w = json.load(f)
    cfg = load_model_config(path)
    B, ps, chunk = w["max_batch"], w["page_size"], w["chunk_size"]
    model = AfmoeForCausalLM(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, decode=True, page_size=ps)
    specs = model_step.cache_specs(
        cfg, page_size=ps, num_pages=w["num_pages"], cache_size=w["cache_size"], chunk_size=chunk,
        max_batch=B, itemsize=2,
    )
    assert [(c.kind, c.layers, c.table_width) for c in specs] == [("paged", 1, 704), ("ring", 4, 273)]
    pool = jax.tree_util.tree_map(lambda s: one_chip(s.shape, s.dtype), model.pool_shapes(specs, jnp.bfloat16))

    def leaves(shapes, name=""):
        if isinstance(shapes, dict):
            return {k: leaves(v, k) for k, v in shapes.items()}
        return one_chip(shapes, jnp.float32 if name in ("scale", "select_bias") else jnp.bfloat16)

    params = leaves(weights_afmoe.param_shapes(raw))
    rows, tokens = (B, 1) if program == "decode_paged" else (1, chunk)
    tables = {c.kind: one_chip((rows, c.table_width), jnp.int32) for c in specs}

    def step(p, pool, tok, pos, tables):
        logits, pool, counts = _forward(model, p, pool, tok, StepContext(positions=pos, tables=tables))
        return (logits[:, -1, :] if tokens == 1 else logits), pool, counts

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, pool, one_chip((rows, tokens), jnp.int32), one_chip((rows, tokens), jnp.int32), tables
    ).compile()
    text = compiled.as_text()
    assert "%ragged-dot-none" in text
    if tokens == 1:
        assert text.count("paged_decode_attention_window") >= 4
        assert len(text.split("paged_decode_attention")) > len(text.split("paged_decode_attention_window"))
    else:
        assert "paged_decode_attention" not in text
    plan = compiled.memory_analysis()
    held = sum(int(jnp.prod(jnp.asarray(x.shape))) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    assert 8.6e9 < held < 8.7e9
    assert plan.alias_size_in_bytes >= sum(c.pool_bytes for c in specs)  # the pools are written in place
    live = plan.argument_size_in_bytes + plan.output_size_in_bytes - plan.alias_size_in_bytes + plan.temp_size_in_bytes
    assert live < 15.75e9  # 12.42 GB (decode) and 13.01 GB (chunk) at PR 36
    # no second copy of the weights or of a pool; the chunk's are its score matrices (0.58 GB)
    assert plan.temp_size_in_bytes < (0.1e9 if tokens == 1 else 1e9)
