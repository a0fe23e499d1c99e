"""Tests for the training/model config system (args_utils.py parity)."""

import json
import os

import pytest
import yaml

from relora_tpu.config.model import MODEL_ZOO, ModelConfig, load_model_config
from relora_tpu.config.training import TrainingConfig, parse_token_count, parse_train_args


def base_cfg(**kw):
    d = dict(dataset_path="/tmp/ds", batch_size=4)
    d.update(kw)
    return TrainingConfig(**d)


def test_requires_exactly_one_data_source():
    with pytest.raises(ValueError, match="Exactly one"):
        TrainingConfig(batch_size=4).finalize()
    with pytest.raises(ValueError, match="Exactly one"):
        TrainingConfig(
            batch_size=4, dataset_path="/x", megatron_dataset_config="/y"
        ).finalize()


def test_batch_size_required():
    with pytest.raises(ValueError, match="batch_size"):
        TrainingConfig(dataset_path="/x").finalize()


def test_total_batch_derivation():
    cfg = base_cfg(gradient_accumulation=8).finalize()
    assert cfg.total_batch_size == 32
    cfg = base_cfg().finalize()
    assert cfg.total_batch_size == 4 and cfg.gradient_accumulation == 1


def test_grad_accum_for_world():
    cfg = base_cfg(total_batch_size=1024, batch_size=8).finalize()
    assert cfg.grad_accum_for(32) == 4
    with pytest.raises(ValueError):
        cfg.grad_accum_for(3)


def test_max_train_tokens_overrides_steps():
    cfg = base_cfg(total_batch_size=8, max_train_tokens="1M").finalize()
    assert cfg.num_training_steps == 1_000_000 // 8
    assert parse_token_count("2B") == 2_000_000_000
    assert parse_token_count(100) == 100
    assert parse_token_count(None) is None


def test_fp16_rejected():
    with pytest.raises(NotImplementedError):
        base_cfg(dtype="fp16").finalize()


def test_reset_modes_mutually_exclusive():
    with pytest.raises(ValueError, match="mutually exclusive"):
        base_cfg(
            reset_optimizer_on_relora=True, optimizer_magnitude_pruning=0.8
        ).finalize()
    cfg = base_cfg(
        reset_optimizer_on_relora=False, optimizer_magnitude_pruning=0.8
    ).finalize()
    assert cfg.optimizer_reset_mode == "magnitude"
    assert cfg.optimizer_reset_ratio == 0.8
    cfg = base_cfg(reset_optimizer_on_relora=True).finalize()
    assert cfg.optimizer_reset_mode == "zero"


def test_relora_without_peft_dropped():
    """Reference parity: args_utils clears relora before the (dead) promotion,
    so --relora without --use_peft trains full-rank."""
    cfg = base_cfg(relora=1000).finalize()
    assert cfg.use_peft is False and cfg.relora is None
    cfg = base_cfg(relora=1000, use_peft=True).finalize()
    assert cfg.relora == 1000
    cfg = base_cfg(use_peft=False).finalize()
    assert cfg.relora is None and cfg.lora_r is None


def test_skip_batches_parsing():
    cfg = base_cfg(skip_batches="3,7,12").finalize()
    assert cfg.skip_batches == {3, 7, 12}
    cfg = base_cfg().finalize()
    assert cfg.skip_batches == set()


def test_yaml_roundtrip(tmp_path):
    """A reference-format YAML (1B_v1.0.yaml style) loads correctly."""
    raw = {
        "dataset_path": "/tmp/ds",
        "use_peft": True,
        "lora_r": 128,
        "relora": 1000,
        "restart_warmup_steps": 100,
        "reset_optimizer_on_relora": False,
        "optimizer_magnitude_pruning": 0.8,
        "batch_size": 8,
        "total_batch_size": 1024,
        "lr": "4e-4",  # yaml may leave scientific notation as str
        "adam_beta2": 0.95,
        "scheduler": "cosine_restarts",
        "warmup_steps": 500,
        "num_training_steps": 130000,
        "dtype": "bfloat16",
    }
    p = tmp_path / "cfg.yaml"
    p.write_text(yaml.safe_dump(raw))
    cfg = TrainingConfig.from_yaml(str(p))
    assert cfg.lr == 4e-4
    assert cfg.optimizer_reset_mode == "magnitude"
    assert cfg.total_batch_size == 1024

    out = tmp_path / "resolved.yaml"
    cfg.save(str(out))
    again = yaml.safe_load(out.read_text())
    assert again["relora"] == 1000


def test_cli_parsing():
    cfg = parse_train_args(
        [
            "--dataset_path", "/tmp/ds",
            "--batch_size", "4",
            "--relora", "100",
            "--use_peft", "true",
            "--lr", "1e-3",
            "--scheduler", "cosine_restarts",
            "--cycle_length", "100",
            "--restart_warmup_steps", "10",
        ]
    )
    assert cfg.relora == 100 and cfg.lr == 1e-3


def test_cli_yaml_exclusive(tmp_path):
    p = tmp_path / "cfg.yaml"
    p.write_text(yaml.safe_dump({"dataset_path": "/tmp/ds", "batch_size": 2}))
    with pytest.raises(RuntimeError, match="not both"):
        parse_train_args(["--training_config", str(p), "--batch_size", "4"])
    cfg = parse_train_args(["--training_config", str(p)])
    assert cfg.batch_size == 2


def test_model_zoo_sizes():
    # spot-check against the reference JSON sweep
    c = MODEL_ZOO["llama_35m"]
    assert (c.hidden_size, c.intermediate_size, c.num_hidden_layers, c.num_attention_heads) == (384, 1024, 6, 8)
    c = MODEL_ZOO["llama_1b"]
    assert (c.hidden_size, c.intermediate_size, c.num_hidden_layers) == (2048, 5461, 24)
    c = MODEL_ZOO["llama_7b"]
    assert c.max_sequence_length == 2048 and c.hidden_size == 4096
    assert load_model_config("llama_250m").vocab_size == 32100
    # param count sanity: llama_250m should be ~250M incl embeddings
    n = MODEL_ZOO["llama_250m"].num_params()
    assert 200e6 < n < 300e6


def test_model_config_hf_json(tmp_path):
    p = tmp_path / "config.json"
    p.write_text(
        '{"hidden_size": 384, "intermediate_size": 1024, "num_hidden_layers": 6,'
        '"num_attention_heads": 8, "vocab_size": 32100, "max_sequence_length": 1024,'
        '"rms_norm_eps": 1e-6, "model_type": "llama"}'
    )
    c = ModelConfig.from_hf_json(str(p))
    assert c.family == "llama" and c.head_dim == 48


def test_package_import_does_not_initialize_jax():
    """Importing config/logging must not touch the XLA backend (it would break
    a later jax.distributed.initialize() on multi-host)."""
    import subprocess, sys

    code = (
        "import relora_tpu.config.training, relora_tpu.utils.logging, sys;"
        "assert 'jax' not in sys.modules or not __import__('jax')._src.xla_bridge._backends,"
        "'XLA backend initialized at import time'"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, cwd="/root/repo")
    assert r.returncode == 0, r.stderr


MIMO_JSON = {
    "model_type": "mimo_v2", "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
    "hybrid_layer_pattern": [0, 1, 0], "moe_layer_freq": [0, 1, 1], "num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2, "head_dim": 24, "v_head_dim": 16,
    "partial_rotary_factor": 0.334, "rope_theta": 10000000, "swa_rope_theta": 10000, "sliding_window": 8,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False, "attention_value_scale": 0.707,
    "moe_intermediate_size": 32, "n_routed_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": None, "layernorm_epsilon": 1e-5, "vocab_size": 100,
}


def _mimo(tmp_path, **over):
    p = tmp_path / "config.json"
    p.write_text(json.dumps({**MIMO_JSON, **over}))
    return ModelConfig.from_hf_json(str(p))


def test_mimo_v2_json_gives_the_third_family(tmp_path):
    c = _mimo(tmp_path, experts_held=4, expert_offset=8)
    assert c.family == "mimo" and c.layer_window == (0, 1, 0) and c.layer_moe == (0, 1, 1)
    assert (c.head_dim, c.qk_head_dim, c.v_head_dim, c.rotary_dim) == (24, 24, 16, 8)
    assert (c.kv_heads, c.window_kv_heads, c.sliding_window) == (1, 2, 8)
    assert (c.rotary_emb_base, c.window_rotary_base) == (1e7, 1e4)
    assert c.window_sink and not c.global_sink and c.value_scale == 0.707
    assert (c.n_routed_experts, c.experts_held, c.expert_offset, c.num_experts_per_tok) == (16, 4, 8, 4)
    assert c.routed_scaling_factor == 1.0 and c.rms_norm_eps == 1e-5
    # without a share the chip holds every expert; a dict round trip keeps the per-layer kinds
    assert _mimo(tmp_path).experts_held == 16
    assert ModelConfig.from_dict(json.loads(json.dumps(c.to_dict()))) == c
    # what this chip holds: one global dense layer, one window and one global expert layer of 4 experts
    attn = lambda n_kv: 64 * (4 * 24 + n_kv * 40) + 4 * 16 * 64 + 2 * 64
    want = 64 + attn(1) + 3 * 64 * 128 + (attn(2) + 4) + attn(1) + 2 * (65 * 16 + 4 * 3 * 64 * 32) + 2 * 100 * 64
    assert c.num_params() == want


@pytest.mark.parametrize(
    "over, match",
    [
        ({"hybrid_layer_pattern": [0, 1]}, "must have num_hidden_layers"),
        ({"experts_held": 4, "expert_offset": 14}, "not among the 16 routed"),
        ({"n_group": 2}, "group-limited routing"),
        ({"n_shared_experts": 1}, "shared experts"),
        ({"scoring_func": "softmax"}, "sigmoid only"),
        ({"swa_head_dim": 32}, "swa_head_dim"),
    ],
)
def test_mimo_v2_json_refuses_what_the_model_does_not_compute(tmp_path, over, match):
    with pytest.raises(ValueError, match=match):
        _mimo(tmp_path, **over)


def test_unknown_model_type_is_an_error(tmp_path):
    """No silent reading of another family's config as Llama."""
    p = tmp_path / "config.json"
    p.write_text(json.dumps({**MIMO_JSON, "model_type": "lfm2"}))
    with pytest.raises(ValueError, match="model_type 'lfm2' is none of"):
        load_model_config(str(p))
    p.write_text('{"hidden_size": 8, "intermediate_size": 8, "num_hidden_layers": 1, "num_attention_heads": 1, "vocab_size": 8}')
    assert load_model_config(str(p)).family == "llama"  # the reference's own configs carry no model_type


def test_every_config_file_in_the_tree_parses():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = []
    for sub in ("configs", os.path.join("benchmark", "configs"), os.path.join("benchmark", "tests", "data", "configs")):
        for name in sorted(os.listdir(os.path.join(root, sub))):
            if name.endswith(".json"):
                found.append(load_model_config(os.path.join(root, sub, name)).family)
    assert sorted(set(found)) == ["afmoe", "mimo", "neox"] and len(found) >= 6
