"""Model architecture configs and the reference size sweep.

The reference ships 15 Llama JSON configs (``configs/llama_{9m..7b}.json``) in
HF format; here the same sweep lives in one typed table (`MODEL_ZOO`).
`load_model_config` also reads HF-style JSON files directly, so a user of the
reference can point us at their existing config files unchanged.

Reference parity: configs/llama_35m.json etc.; fields mirror
peft_pretraining/modeling_llama.py's LlamaConfig usage and
modeling_pythia.py's GPTNeoXConfig usage.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for the four model families.

    ``family`` is "llama" (RMSNorm, SwiGLU, no biases, separate q/k/v) or
    "neox" (LayerNorm, GELU MLP, biases, fused QKV, parallel residual,
    partial rotary) — the two families the reference implements
    (modeling_llama.py, modeling_pythia.py) — or "mimo" (HF ``mimo_v2``:
    sliding-window layers with a sink bias beside global layers, K heads
    wider than V heads, a dense FFN in the leading layers and sigmoid-routed
    experts after them; served only, see models/mimo.py) or "afmoe" (HF
    ``afmoe``, Arcee Trinity: gated attention with per-head q/k norms, rotary
    window layers beside position-free global ones, four norms a layer, a
    shared expert beside the routed ones; served only, see models/afmoe.py).
    """

    family: str = "llama"
    vocab_size: int = 32100
    hidden_size: int = 384
    intermediate_size: int = 1024
    num_hidden_layers: int = 6
    num_attention_heads: int = 8
    # grouped-query attention: fewer K/V heads than Q heads (None = MHA, the
    # reference's models; an extension for modern Llama variants)
    num_key_value_heads: Optional[int] = None
    max_sequence_length: int = 1024
    rms_norm_eps: float = 1e-6
    layer_norm_eps: float = 1e-5  # neox
    initializer_range: float = 0.02
    rotary_pct: float = 1.0  # neox partial rotary (modeling_pythia.py:97)
    rotary_emb_base: float = 10000.0
    # context extension (parity: rope scaling variants, modeling_pythia.py:333-375)
    rope_scaling_type: Optional[str] = None  # None | "linear" | "dynamic"
    rope_scaling_factor: float = 1.0
    use_parallel_residual: bool = True  # neox (modeling_pythia.py:443-456)
    tie_word_embeddings: bool = False
    bos_token_id: int = 0
    eos_token_id: int = 1
    # -- mimo, afmoe: per-layer kinds, two head geometries, the expert fields --
    # 1 = sliding-window attention, 0 = global (HF hybrid_layer_pattern)
    layer_window: Tuple[int, ...] = ()
    # 1 = routed experts, 0 = dense FFN (HF moe_layer_freq)
    layer_moe: Tuple[int, ...] = ()
    qk_head_dim: int = 0
    v_head_dim: int = 0
    window_kv_heads: int = 0  # swa_num_key_value_heads
    sliding_window: int = 0
    window_rotary_base: float = 10000.0  # swa_rope_theta
    window_sink: bool = False  # add_swa_attention_sink_bias
    global_sink: bool = False  # add_full_attention_sink_bias
    value_scale: float = 1.0  # attention_value_scale
    moe_intermediate_size: int = 0
    n_routed_experts: int = 0  # the router's width, as published
    # the experts this chip holds: expert_offset .. expert_offset+experts_held-1
    # (0 held = all of them)
    experts_held: int = 0
    expert_offset: int = 0
    num_experts_per_tok: int = 0
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # -- afmoe: what no other family has ---------------------------------------
    attn_gate: bool = False  # o *= sigmoid(x Wg) before the output projection
    qk_norm: bool = False  # RMSNorm over each q and k head's features
    global_rotary: bool = True  # False: global layers take no positional encoding
    sandwich_norm: bool = False  # a norm after attention and after the FFN too
    embed_scale: float = 1.0  # multiplies the embedding (mup_enabled: sqrt(hidden))
    n_shared_experts: int = 0  # SwiGLU experts every token takes, unrouted

    @property
    def head_dim(self) -> int:
        return self.qk_head_dim or self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_pct)

    def num_params(self, include_embeddings: bool = True) -> int:
        """Approximate parameter count (dense, untied)."""
        h, i, L, v = self.hidden_size, self.intermediate_size, self.num_hidden_layers, self.vocab_size
        if self.family in ("mimo", "afmoe"):
            # what this chip holds: its share of the experts, every other leaf whole
            q, o = self.num_attention_heads * self.qk_head_dim, self.num_attention_heads * self.v_head_dim
            n = h
            for window, moe in zip(self.layer_window, self.layer_moe):
                n_kv = self.window_kv_heads if window else self.kv_heads
                n += h * (q + n_kv * (self.qk_head_dim + self.v_head_dim)) + o * h
                n += (4 if self.sandwich_norm else 2) * h
                n += h * o if self.attn_gate else 0
                n += 2 * self.qk_head_dim if self.qk_norm else 0
                n += self.num_attention_heads if (self.window_sink if window else self.global_sink) else 0
                if moe:
                    n += (h + 1) * self.n_routed_experts
                    n += (self.experts_held + self.n_shared_experts) * 3 * h * self.moe_intermediate_size
                else:
                    n += 3 * h * i
            return n + (2 * v * h if include_embeddings else 0)
        if self.family == "llama":
            per_layer = 4 * h * h + 3 * h * i + 2 * h
            extra = h  # final norm
        else:
            # fused qkv (3h*h+3h), dense (h*h+h), 2-layer mlp, 2 LayerNorms w/ bias
            per_layer = (3 * h * h + 3 * h) + (h * h + h) + (2 * h * i + i + h) + 4 * h
            extra = 2 * h
        n = L * per_layer + extra
        if include_embeddings:
            n += 2 * v * h if not self.tie_word_embeddings else v * h
        return n

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ModelConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        # a JSON round trip turns the per-layer tuples into lists
        return cls(**{k: tuple(v) if isinstance(v, list) else v for k, v in d.items() if k in known})

    @classmethod
    def from_hf_json(cls, path: str) -> "ModelConfig":
        """Read an HF-style config JSON (the reference's configs/*.json format)."""
        with open(path) as f:
            d = json.load(f)
        model_type = d.get("model_type", "llama")
        if model_type not in MODEL_TYPES:
            raise ValueError(
                f"{path}: model_type {model_type!r} is none of {sorted(MODEL_TYPES)}"
            )
        if model_type == "mimo_v2":
            return cls._from_mimo_json(d)
        if model_type == "afmoe":
            return cls._from_afmoe_json(d)
        return cls(
            family=MODEL_TYPES[model_type],
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_hidden_layers=d["num_hidden_layers"],
            num_attention_heads=d["num_attention_heads"],
            num_key_value_heads=d.get("num_key_value_heads"),
            max_sequence_length=d.get("max_sequence_length", d.get("max_position_embeddings", 2048)),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            layer_norm_eps=d.get("layer_norm_eps", 1e-5),
            initializer_range=d.get("initializer_range", 0.02),
            rotary_pct=d.get("rotary_pct", 1.0),
            rotary_emb_base=d.get("rotary_emb_base", 10000.0),
            use_parallel_residual=d.get("use_parallel_residual", True),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            bos_token_id=d.get("bos_token_id", 0),
            eos_token_id=d.get("eos_token_id", 1),
            rope_scaling_type=(d.get("rope_scaling") or {}).get("type"),
            rope_scaling_factor=(d.get("rope_scaling") or {}).get("factor", 1.0),
        )

    @classmethod
    def _from_mimo_json(cls, d: dict) -> "ModelConfig":
        """HF ``mimo_v2`` keys.  ``experts_held`` / ``expert_offset`` are this
        repo's: the chip's share of ``n_routed_experts`` (absent = all)."""
        L = d["num_hidden_layers"]
        window, moe = tuple(d["hybrid_layer_pattern"]), tuple(d["moe_layer_freq"])
        if len(window) != L or len(moe) != L:
            raise ValueError(
                f"hybrid_layer_pattern ({len(window)}) and moe_layer_freq "
                f"({len(moe)}) must have num_hidden_layers = {L} entries"
            )
        for key in ("num_attention_heads", "head_dim", "v_head_dim"):
            if d.get(f"swa_{key}", d[key]) != d[key]:
                raise ValueError(f"swa_{key} = {d['swa_' + key]} differs from {key} = {d[key]}: not supported")
        if d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1:
            raise ValueError("group-limited routing (n_group / topk_group > 1) is not supported")
        if d.get("n_shared_experts"):
            raise ValueError("shared experts are not supported")
        if d.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError(f"scoring_func {d['scoring_func']!r} is not supported (sigmoid only)")
        n_experts = d["n_routed_experts"]
        held, offset = _experts_share(d, n_experts)
        return cls(
            family="mimo",
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_hidden_layers=L,
            num_attention_heads=d["num_attention_heads"],
            num_key_value_heads=d["num_key_value_heads"],
            max_sequence_length=d.get("max_position_embeddings", 2048),
            rms_norm_eps=d.get("layernorm_epsilon", 1e-5),
            initializer_range=d.get("initializer_range", 0.02),
            rotary_pct=d.get("partial_rotary_factor", 1.0),
            rotary_emb_base=d.get("rope_theta", 10000.0),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            bos_token_id=d.get("bos_token_id", 0),
            eos_token_id=d.get("eos_token_id", 1),
            layer_window=window,
            layer_moe=moe,
            qk_head_dim=d["head_dim"],
            v_head_dim=d["v_head_dim"],
            window_kv_heads=d["swa_num_key_value_heads"],
            sliding_window=d["sliding_window"],
            window_rotary_base=d.get("swa_rope_theta", 10000.0),
            window_sink=d.get("add_swa_attention_sink_bias", False),
            global_sink=d.get("add_full_attention_sink_bias", False),
            value_scale=d.get("attention_value_scale", 1.0),
            moe_intermediate_size=d["moe_intermediate_size"],
            n_routed_experts=n_experts,
            experts_held=held,
            expert_offset=offset,
            num_experts_per_tok=d["num_experts_per_tok"],
            norm_topk_prob=d.get("norm_topk_prob", True),
            routed_scaling_factor=d.get("routed_scaling_factor") or 1.0,
        )

    @classmethod
    def _from_afmoe_json(cls, d: dict) -> "ModelConfig":
        """HF ``afmoe`` keys (Arcee Trinity), and this repo's ``experts_held``
        / ``expert_offset``.  ``layer_types`` says which layers slide,
        ``num_dense_layers`` how many leading ones keep a dense FFN."""
        L = d["num_hidden_layers"]
        kinds = {"sliding_attention": 1, "full_attention": 0}
        odd = sorted(set(d["layer_types"]) - set(kinds))
        if odd or len(d["layer_types"]) != L:
            raise ValueError(
                f"layer_types must name num_hidden_layers = {L} layers, each one of {sorted(kinds)}"
                + (f" (got {odd})" if odd else f" (got {len(d['layer_types'])})")
            )
        if d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1:
            raise ValueError("group-limited routing (n_group / topk_group > 1) is not supported")
        if d.get("score_func", "sigmoid") != "sigmoid":
            raise ValueError(f"score_func {d['score_func']!r} is not supported (sigmoid only)")
        if d.get("rope_scaling"):
            raise ValueError("rope_scaling is not supported for afmoe")
        dense = d["num_dense_layers"]
        if not 0 <= dense <= L:
            raise ValueError(f"num_dense_layers = {dense} is not within num_hidden_layers = {L}")
        n_experts = d["num_experts"]
        held, offset = _experts_share(d, n_experts)
        head = d.get("head_dim") or d["hidden_size"] // d["num_attention_heads"]
        return cls(
            family="afmoe",
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_hidden_layers=L,
            num_attention_heads=d["num_attention_heads"],
            num_key_value_heads=d["num_key_value_heads"],
            max_sequence_length=d.get("max_position_embeddings", 2048),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            initializer_range=d.get("initializer_range", 0.02),
            rotary_emb_base=d.get("rope_theta", 10000.0),
            window_rotary_base=d.get("rope_theta", 10000.0),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            bos_token_id=d.get("bos_token_id", 0),
            eos_token_id=d.get("eos_token_id", 1),
            layer_window=tuple(kinds[k] for k in d["layer_types"]),
            layer_moe=tuple(int(i >= dense) for i in range(L)),
            qk_head_dim=head,
            v_head_dim=head,
            window_kv_heads=d["num_key_value_heads"],
            sliding_window=d["sliding_window"],
            moe_intermediate_size=d["moe_intermediate_size"],
            n_routed_experts=n_experts,
            experts_held=held,
            expert_offset=offset,
            num_experts_per_tok=d["num_experts_per_tok"],
            norm_topk_prob=d.get("route_norm", True),
            routed_scaling_factor=d.get("route_scale") or 1.0,
            attn_gate=True,
            qk_norm=True,
            global_rotary=False,
            sandwich_norm=True,
            embed_scale=float(d["hidden_size"]) ** 0.5 if d.get("mup_enabled") else 1.0,
            n_shared_experts=d.get("num_shared_experts", 0),
        )


def _experts_share(d: dict, n_experts: int) -> Tuple[int, int]:
    """``experts_held`` and ``expert_offset`` of a configuration file: the
    chip's share of the ``n_experts`` the router scores (absent = all)."""
    held = d.get("experts_held", n_experts)
    offset = d.get("expert_offset", 0)
    if not 0 < held <= n_experts or not 0 <= offset <= n_experts - held:
        raise ValueError(f"experts {offset} .. {offset + held - 1} are not among the {n_experts} routed")
    return held, offset


#: HF ``model_type`` -> family, four of them; any other is an error (an absent
#: key is Llama, the reference's own configs/*.json carry none)
MODEL_TYPES = {"llama": "llama", "gpt_neox": "neox", "mimo_v2": "mimo", "afmoe": "afmoe"}


def _llama(h: int, i: int, L: int, heads: int, seq: int = 1024, vocab: int = 32100) -> ModelConfig:
    return ModelConfig(
        family="llama",
        hidden_size=h,
        intermediate_size=i,
        num_hidden_layers=L,
        num_attention_heads=heads,
        max_sequence_length=seq,
        vocab_size=vocab,
    )


# The reference's full Llama size sweep (configs/llama_9m.json .. llama_7b.json).
MODEL_ZOO: dict[str, ModelConfig] = {
    "llama_9m": _llama(128, 352, 4, 4),
    "llama_20m": _llama(256, 688, 4, 4),
    "llama_35m": _llama(384, 1024, 6, 8),
    "llama_40m": _llama(416, 1024, 8, 8),
    "llama_60m": _llama(512, 1376, 8, 8),
    "llama_71m": _llama(512, 1368, 12, 8),
    "llama_100m": _llama(640, 1708, 12, 10),
    "llama_130m": _llama(768, 2048, 12, 12),
    "llama_250m": _llama(768, 2560, 24, 16),
    "llama_250m_50K": _llama(768, 2560, 24, 16, vocab=50257),
    "llama_250m_old": _llama(768, 2560, 24, 16, vocab=32000),
    "llama_350m": _llama(1024, 2736, 24, 16),
    "llama_1b": _llama(2048, 5461, 24, 32),
    "llama_3b": _llama(2560, 6848, 32, 32),
    "llama_7b": _llama(4096, 11008, 32, 32, seq=2048),
    # Pythia/GPT-NeoX sizes used by the reference's production recipe
    # (training_configs/1B_v1.0.yaml: EleutherAI/pythia-1b).
    # pythia_14m is a dev size (llama_9m's role for the neox family —
    # smoke tests and CI; not an EleutherAI release).
    "pythia_14m": ModelConfig(
        family="neox", vocab_size=50304, hidden_size=128, intermediate_size=512,
        num_hidden_layers=4, num_attention_heads=4, max_sequence_length=2048,
        rotary_pct=0.25, tie_word_embeddings=False,
    ),
    "pythia_70m": ModelConfig(
        family="neox", vocab_size=50304, hidden_size=512, intermediate_size=2048,
        num_hidden_layers=6, num_attention_heads=8, max_sequence_length=2048,
        rotary_pct=0.25, tie_word_embeddings=False,
    ),
    "pythia_160m": ModelConfig(
        family="neox", vocab_size=50304, hidden_size=768, intermediate_size=3072,
        num_hidden_layers=12, num_attention_heads=12, max_sequence_length=2048,
        rotary_pct=0.25,
    ),
    "pythia_410m": ModelConfig(
        family="neox", vocab_size=50304, hidden_size=1024, intermediate_size=4096,
        num_hidden_layers=24, num_attention_heads=16, max_sequence_length=2048,
        rotary_pct=0.25,
    ),
    "pythia_1b": ModelConfig(
        family="neox", vocab_size=50304, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=16, num_attention_heads=8, max_sequence_length=2048,
        rotary_pct=0.25,
    ),
    "pythia_1.4b": ModelConfig(
        family="neox", vocab_size=50304, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=24, num_attention_heads=16, max_sequence_length=2048,
        rotary_pct=0.25,
    ),
}


# HF hub ids used by reference recipes -> zoo entries, so configs like
# "model_name_or_path: EleutherAI/pythia-1b" (training_configs/1B_v1.0.yaml)
# resolve without network access.  Weights still come from a local snapshot
# via --warmed_up_model.
HF_ID_ALIASES = {
    f"EleutherAI/pythia-{size}": f"pythia_{size.replace('-deduped', '')}"
    for size in ("70m", "160m", "410m", "1b", "1.4b")
} | {
    f"EleutherAI/pythia-{size}-deduped": f"pythia_{size}"
    for size in ("70m", "160m", "410m", "1b", "1.4b")
}


def load_model_config(name_or_path: str) -> ModelConfig:
    """Resolve a zoo name ("llama_35m"), a known HF hub id, an HF-style JSON
    path, or a dir with config.json."""
    import os

    if name_or_path in MODEL_ZOO:
        return MODEL_ZOO[name_or_path]
    if name_or_path in HF_ID_ALIASES:
        return MODEL_ZOO[HF_ID_ALIASES[name_or_path]]
    if os.path.isdir(name_or_path):
        name_or_path = os.path.join(name_or_path, "config.json")
    if os.path.exists(name_or_path):
        return ModelConfig.from_hf_json(name_or_path)
    raise ValueError(
        f"Unknown model config {name_or_path!r}: not in MODEL_ZOO "
        f"({sorted(MODEL_ZOO)}), not a known HF id, and not a file"
    )
