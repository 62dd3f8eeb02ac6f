"""A dense decoder with grouped-query attention (Llama / Qwen2 / Yi):
the program's configuration for it, and its counts from shapes alone.

``cfg`` is a configuration file's dict (Hugging Face keys).
"""
from __future__ import annotations

from typing import Any


def model_config(config: dict) -> Any:
    """The program's ``ModelConfig`` for a dense decoder configuration
    given in Hugging Face keys."""
    from repro.configs.base import ModelConfig
    # what the program's dense decoder fixes; a configuration stating
    # otherwise would be run as something it is not
    if config["rms_norm_eps"] != 1e-6 or config["hidden_act"] != "silu":
        raise ValueError(f"{config['name']}: the program runs RMSNorm eps "
                         f"1e-6 and SiLU only")
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    return ModelConfig(
        name=config["name"], family="lm",
        n_layers=config["num_hidden_layers"], d_model=d,
        vocab=config["vocab_size"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", d // heads),
        qkv_bias=bool(config["attention_bias"]),
        rope_theta=float(config["rope_theta"]),
        d_ff=config["intermediate_size"], norm="rms",
        tie_embeddings=bool(config["tie_word_embeddings"]))


def _dims(cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    return d, f, h, kv, hd, cfg["num_hidden_layers"], cfg["vocab_size"]


def layer_matmul_params(cfg: dict) -> int:
    d, f, h, kv, hd, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def n_params(cfg: dict) -> int:
    """Embedding, layers (with norms and biases), final norm and, when
    untied, the head."""
    d, f, h, kv, hd, L, v = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d
    if cfg["attention_bias"]:
        per_layer += h * hd + 2 * kv * hd
    head = 0 if cfg["tie_word_embeddings"] else d * v
    return v * d + L * per_layer + d + head


def attention_flops(cfg: dict, position: int) -> int:
    _, _, h, _, hd, L, _ = _dims(cfg)
    return 4 * (position + 1) * h * hd * L


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    d, _, _, _, _, L, v = _dims(cfg)
    dense = 2 * L * layer_matmul_params(cfg) + 2 * d * v
    attn = sum(attention_flops(cfg, p) for p in range(seq))
    return 3 * batch * (seq * dense + attn)
