"""A decoder whose every layer is grouped-query attention with QK-norm
and a dropless mixture of experts (the Qwen3-MoE block: SDAR-30B-A3B):
the program's configuration for it, and its counts from shapes alone.

``cfg`` is a configuration file's dict (Hugging Face keys). Under
expert parallelism ``expert_parallel`` chips share each layer; this one
holds ``num_experts`` of the router's ``num_experts * expert_parallel``,
from ``expert_offset``. The router keeps its full width and top-k.
"""
from __future__ import annotations

from typing import Any


def _router_width(cfg: dict) -> int:
    return cfg["num_experts"] * cfg["expert_parallel"]


def model_config(config: dict) -> Any:
    """The program's ``ModelConfig``: every layer attention with QK-norm
    and a dropless expert layer holding this chip's share."""
    from repro.configs.base import ModelConfig
    # what the program's block fixes; a configuration stating otherwise
    # would be run as something it is not
    fixed = {"rms_norm_eps": 1e-6, "hidden_act": "silu",
             "decoder_sparse_step": 1, "mlp_only_layers": [],
             "rope_scaling": None, "use_sliding_window": False}
    wrong = {k: config.get(k) for k, v in fixed.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"{config['name']}: the program runs {fixed}; the "
                         f"configuration states {wrong}")
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    return ModelConfig(
        name=config["name"], family="lm",
        n_layers=config["num_hidden_layers"], d_model=d,
        vocab=config["vocab_size"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim", d // heads),
        qkv_bias=bool(config["attention_bias"]), qk_norm=True,
        rope_theta=float(config["rope_theta"]),
        d_ff=config["moe_intermediate_size"],
        n_experts=_router_width(config),
        experts_held=config["num_experts"],
        expert_offset=config.get("expert_offset", 0),
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        router_aux_coef=float(config["router_aux_loss_coef"]),
        moe_strategy="dropless", norm="rms",
        tie_embeddings=bool(config["tie_word_embeddings"]))


def _dims(cfg: dict):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    return d, f, h, kv, hd, cfg["num_hidden_layers"], cfg["vocab_size"]


def attention_params(cfg: dict) -> int:
    d, _, h, kv, hd, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def routed_rows_per_token(cfg: dict) -> float:
    """Token-expert assignments one token sends to the experts held here,
    expected: top-k of the router's width, of which this chip holds
    ``num_experts`` (8 x 32 / 128 = 2 for SDAR-30B-A3B's share)."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / _router_width(cfg)


def layer_matmul_params(cfg: dict) -> int:
    """Attention, the router, and the expected work of the held experts:
    each assignment routed here multiplies through one expert's gate, up
    and down matrices."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed = round(3 * d * f * routed_rows_per_token(cfg))
    return attention_params(cfg) + d * _router_width(cfg) + routed


def n_params(cfg: dict) -> int:
    """Embedding; per layer attention, QK-norm scales, two norms, the
    router and the held experts; final norm and, when untied, the head."""
    d, f, h, kv, hd, L, v = _dims(cfg)
    per_layer = (attention_params(cfg) + 2 * hd + 2 * d
                 + d * _router_width(cfg) + cfg["num_experts"] * 3 * d * f)
    if cfg["attention_bias"]:
        per_layer += h * hd + 2 * kv * hd
    head = 0 if cfg["tie_word_embeddings"] else d * v
    return v * d + L * per_layer + d + head


def attention_flops(cfg: dict, position: int) -> int:
    _, _, h, _, hd, L, _ = _dims(cfg)
    return 4 * (position + 1) * h * hd * L


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    """Forward + backward (3x forward); the experts at the expected rows
    routed here (:func:`routed_rows_per_token`), none recomputed."""
    d, _, _, _, _, L, v = _dims(cfg)
    dense = 2 * L * layer_matmul_params(cfg) + 2 * d * v
    attn = sum(attention_flops(cfg, p) for p in range(seq))
    return 3 * batch * (seq * dense + attn)
