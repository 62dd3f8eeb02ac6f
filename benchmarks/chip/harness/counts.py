"""Operations and bytes the *algorithm* needs, from shapes alone.

Whatever implements the work, these count the same: no padding, no
recomputation, no random bits.
``cfg`` is a configuration file's dict (Hugging Face keys).
"""
from __future__ import annotations


def _dims(cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    return d, f, h, kv, hd, cfg["num_hidden_layers"], cfg["vocab_size"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    d, f, h, kv, hd, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def n_params(cfg: dict) -> int:
    """Every parameter: embedding, layers (with norms and biases), final
    norm and, when untied, the head."""
    d, f, h, kv, hd, L, v = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d
    if cfg["attention_bias"]:
        per_layer += h * hd + 2 * kv * hd
    head = 0 if cfg["tie_word_embeddings"] else d * v
    return v * d + L * per_layer + d + head


def attention_flops(cfg: dict, position: int) -> int:
    """QK^T and PV of one query at ``position`` over its causal prefix,
    summed over layers."""
    _, _, h, _, hd, L, _ = _dims(cfg)
    return 4 * (position + 1) * h * hd * L


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    """Forward + backward (3x forward) of one step, every position with
    its logits; nothing recomputed counts."""
    d, _, _, _, _, L, v = _dims(cfg)
    dense = 2 * L * layer_matmul_params(cfg) + 2 * d * v
    attn = sum(attention_flops(cfg, p) for p in range(seq))
    return 3 * batch * (seq * dense + attn)


def adamw_bytes(cfg: dict, *, kahan: bool = True, itemsize: int = 2) -> int:
    """The fused SR-AdamW update's least traffic: read w, m, v, g (and
    the Kahan buffer c), write w, m, v (and c), each once, in bf16.
    Random bits and padding are the implementation's, not counted."""
    per = (5 + 4) if kahan else (4 + 3)
    return per * itemsize * n_params(cfg)
