"""Operations and bytes the *algorithm* needs, from shapes alone.

Whatever implements the work, these count the same: no padding, no
recomputation, no random bits.
``cfg`` is a configuration file's dict (Hugging Face keys); each count
but the optimizer's is its architecture's (``harness.arch``), found by
the configuration's ``reference`` key.
"""
from __future__ import annotations

from harness import arch


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    return arch.module(cfg).layer_matmul_params(cfg)


def n_params(cfg: dict) -> int:
    """Every parameter of the model."""
    return arch.module(cfg).n_params(cfg)


def attention_flops(cfg: dict, position: int) -> int:
    """QK^T and PV of one query at ``position`` over its causal prefix,
    summed over layers."""
    return arch.module(cfg).attention_flops(cfg, position)


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    """Forward + backward (3x forward) of one step, every position with
    its logits; nothing recomputed counts."""
    return arch.module(cfg).train_flops_per_step(cfg, batch, seq)


def adamw_bytes(cfg: dict, *, kahan: bool = True, itemsize: int = 2) -> int:
    """The fused SR-AdamW update's least traffic: read w, m, v, g (and
    the Kahan buffer c), write w, m, v (and c), each once, in bf16.
    Random bits and padding are the implementation's, not counted."""
    per = (5 + 4) if kahan else (4 + 3)
    return per * itemsize * n_params(cfg)
