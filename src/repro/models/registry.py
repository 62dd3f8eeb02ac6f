"""Architecture registry + the uniform model API used by train/serve/dryrun.

Batch dict conventions (what ``input_specs()`` must produce):

* lm:    {"tokens": (B,S) i32, "labels": (B,S) i32}
* vlm:   {"embeds": (B,S,D), "mrope_positions": (3,B,S) i32, "labels": (B,S)}
* audio: {"src_embeds": (B,S_src,D), "tokens": (B,S_tgt) i32, "labels": ...}
* ssm/hybrid: same as lm.

Decode: ``make_cache`` builds the state pytree; ``decode`` advances one
token. Whole-sequence logits are f32 (consumed fused by the loss).
"""
from __future__ import annotations

import importlib
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.qarith import QArith
from repro.models import encdec as ED
from repro.models import transformer as T

__all__ = ["ARCH_IDS", "get_config", "init", "forward_logits", "make_cache",
           "decode", "TGT_LEN_ENCDEC"]

ARCH_IDS = (
    "llama4-scout-17b-a16e", "mixtral-8x22b", "command-r-35b", "yi-9b",
    "qwen2.5-3b", "mistral-nemo-12b", "qwen2-vl-7b", "whisper-base",
    "falcon-mamba-7b", "recurrentgemma-2b",
)

_MODULES = {
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "mixtral-8x22b": "mixtral_8x22b",
    "command-r-35b": "command_r_35b",
    "yi-9b": "yi_9b",
    "qwen2.5-3b": "qwen2_5_3b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "whisper-base": "whisper_base",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "recurrentgemma-2b": "recurrentgemma_2b",
}

# Whisper's decoder is designed for 448 tokens; teacher-forced target length
# used for its *train* cells (the src frame length carries seq_len).
TGT_LEN_ENCDEC = 448


def get_config(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


def init(cfg, key, dtype=jnp.float32):
    if cfg.encdec:
        return ED.init_encdec(cfg, key, dtype)
    return T.init_lm(cfg, key, dtype)


def forward_logits(qa: QArith, params, cfg, batch: dict[str, Any], *,
                   remat: bool = True, attn_chunk: int = 1024,
                   with_stats: bool = False):
    """Teacher-forced logits (B,S,V) f32 for any family; with
    ``with_stats``, (logits, the expert layers' router loss and load
    counters), which are empty for a model without such layers."""
    if cfg.encdec:
        enc_out = ED.encode(qa, params, cfg, batch["src_embeds"],
                            remat=remat, attn_chunk=attn_chunk)
        logits = ED.decoder_forward(qa, params, cfg, batch["tokens"],
                                    enc_out, remat=remat,
                                    attn_chunk=attn_chunk)
        return (logits, {}) if with_stats else logits
    tokens = batch.get("tokens", batch.get("embeds"))
    return T.forward(qa, params, cfg, tokens,
                     mrope_positions=batch.get("mrope_positions"),
                     remat=remat, attn_chunk=attn_chunk,
                     with_stats=with_stats)


def make_cache(qa: QArith, params, cfg, batch: dict[str, Any], *,
               batch_size: int, max_len: int, dtype=jnp.bfloat16,
               page_size=None, n_rows=None):
    if cfg.encdec:
        if page_size is not None:
            raise ValueError("paged KV cache is not supported for enc-dec")
        enc_out = ED.encode(qa, params, cfg, batch["src_embeds"], remat=False)
        return ED.init_decode_cache(cfg, params, qa, enc_out, batch_size,
                                    max_len, dtype)
    return T.init_cache(cfg, batch_size, max_len, dtype,
                        page_size=page_size, n_rows=n_rows)


def decode(qa: QArith, params, cfg, token, cache, cache_pos, *,
           mrope_positions=None, block_table=None):
    if cfg.encdec:
        if block_table is not None:
            raise ValueError("paged KV cache is not supported for enc-dec")
        return ED.encdec_decode_step(qa, params, cfg, token, cache, cache_pos)
    return T.decode_step(qa, params, cfg, token, cache, cache_pos,
                         mrope_positions=mrope_positions,
                         block_table=block_table)
