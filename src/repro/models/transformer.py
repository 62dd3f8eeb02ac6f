"""Decoder-only LM covering the dense / GQA / MoE / SSM / hybrid families.

Layers are *stacked* (leading L dim) and applied with ``lax.scan`` —
essential at 40–60 layers to keep HLO size and compile time bounded on the
512-device dry-run — with ``jax.checkpoint`` (remat) around the body for
training memory. Decode reuses the same scan, carrying per-layer KV /
recurrent state slices as scan xs/ys.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.qarith import QArith
from repro.dist.axes import shard_batch
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.profile import scopes

__all__ = ["init_lm", "forward", "init_cache", "decode_step"]

PyTree = Any


# ---------------------------------------------------------------------------
# Block init / apply (one layer)
# ---------------------------------------------------------------------------

def _block_kind(cfg, layer_idx: int) -> str:
    if cfg.family == "ssm":
        return "mamba"
    if cfg.block_pattern:
        return cfg.block_pattern[layer_idx % len(cfg.block_pattern)]
    return "moe" if cfg.n_experts else "attn"


def block_init(key, cfg, kind: str, dtype=jnp.float32) -> PyTree:
    ks = jax.random.split(key, 4)
    if kind == "mamba":
        return {"ln1": L.norm_init(cfg.norm, cfg.d_model, dtype),
                "mixer": S.mamba_init(ks[0], cfg, dtype)}
    p = {"ln1": L.norm_init(cfg.norm, cfg.d_model, dtype),
         "ln2": L.norm_init(cfg.norm, cfg.d_model, dtype)}
    if kind == "rec":
        p["mixer"] = R.rglru_init(ks[0], cfg, dtype)
    else:  # attn / local_attn / moe
        p["mixer"] = L.attention_init(ks[0], cfg, dtype)
    if kind == "moe":
        p["ffn"] = M.moe_init(ks[1], cfg, dtype)
    else:
        p["ffn"] = M.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p


def block_apply(qa: QArith, cfg, kind: str, p, x, *, positions,
                cache=None, cache_pos=None, mrope_positions=None,
                attn_chunk: int = 1024, block_table=None):
    """Returns (x, new_cache, stats). cache=None for full-sequence
    (train/prefill). ``stats`` holds a dropless expert layer's router
    loss and load counters (:func:`repro.models.moe.dropless_apply`),
    else nothing."""
    with jax.named_scope(scopes.NORM):
        h = L.norm_apply(qa, cfg.norm, p["ln1"], x)
    new_cache = None
    if kind == "mamba":
        if cache is None:
            y = S.mamba_apply(qa, p["mixer"], h, cfg)
        else:
            if x.shape[1] != 1:
                raise ValueError("mamba decode is strictly one token per "
                                 "step; chunked prefill requires an "
                                 "attention-only block pattern")
            y, new_cache = S.mamba_decode_step(qa, p["mixer"], h, cfg, cache)
        return qa.add(x, y), new_cache, {}
    if kind == "rec":
        if cache is None:
            y = R.rglru_apply(qa, p["mixer"], h, cfg)
        else:
            if x.shape[1] != 1:
                raise ValueError("recurrent decode is strictly one token "
                                 "per step; chunked prefill requires an "
                                 "attention-only block pattern")
            y, new_cache = R.rglru_decode_step(qa, p["mixer"], h, cfg, cache)
    else:
        window = (cfg.local_attn_window if kind == "local_attn"
                  else cfg.swa_window)
        with jax.named_scope(scopes.ATTENTION):
            y, new_cache = L.attention_apply(
                qa, p["mixer"], h, cfg, positions=positions, causal=True,
                window=window, cache=cache, cache_pos=cache_pos,
                chunk=attn_chunk, mrope_positions=mrope_positions,
                block_table=block_table)
    x = qa.add(x, y)
    with jax.named_scope(scopes.NORM):
        h = L.norm_apply(qa, cfg.norm, p["ln2"], x)
    stats = {}
    with jax.named_scope(scopes.MLP):
        if kind == "moe" and cfg.moe_strategy == "dropless":
            y, stats = M.dropless_apply(qa, p["ffn"], h, cfg)
        elif kind == "moe":
            y = M.moe_apply(qa, p["ffn"], h, cfg)
        else:
            y = M.mlp_apply(qa, p["ffn"], h, cfg.act_fn)
    return qa.add(x, y), new_cache, stats


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def _layer_plan(cfg) -> tuple[list[str], int, list[str]]:
    """(scan kinds per group-slot, n_groups, remainder kinds).

    Uniform stacks scan one layer per step; hybrid patterns scan one
    *pattern group* per step with the remainder unrolled.
    """
    if cfg.block_pattern:
        plen = len(cfg.block_pattern)
        return (list(cfg.block_pattern), cfg.n_layers // plen,
                [cfg.block_pattern[i] for i in range(cfg.n_layers % plen)])
    kind = _block_kind(cfg, 0)
    return [kind], cfg.n_layers, []


def init_lm(cfg, key, dtype=jnp.float32) -> PyTree:
    kinds, n_groups, rem = _layer_plan(cfg)
    k_embed, k_layers, k_rem, k_head = jax.random.split(key, 4)
    params: dict[str, PyTree] = {
        "embed": L.embed_init(k_embed, cfg.vocab, cfg.d_model, dtype),
        "final_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab, dtype=dtype)

    def group_init(k):
        gks = jax.random.split(k, len(kinds))
        return {f"b{i}": block_init(gks[i], cfg, kind, dtype)
                for i, kind in enumerate(kinds)}

    params["layers"] = jax.vmap(group_init)(jax.random.split(k_layers, n_groups))
    if rem:
        rks = jax.random.split(k_rem, len(rem))
        params["rem"] = {f"b{i}": block_init(rks[i], cfg, kind, dtype)
                         for i, kind in enumerate(rem)}
    return params


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------

def _block_cache(cfg, kind: str, batch: int, max_len: int, dtype,
                 page_size=None, n_rows=None):
    hd = cfg.head_dim
    if kind == "mamba":
        return {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner), dtype),
                "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32)}
    if kind == "rec":
        w = cfg.lru_width or cfg.d_model
        return {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, w), dtype),
                "h": jnp.zeros((batch, w), jnp.float32)}
    window = cfg.local_attn_window if kind == "local_attn" else cfg.swa_window
    clen = min(max_len, window) if window else max_len
    if page_size is not None and clen == max_len:
        # full-context attention layer → paged pool. Window-sized ring
        # layers stay contiguous: their cache is already token-tight.
        return {"k_pages": jnp.zeros((n_rows, page_size, cfg.n_kv_heads, hd), dtype),
                "v_pages": jnp.zeros((n_rows, page_size, cfg.n_kv_heads, hd), dtype),
                "pos_pages": jnp.full((n_rows, page_size), -1, jnp.int32)}
    return (jnp.zeros((batch, clen, cfg.n_kv_heads, hd), dtype),
            jnp.zeros((batch, clen, cfg.n_kv_heads, hd), dtype),
            jnp.full((batch, clen), -1, jnp.int32))


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, *,
               page_size=None, n_rows=None) -> PyTree:
    """Decode cache. ``page_size``/``n_rows`` switch full-context attention
    layers to the paged layout (all layers share one block table, so the
    pool rows are per-layer but the logical→physical map is engine-wide);
    recurrent / ring-window leaves keep the per-slot layout either way."""
    if (page_size is None) != (n_rows is None):
        raise ValueError("page_size and n_rows must be given together")
    kinds, n_groups, rem = _layer_plan(cfg)
    one_group = {f"b{i}": _block_cache(cfg, kind, batch, max_len, dtype,
                                       page_size, n_rows)
                 for i, kind in enumerate(kinds)}
    stacked = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n_groups, *a.shape)).copy(), one_group)
    cache = {"layers": stacked}
    if rem:
        cache["rem"] = {f"b{i}": _block_cache(cfg, kind, batch, max_len, dtype,
                                              page_size, n_rows)
                        for i, kind in enumerate(rem)}
    return cache


# ---------------------------------------------------------------------------
# Forward (train / prefill) and decode
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.EMBED)
def _embed_tokens(qa, cfg, params, tokens_or_embeds):
    if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
        x = jnp.take(params["embed"]["embedding"], tokens_or_embeds, axis=0)
    else:
        # modality-frontend stub path ([vlm]/[audio]): precomputed embeddings
        x = tokens_or_embeds
    x = qa.cast(x)
    if cfg.block_pattern:  # (recurrent)gemma convention
        x = qa.mul(x, jnp.asarray(math.sqrt(cfg.d_model), jnp.float32))
    return x


@jax.named_scope(scopes.HEAD)
def _logits(qa, cfg, params, x):
    h = L.norm_apply(qa, cfg.norm, params["final_norm"], x)
    if cfg.tie_embeddings:
        return qa.matmul_f32out(h, params["embed"]["embedding"].T)
    return qa.matmul_f32out(h, params["lm_head"]["kernel"])


def _layer_stats(stats: list[dict]) -> dict:
    """The expert layers' stats of one forward, each a stack over layers:
    the router loss and the held share averaged, the load's maximum."""
    if not stats:
        return {}
    cat = {k: jnp.concatenate([jnp.ravel(s[k]) for s in stats])
           for k in stats[0]}
    return {"router_loss": cat["router_loss"].mean(),
            "held_share": cat["held_share"].mean(),
            "max_load": cat["max_load"].max()}


def forward(qa: QArith, params, cfg, tokens, *, positions=None,
            mrope_positions=None, remat: bool = True,
            attn_chunk: int = 1024, logits: bool = True,
            with_stats: bool = False):
    """Full-sequence forward. tokens: (B,S) int32 or (B,S,D) embeddings.
    ``with_stats`` also returns the expert layers' router loss and load
    counters (:func:`_layer_stats`; empty for a model without them)."""
    kinds, n_groups, rem = _layer_plan(cfg)
    B, Sq = tokens.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    x = shard_batch(_embed_tokens(qa, cfg, params, tokens))

    def group_body(x, p_group):
        stats = []
        for i, kind in enumerate(kinds):
            x, _, st = block_apply(qa, cfg, kind, p_group[f"b{i}"], x,
                                   positions=positions,
                                   mrope_positions=mrope_positions,
                                   attn_chunk=attn_chunk)
            x = shard_batch(x)
            stats += [st] if st else []
        return x, stats

    body = jax.checkpoint(group_body) if remat else group_body
    x, stats = jax.lax.scan(body, x, params["layers"])
    for i, kind in enumerate(rem):
        x, _, st = block_apply(qa, cfg, kind, params["rem"][f"b{i}"], x,
                               positions=positions,
                               mrope_positions=mrope_positions,
                               attn_chunk=attn_chunk)
        stats += [st] if st else []
    out = _logits(qa, cfg, params, x) if logits else x
    return (out, _layer_stats(stats)) if with_stats else out


def decode_step(qa: QArith, params, cfg, token, cache, cache_pos, *,
                mrope_positions=None, block_table=None):
    """One decode step. token: (B,S) int32 (or (B,S,D) embeds); cache_pos:
    int32 position — a scalar when the whole batch decodes in lock-step
    (S=1), a (B,) vector when every lane sits at its own depth (the
    continuous-batching slot layout, S=1), or a (B,S) matrix of per-token
    positions (chunked prefill; −1 marks padding tokens past a lane's
    chunk). ``block_table`` (B, n_blocks) int32 routes paged-cache leaves.
    Returns (logits, new_cache)."""
    kinds, _, rem = _layer_plan(cfg)
    B, S = token.shape[:2]
    if jnp.ndim(cache_pos) == 0:
        positions = jnp.broadcast_to(cache_pos[None, None], (B, S)).astype(jnp.int32)
    else:
        positions = cache_pos.reshape(B, S).astype(jnp.int32)
    x = shard_batch(_embed_tokens(qa, cfg, params, token))

    def group_body(x, inp):
        p_group, c_group = inp
        new_c = {}
        for i, kind in enumerate(kinds):
            x, new_c[f"b{i}"], _ = block_apply(
                qa, cfg, kind, p_group[f"b{i}"], x, positions=positions,
                cache=c_group[f"b{i}"], cache_pos=cache_pos,
                mrope_positions=mrope_positions, block_table=block_table)
            x = shard_batch(x)
        return x, new_c

    x, new_layer_cache = jax.lax.scan(group_body, x,
                                      (params["layers"], cache["layers"]))
    new_cache = {"layers": new_layer_cache}
    if rem:
        new_cache["rem"] = {}
        for i, kind in enumerate(rem):
            x, new_cache["rem"][f"b{i}"], _ = block_apply(
                qa, cfg, kind, params["rem"][f"b{i}"], x, positions=positions,
                cache=cache["rem"][f"b{i}"], cache_pos=cache_pos,
                mrope_positions=mrope_positions, block_table=block_table)
    return _logits(qa, cfg, params, x), new_cache
