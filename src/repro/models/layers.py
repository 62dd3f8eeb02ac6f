"""Reusable quantized layers: dense, embedding, RoPE/M-RoPE, GQA attention.

All contractions go through :class:`repro.core.qarith.QArith` — bf16 inputs,
f32 accumulation (the FMAC model / MXU), one output rounding. Attention is
treated as a single fused op (internals in f32, output rounded once), which
is both the paper's footnote-4 convention and how fused TPU attention
kernels behave.
"""
from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.qarith import QArith
from repro.kernels import dispatch

__all__ = ["dense_init", "dense", "embed_init", "rope", "mrope",
           "flash_attention", "decode_attention", "attention_init",
           "attention_apply", "copy_page_rows", "norm_init", "norm_apply"]


def copy_page_rows(pages, dst, src, pdim: int = 0):
    """In-graph physical page copy: ``pages[dst[j]] = pages[src[j]]``.

    The copy-on-write primitive of the prefix cache
    (:mod:`repro.serve.paged`): before a lane's first write into a page
    it shares with the prefix index or another lane, the engine remaps
    that block to a private page and the serve step copies the row here
    — K gathered rows, never the whole pool. ``dst``/``src`` are (K,)
    i32 with a *static* K; padding entries carry ``dst = n_rows`` (out
    of range ⇒ dropped at the scatter, the same convention as the null-
    page write guard) and ``src = 0`` (harmlessly gathered). ``pdim``
    is the page-row dim: 0 for a bare paged leaf, 1 under a stacked
    layer dim (:data:`repro.dist.partition.STACKED_CACHE_ROOTS`).

    Applies identically to ``k_pages``/``v_pages`` *and* ``pos_pages``:
    the private copy must carry the source positions, or the copied KV
    cells would mask away as empty.
    """
    if pdim == 0:
        return pages.at[dst].set(pages[src], mode="drop")
    assert pdim == 1, pdim
    return pages.at[:, dst].set(pages[:, src], mode="drop")


# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, dtype=jnp.float32):
    std = 1.0 / math.sqrt(d_in)
    p = {"kernel": (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense(qa: QArith, p, x):
    y = qa.einsum("...d,df->...f", x, p["kernel"])
    if "bias" in p:
        y = qa.add(y, p["bias"])
    return y


def embed_init(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"embedding": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                          * (1.0 / math.sqrt(d_model))).astype(dtype)}


def norm_init(kind: str, d: int, dtype=jnp.float32):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "ln":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(qa: QArith, kind: str, p, x):
    if kind == "ln":
        return qa.layernorm(x, p["scale"], p["bias"])
    return qa.rmsnorm(x, p["scale"])


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def _rope_angles(positions, head_dim: int, theta: float):
    # positions: (..., S) int32 → (..., S, head_dim/2) angles, f32
    freqs = jnp.exp(-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                    / head_dim * math.log(theta))
    return positions[..., None].astype(jnp.float32) * freqs


def rope(x, positions, theta: float = 10000.0):
    """Standard RoPE. x: (B,S,H,D); positions: (B,S) or (S,)."""
    d = x.shape[-1]
    ang = _rope_angles(positions, d, theta)               # (B,S,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                               # (B,S,1,D/2)
    sin = sin[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope(x, positions_3d, sections: tuple[int, ...], theta: float = 10000.0):
    """Qwen2-VL M-RoPE: rotary halves split into (t,h,w) sections, each
    rotated by its own position stream. positions_3d: (3, B, S)."""
    d = x.shape[-1]
    ang_full = _rope_angles(positions_3d, d, theta)       # (3,B,S,D/2)
    idx = []
    for i, sec in enumerate(sections):
        idx += [i] * sec
    sel = jnp.asarray(idx)                                # (D/2,) section id
    # choose, per rotary frequency, which position stream (t/h/w) drives it
    ang = jnp.take_along_axis(jnp.moveaxis(ang_full, 0, -1),  # (B,S,D/2,3)
                              sel[None, None, :, None], axis=-1)[..., 0]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA + causal/SWA masks, flash-chunked for long sequences)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _mask(q_pos, k_pos, *, causal: bool, window):
    # q_pos: (Sq,), k_pos: (Sk,) → bool (Sq, Sk) "allowed"
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    ok &= k_pos[None, :] >= 0            # ring-buffer empty slots carry pos=-1
    return ok


def _expand_kv(k, n_heads: int):
    """GQA → MHA lowering: repeat KV heads to the full q-head count.

    This is the Megatron-style form that keeps the attention einsums
    shardable on the (single) head dimension for any tp ≤ n_heads with
    n_heads % tp == 0 — GSPMD cannot split one mesh axis across the
    (kv_heads, group) pair that the grouped form would need.
    """
    B, S, Hkv, D = k.shape
    if Hkv == n_heads:
        return k
    g = n_heads // Hkv
    return jnp.repeat(k, g, axis=2)


@functools.lru_cache(maxsize=64)
def _flash_core(causal: bool, window, chunk: int, softcap, dtype_name: str):
    """Flash attention with a custom VJP (the production memory fix).

    Without it, JAX's scan linearization materializes the per-chunk f32
    probabilities as backward residuals — ~10× the layer activation
    budget at 4k context (§Perf iteration 1 in EXPERIMENTS.md). The
    custom backward recomputes p per chunk from (q, k, LSE); residuals
    are just (q, k, v, out, lse).
    """
    dtype = jnp.dtype(dtype_name)

    def _scores(q, kc, q_pos, k_pos):
        D = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        tanh_term = None
        if softcap:
            raw = s / softcap
            tanh_term = jnp.tanh(raw)
            s = softcap * tanh_term
        ok = _mask(q_pos, k_pos, causal=causal, window=window)
        return jnp.where(ok[None, None], s, NEG_INF), tanh_term

    def fwd_impl(q, k, v):
        from repro.dist.axes import shard_heads
        B, Sq, Hq, D = q.shape
        Sk = k.shape[1]
        n = Sk // chunk
        q_pos = jnp.arange(Sq)
        ks = jnp.moveaxis(k.reshape(B, n, chunk, Hq, D), 1, 0)
        vs = jnp.moveaxis(v.reshape(B, n, chunk, Hq, D), 1, 0)

        def body(carry, inp):
            m, l, acc = carry
            kc, vc, j = inp
            k_pos = j * chunk + jnp.arange(chunk)
            s, _ = _scores(q, kc, q_pos, k_pos)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(dtype), vc,
                            preferred_element_type=jnp.float32)
            # pin the carry shardings: GSPMD's loop fixed point otherwise
            # replicates the head axis (§Perf command-r iteration 2)
            return (shard_heads(m_new, 1), shard_heads(l_new, 1),
                    shard_heads(acc * corr[..., None] + pv, 1)), None

        m0 = shard_heads(jnp.full((B, Hq, Sq), NEG_INF, jnp.float32), 1)
        l0 = shard_heads(jnp.zeros((B, Hq, Sq), jnp.float32), 1)
        a0 = shard_heads(jnp.zeros((B, Hq, Sq, D), jnp.float32), 1)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (ks, vs, jnp.arange(n)))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).astype(dtype)      # (B,H,Sq,D)
        lse = m + jnp.log(l_safe)
        return out, lse

    @jax.custom_vjp
    def flash(q, k, v):
        out, _ = fwd_impl(q, k, v)
        return out

    def flash_fwd(q, k, v):
        out, lse = fwd_impl(q, k, v)
        return out, (q, k, v, out, lse)

    def flash_bwd(res, dout):
        from repro.dist.axes import shard_heads
        q, k, v, out, lse = res
        B, Sq, Hq, D = q.shape
        Sk = k.shape[1]
        n = Sk // chunk
        q_pos = jnp.arange(Sq)
        dout_f = dout.astype(jnp.float32)
        # row term: D_i = Σ_d dout·out
        Drow = shard_heads(
            jnp.einsum("bhqd,bhqd->bhq", dout_f, out.astype(jnp.float32)), 1)
        ks = jnp.moveaxis(k.reshape(B, n, chunk, Hq, D), 1, 0)
        vs = jnp.moveaxis(v.reshape(B, n, chunk, Hq, D), 1, 0)

        def body(dq_acc, inp):
            kc, vc, j = inp
            k_pos = j * chunk + jnp.arange(chunk)
            s, tanh_term = _scores(q, kc, q_pos, k_pos)
            p = jnp.exp(s - lse[..., None])                # (B,H,Sq,chunk)
            pb = p.astype(dtype)
            dv = jnp.einsum("bhqk,bhqd->bkhd", pb, dout.astype(dtype),
                            preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhqd,bkhd->bhqk", dout.astype(dtype), vc,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - Drow[..., None])
            if softcap:
                ds = ds * (1.0 - jnp.square(tanh_term))
            ds = (ds / math.sqrt(D)).astype(dtype)
            dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, kc,
                                         preferred_element_type=jnp.float32)
            dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(dtype),
                            preferred_element_type=jnp.float32)
            return shard_heads(dq_acc, 2), (shard_heads(dk.astype(dtype), 2),
                                            shard_heads(dv.astype(dtype), 2))

        dq0 = shard_heads(jnp.zeros((B, Sq, Hq, D), jnp.float32), 2)
        dq, (dks, dvs) = jax.lax.scan(body, dq0, (ks, vs, jnp.arange(n)))
        dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, Hq, D)
        dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, Hq, D)
        return dq.astype(q.dtype), dk, dv

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def flash_attention(qa: QArith, q, k, v, *, q_offset=0, causal=True,
                    window=None, chunk: int = 1024, softcap=None):
    """Online-softmax attention over KV chunks (memory O(Sq·chunk)).

    q: (B,Sq,Hq,D); k,v: (B,Sk,Hkv,D). One fused op per the FMAC model:
    f32 internals, single rounding of the output. Backward uses the flash
    custom-VJP (recompute, not residuals). When the model axis does not
    divide the head count, heads are ZERO-PADDED to the next multiple
    (exact semantics — padded outputs are sliced off before wo) so the
    attention still shards instead of replicating (§Perf llama4 iter).
    """
    from repro.dist.axes import padded_head_count, shard_heads

    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    del q_offset  # full-sequence path starts at 0; decode uses decode_attention
    k = _expand_kv(k, Hq)
    v = _expand_kv(v, Hq)
    Hp = padded_head_count(Hq)
    if Hp != Hq:
        pad = [(0, 0), (0, 0), (0, Hp - Hq), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    k = shard_heads(k, 2)
    v = shard_heads(v, 2)
    q = shard_heads(q, 2)
    chunk_eff = min(chunk, Sk)
    assert Sk % chunk_eff == 0, (Sk, chunk_eff)
    flash = _flash_core(bool(causal), window, int(chunk_eff), softcap,
                        jnp.dtype(qa.dtype).name)
    out = flash(q, k, v)                                   # (B,Hp,Sq,D)
    out = jnp.moveaxis(out, 1, 2)
    out = shard_heads(out, 2)
    if Hp != Hq:
        out = out[:, :, :Hq, :]
    return qa.cast(out)


def decode_attention(qa: QArith, q, k_cache, v_cache, k_pos, *, q_pos,
                     window=None, softcap=None):
    """Attention of one (or a chunk of) query token(s) against a KV cache.

    q: (B,S,Hq,D); caches: (B,Sc,Hkv,D); k_pos: (B,Sc) int32 positions
    (−1 ⇒ empty slot); q_pos: (B,) single-token position or (B,S)
    per-query positions (−1 ⇒ masked query row — chunked prefill's
    padding lanes). GQA keeps the grouped form here (decode is
    memory-bound on the cache; no head-TP reshape).

    S=1 inside a ``kernels.dispatch.fused_decode()`` context runs the
    whole pipeline as one Pallas kernel per lane (same op order, same
    single output rounding — token parity preserved). S>1 (chunked
    prefill) always takes the generic path: every query row masks the
    same (Sc,) cache axis, so a chunk step is bitwise-identical to
    feeding its tokens one step at a time.
    """
    B, S, Hq, D = q.shape
    if S == 1:
        q_pos = q_pos.reshape(B)
        if dispatch.fused_decode_enabled():
            from repro.kernels.decode_attention import fused_decode_attention
            out = fused_decode_attention(q, k_cache, v_cache, k_pos, q_pos,
                                         window=window, softcap=softcap,
                                         p_dtype=qa.dtype)
            return qa.cast(out)
        _, Sc, Hkv, _ = k_cache.shape
        group = Hq // Hkv
        qg = q.reshape(B, Hkv, group, D)
        scale = 1.0 / math.sqrt(D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        ok = (k_pos[:, None, None, :] <= q_pos[:, None, None, None]) & \
             (k_pos[:, None, None, :] >= 0)
        if window is not None:
            ok &= q_pos[:, None, None, None] - k_pos[:, None, None, :] < window
        s = jnp.where(ok, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(qa.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        return qa.cast(out.reshape(B, 1, Hq, D))
    # multi-query chunk: per-row causal masks over the same cache axis.
    # Reduction order per query row equals the S=1 path's (same (Sc,)
    # axis, masked cells contribute exact zeros), which is what makes
    # chunked prefill token-for-token identical to one-at-a-time feeding.
    _, Sc, Hkv, _ = k_cache.shape
    group = Hq // Hkv
    qg = q.reshape(B, S, Hkv, group, D)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bshgd,bkhd->bshgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qp = q_pos.reshape(B, S)[:, :, None, None, None]
    kp = k_pos[:, None, None, None, :]
    ok = (kp <= qp) & (kp >= 0)
    if window is not None:
        ok &= qp - kp < window
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bshgk,bkhd->bshgd", p.astype(qa.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return qa.cast(out.reshape(B, S, Hq, D))


# ---------------------------------------------------------------------------
# Full attention block (projections + rope + attend)
# ---------------------------------------------------------------------------

def attention_init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    hd = cfg.head_dim
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init("rms", hd, dtype)
        p["k_norm"] = norm_init("rms", hd, dtype)
    return p


def attention_apply(qa: QArith, p, x, cfg, *, positions, causal=True,
                    window=None, cache=None, cache_pos=None, chunk=1024,
                    kv_override=None, mrope_positions=None, block_table=None):
    """x: (B,S,Dm). Returns (out, new_cache_kv) — cache_kv=(k,v,k_pos) when
    decoding, else None. ``kv_override`` supplies cross-attention K/V.

    Two decode cache layouts are supported:

    * contiguous tuple ``(k_cache, v_cache, k_pos)`` — one `max_len`
      (or window-sized ring) stripe per lane;
    * paged dict ``{"k_pages", "v_pages", "pos_pages"}`` — a shared
      (R, page, Hkv, hd) pool plus a per-lane ``block_table`` (B, n_blocks)
      mapping logical block b → physical page row. Row R−1 is the null
      page: block-table entries of unmapped blocks point there, it is
      never written (writes routed to it are dropped), so its positions
      stay −1 and gathered null blocks mask to exact zeros. Token at
      logical position p always lands at gathered-view index p, so the
      paged view is bitwise-identical to a contiguous cache of the same
      length — the parity contract survives the indirection.

      Pages mapped *shared* by the prefix cache are never written
      through this path: the engine copy-on-write-remaps a shared block
      to a private page (:func:`copy_page_rows`, applied by the serve
      step before decode) before any lane writes into it, so by the
      time the scatter below runs, every written block is private. The
      null-row guard remains the backstop for scheduler bugs.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = dense(qa, p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    if cfg.qk_norm:     # RMSNorm over head_dim, before RoPE
        q = norm_apply(qa, "rms", p["q_norm"], q)
    if kv_override is None:
        k = dense(qa, p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            k = norm_apply(qa, "rms", p["k_norm"], k)
        v = dense(qa, p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
        if cfg.rope_type == "mrope" and mrope_positions is not None:
            q = mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
            k = mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        elif cfg.rope_type != "none":
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override

    new_cache = None
    if isinstance(cache, dict):
        # ---- paged pool: scatter through the block table, gather a view.
        assert block_table is not None, "paged cache requires a block table"
        kp, vp, pp = cache["k_pages"], cache["v_pages"], cache["pos_pages"]
        R_, Psz = pp.shape
        n_blocks = block_table.shape[1]
        tpos = positions.reshape(B, S).astype(jnp.int32)
        blk = jnp.clip(jnp.where(tpos >= 0, tpos // Psz, 0), 0, n_blocks - 1)
        page = jnp.take_along_axis(block_table, blk, axis=1)
        # parked / padding tokens (pos −1) and writes aimed at the null
        # row (an unmapped block — scheduler bug guard) go out of range
        # and are dropped.
        page = jnp.where((tpos >= 0) & (page < R_ - 1), page, R_)
        off = jnp.where(tpos >= 0, tpos % Psz, 0)
        kp = kp.at[page.ravel(), off.ravel()].set(
            k.reshape(B * S, cfg.n_kv_heads, hd).astype(kp.dtype), mode="drop")
        vp = vp.at[page.ravel(), off.ravel()].set(
            v.reshape(B * S, cfg.n_kv_heads, hd).astype(vp.dtype), mode="drop")
        pp = pp.at[page.ravel(), off.ravel()].set(
            tpos.ravel(), mode="drop")
        new_cache = {"k_pages": kp, "v_pages": vp, "pos_pages": pp}
        q_pos = tpos[:, -1] if S == 1 else tpos
        if S == 1 and dispatch.fused_decode_enabled():
            from repro.kernels.decode_attention import (
                fused_paged_decode_attention)
            out = fused_paged_decode_attention(
                q, kp, vp, pp, block_table, q_pos, window=window,
                softcap=cfg.attn_logit_softcap, p_dtype=qa.dtype)
            out = qa.cast(out)
        else:
            k_view = kp[block_table].reshape(B, n_blocks * Psz,
                                             cfg.n_kv_heads, hd)
            v_view = vp[block_table].reshape(B, n_blocks * Psz,
                                             cfg.n_kv_heads, hd)
            pos_view = pp[block_table].reshape(B, n_blocks * Psz)
            out = decode_attention(qa, q, k_view, v_view, pos_view,
                                   q_pos=q_pos, window=window,
                                   softcap=cfg.attn_logit_softcap)
    elif cache is not None:
        # cache_pos is either a scalar step counter (whole batch decodes in
        # lock-step: train-style generate) or a per-lane (B,) position
        # vector (continuous batching: every slot sits at its own depth).
        # Ring-buffer indexing (mod cache length) supports SWA/local
        # windows where the cache is window-sized.
        k_cache, v_cache, k_pos = cache
        Sc = k_cache.shape[1]
        if jnp.ndim(cache_pos) == 0:
            slot = cache_pos % Sc
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), slot, axis=1)
            k_pos = jax.lax.dynamic_update_slice_in_dim(
                k_pos, positions.reshape(B, S).astype(k_pos.dtype), slot, axis=1)
            q_pos = positions.reshape(B, S)[:, -1] if S == 1 \
                else positions.reshape(B, S)
        else:
            # per-lane scatter: S tokens per slot at per-lane depths (the
            # continuous-batching layout; S > 1 is a prefill chunk).
            # Lanes/tokens with position < 0 are parked (continuous
            # batching's `active` mask or chunk padding): their write
            # index is routed out of range and dropped, so masking costs
            # nothing on the KV pool.
            tpos = positions.reshape(B, S).astype(jnp.int32)
            lane = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
            slot = jnp.where(tpos >= 0, tpos % Sc, Sc)
            k_cache = k_cache.at[lane, slot].set(
                k.astype(k_cache.dtype), mode="drop")
            v_cache = v_cache.at[lane, slot].set(
                v.astype(v_cache.dtype), mode="drop")
            k_pos = k_pos.at[lane, slot].set(tpos, mode="drop")
            q_pos = tpos[:, -1] if S == 1 else tpos
        out = decode_attention(qa, q, k_cache, v_cache, k_pos,
                               q_pos=q_pos,
                               window=window, softcap=cfg.attn_logit_softcap)
        new_cache = (k_cache, v_cache, k_pos)
    else:
        out = flash_attention(qa, q, k, v, causal=causal, window=window,
                              chunk=chunk, softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, S, cfg.n_heads * hd)
    return dense(qa, p["wo"], out), new_cache
