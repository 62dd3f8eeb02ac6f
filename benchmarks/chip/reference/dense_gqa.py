"""Plain float32 reference of a dense decoder with grouped-query
attention (Llama / Qwen2 / Yi): RMSNorm, RoPE (rotate-half), causal
softmax attention, SwiGLU MLP, tied or untied head.

It reads the program's parameter layout (a nested dict with the layers
stacked on a leading axis) and nothing else of the program. Every
matrix product goes through ``mm``: ``exact`` is float32 at
``Precision.HIGHEST``; ``fp8`` rounds both operands to float8 e4m3
(per-tensor scale) first -- the control, one precision below bf16.
Memory stays small: each layer is rematerialised, attention runs in
blocks of queries and the loss in blocks of positions.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
E4M3_MAX = 448.0


def exact(spec: str, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _e4m3(x):
    """Round to float8 e4m3 with a per-tensor scale; the gradient passes
    straight through."""
    x = x.astype(F32)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / E4M3_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def fp8(spec: str, a, b):
    return exact(spec, _e4m3(a), _e4m3(b))


MATMULS = {"exact": exact, "fp8": fp8}


def _rms(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[..., None] * inv               # (B,S,d/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dense(mm, p, x):
    y = mm("bsd,df->bsf", x, p["kernel"])
    return y + p["bias"].astype(F32) if "bias" in p else y


def _attend(mm, q, k, v, block: int):
    """Causal attention; q (B,S,H,hd), k/v (B,S,H,hd) already expanded."""
    B, S, H, hd = q.shape
    block = block if S % block == 0 else S
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        s = mm("bqhd,bkhd->bhqk", qb, k) / math.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, jnp.arange(S // block))      # (n,B,blk,H,hd)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def layer(cfg, mm, lp, x, pos, block: int = 512):
    """One decoder layer. ``lp`` is that layer's slice of the stack."""
    B, S, _ = x.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // H)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    a = lp["mixer"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _rope(_dense(mm, a["wq"], h).reshape(B, S, H, hd), pos, theta)
    k = _rope(_dense(mm, a["wk"], h).reshape(B, S, Hkv, hd), pos, theta)
    v = _dense(mm, a["wv"], h).reshape(B, S, Hkv, hd)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    o = _attend(mm, q, k, v, block).reshape(B, S, H * hd)
    x = x + _dense(mm, a["wo"], o)
    f = lp["ffn"]
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = mm("bsd,df->bsf", h, f["w_gate"])
    u = mm("bsd,df->bsf", h, f["w_up"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, f["w_down"])


def frozen(cfg: dict) -> tuple:
    """The configuration's numbers and flags as a hashable tuple (a
    static argument of a jitted function)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def head_matrix(cfg, params):
    """(D, V): the untied head, or the embedding's transpose."""
    if cfg["tie_word_embeddings"]:
        return params["embed"]["embedding"].T
    return params["lm_head"]["kernel"]


def hidden(cfg, params, tokens, mm=exact):
    """Final-normed hidden states (B,S,D) of a teacher-forced pass."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    body = jax.checkpoint(lambda x, lp: (layer(cfg, mm, lp, x, pos), None))
    x, _ = jax.lax.scan(body, x, params["layers"]["b0"])
    return _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def loss(cfg, params, tokens, labels, mm=exact, block: int = 512):
    """Mean next-token cross entropy, logits made ``block`` rows at a time."""
    h = hidden(cfg, params, tokens, mm)
    D = h.shape[-1]
    h, labels = h.reshape(-1, D), labels.reshape(-1)
    n = h.shape[0]
    block = block if n % block == 0 else n
    w = head_matrix(cfg, params)

    @jax.checkpoint
    def one(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * block, block)
        lb = jax.lax.dynamic_slice_in_dim(labels, i * block, block)
        z = mm("sd,dv->sv", hb, w)
        gold = jnp.take_along_axis(z, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold)

    return jnp.sum(jax.lax.map(one, jnp.arange(n // block))) / n


@partial(jax.jit, static_argnums=(0, 3))
def logits_rows(cfg_items, params, h_rows, mm_name: str):
    """Logits (n, V) of the hidden rows ``h_rows`` (n, D)."""
    cfg = dict(cfg_items)
    return MATMULS[mm_name]("sd,dv->sv", h_rows, head_matrix(cfg, params))
