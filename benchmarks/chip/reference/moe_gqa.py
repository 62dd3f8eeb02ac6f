"""Plain float32 reference of a decoder whose every layer is grouped-
query attention with QK-norm and a mixture of experts (the Qwen3-MoE
block: SDAR-30B-A3B), holding one chip's share of the experts.

    x += o(attn(rope(qnorm(q)), rope(knorm(k)), v))          on rms(x)
    x += sum over the top-k e held here of g_e down_e(silu(gate_e(h)) up_e(h))
                                                              on h = rms(x)

The router is softmax over all of its ``num_experts * expert_parallel``
outputs, then top-k, renormalised over the k chosen
(``norm_topk_prob``). Experts held elsewhere add nothing (their gate is
0 here, as in the program). Every token goes through every held expert
and is weighted by its gate, 0 where that expert was not chosen: no
sorting, no grouped matmul. The loss adds the Switch load-balancing
loss, ``router_aux_loss_coef`` times E * sum_e (assignments to e / T) *
(mean probability of e), averaged over layers, over all E outputs.

It reads the program's parameter layout and nothing else of the
program; the attention, norms, RoPE, head and matmuls (``exact``,
``fp8``) are those of :mod:`reference.dense_gqa`. Memory stays small:
each layer is rematerialised, attention runs in blocks of queries, the
experts in blocks of tokens and the loss in blocks of positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.dense_gqa import (F32, MATMULS, _attend, _dense, _rms, _rope,
                                 exact, frozen, head_matrix, logits_rows)

__all__ = ["MATMULS", "frozen", "hidden", "logits_rows", "loss", "layer"]


def _router(cfg, mm, f, h):
    """Gates (T, E) -- 0 off the top-k -- and the layer's router loss."""
    E = cfg["num_experts"] * cfg["expert_parallel"]
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("td,de->te", h, f["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32), axis=1)     # (T,E)
    gates = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32) * top[..., None], 1)
    per_expert = jax.lax.stop_gradient(jnp.sum(chosen, 0)) / h.shape[0]
    return gates, E * jnp.sum(per_expert * jnp.mean(probs, 0))


def _experts(cfg, mm, f, h, gates, block: int):
    """Every token through every held expert, weighted by its gate."""
    first, held = cfg.get("expert_offset", 0), cfg["num_experts"]
    g_here = gates[:, first:first + held]                          # (T,H)
    T, D = h.shape
    block = block if T % block == 0 else T

    @jax.checkpoint
    def one(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * block, block)
        gb = jax.lax.dynamic_slice_in_dim(g_here, i * block, block)
        a = jax.nn.silu(mm("td,hdf->thf", hb, f["we_gate"])) \
            * mm("td,hdf->thf", hb, f["we_up"])
        y = mm("thf,hfd->thd", a, f["we_down"])
        return jnp.sum(y * gb[..., None], axis=1)

    return jax.lax.map(one, jnp.arange(T // block)).reshape(T, D)


def layer(cfg, mm, lp, x, pos, block: int = 512):
    """One layer; returns the new x and its router loss."""
    B, S, D = x.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", D // H)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    a = lp["mixer"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = _dense(mm, a["wq"], h).reshape(B, S, H, hd)
    k = _dense(mm, a["wk"], h).reshape(B, S, Hkv, hd)
    q = _rope(_rms(q, a["q_norm"]["scale"], eps), pos, theta)
    k = _rope(_rms(k, a["k_norm"]["scale"], eps), pos, theta)
    v = _dense(mm, a["wv"], h).reshape(B, S, Hkv, hd)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)
    o = _attend(mm, q, k, v, block).reshape(B, S, H * hd)
    x = x + _dense(mm, a["wo"], o)
    h = _rms(x, lp["ln2"]["scale"], eps).reshape(B * S, D)
    gates, router_loss = _router(cfg, mm, lp["ffn"], h)
    y = _experts(cfg, mm, lp["ffn"], h, gates, block)
    return x + y.reshape(B, S, D), router_loss


def _stack(cfg, params, tokens, mm):
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    body = jax.checkpoint(lambda x, lp: layer(cfg, mm, lp, x, pos))
    x, router_loss = jax.lax.scan(body, x, params["layers"]["b0"])
    return (_rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"]),
            jnp.mean(router_loss))


def hidden(cfg, params, tokens, mm=exact):
    """Final-normed hidden states (B,S,D) of a teacher-forced pass."""
    return _stack(cfg, params, tokens, mm)[0]


def loss(cfg, params, tokens, labels, mm=exact, block: int = 512):
    """Mean next-token cross entropy, logits made ``block`` rows at a
    time, plus the router loss at ``router_aux_loss_coef``."""
    h, router_loss = _stack(cfg, params, tokens, mm)
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

    xent = jnp.sum(jax.lax.map(one, jnp.arange(n // block))) / n
    return xent + cfg["router_aux_loss_coef"] * router_loss
