"""The dropless expert layer that holds a share of the experts, and
QK-norm attention: each against a plain jnp version on the CPU, with
seeded random weights at small sizes.

* the share layer (router over all experts, grouped matmuls over the
  held ones) equals a plain layer that sends every token through every
  held expert weighted by its gate, forward and gradients;
* the shares of all the chips sum to the uncut layer;
* no token is dropped where the capacity path drops some;
* QK-norm is an RMSNorm over head_dim on q and k before RoPE;
* under a 16-bit policy every leaf, the router too, is in the param
  dtype, and each expert leaf takes the fused update's native path.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import QArith, get_policy
from repro.kernels.fused_adamw import tiling
from repro.models import layers as L
from repro.models import moe as M
from repro.models import registry as R
from repro.optim import constant
from repro.optim.fused import fused_adamw_optimizer
from repro.train.step import make_train_step
from repro.train.train_state import make_train_state

F32 = get_policy("fp32")
BF16 = get_policy("bf16_sr_kahan")
CFG = ModelConfig(
    name="moe-share", family="lm", n_layers=2, d_model=32, vocab=128,
    n_heads=4, n_kv_heads=2, head_dim=8, d_ff=16,
    n_experts=16, top_k=4, experts_held=4, expert_offset=8,
    moe_strategy="dropless", norm_topk_prob=True, router_aux_coef=1e-3,
    qk_norm=True, tie_embeddings=False)
T = 64


def _params(cfg, key=0, dtype=jnp.float32):
    return M.moe_init(jax.random.PRNGKey(key), cfg, dtype)


def _x(key=1, n=T, d=CFG.d_model):
    return jax.random.normal(jax.random.PRNGKey(key), (1, n, d), jnp.float32)


def plain_layer(p, x, cfg):
    """Every token through every held expert, weighted by its gate (0 off
    the top-k and for experts held elsewhere)."""
    xt = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    hp = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(jnp.dot(xt, p["router"].astype(jnp.float32),
                                   precision=hp), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, cfg.n_experts) * top[..., None], 1)
    first = cfg.expert_offset
    gates = gates[:, first:first + cfg.n_held]
    w = {k: p[k].astype(jnp.float32) for k in ("we_gate", "we_up", "we_down")}
    a = jax.nn.silu(jnp.einsum("td,edf->tef", xt, w["we_gate"], precision=hp)) \
        * jnp.einsum("td,edf->tef", xt, w["we_up"], precision=hp)
    y = jnp.einsum("tef,efd->ted", a, w["we_down"], precision=hp)
    return jnp.sum(y * gates[..., None], axis=1).reshape(x.shape)


def test_share_layer_matches_plain_forward_and_gradients():
    qa = QArith(F32)
    p, x = _params(CFG), _x()
    ct = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def loss(fn):
        return lambda p, x: jnp.sum(fn(p, x) * ct)

    with jax.default_matmul_precision("highest"):
        prog = loss(lambda p, x: M.dropless_apply(qa, p, x, CFG)[0])
        v, g = jax.value_and_grad(prog, argnums=(0, 1))(p, x)
    v_ref, g_ref = jax.value_and_grad(
        loss(lambda p, x: plain_layer(p, x, CFG)), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(v, v_ref, rtol=1e-5)
    for name in ("router", "we_gate", "we_up", "we_down"):
        np.testing.assert_allclose(g[0][name], g_ref[0][name],
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(g[1], g_ref[1], rtol=1e-4, atol=1e-5)


def test_shares_sum_to_the_uncut_layer():
    """Four chips of expert parallel 4, each holding 4 of the 16 experts
    with the same router, give parts that add up to the whole layer."""
    qa = QArith(F32)
    whole = dataclasses.replace(CFG, experts_held=0, expert_offset=0)
    p, x = _params(whole), _x()
    held = CFG.n_held
    parts = []
    with jax.default_matmul_precision("highest"):
        y_whole, stats = M.dropless_apply(qa, p, x, whole)
        for chip in range(CFG.n_experts // held):
            cfg = dataclasses.replace(CFG, expert_offset=chip * held)
            share = dict(p, **{k: p[k][chip * held:(chip + 1) * held]
                               for k in ("we_gate", "we_up", "we_down")})
            y, st = M.dropless_apply(qa, share, x, cfg)
            parts.append(y)
            assert float(st["router_loss"]) == pytest.approx(
                float(stats["router_loss"]))
    np.testing.assert_allclose(sum(parts), y_whole, rtol=1e-5, atol=1e-6)
    assert float(stats["held_share"]) == 1.0
    np.testing.assert_allclose(sum(parts), plain_layer(p, x, whole),
                               rtol=1e-4, atol=1e-5)


def _crowded(cfg, key=0):
    """A router biased so that nearly every token picks experts 0 and 1."""
    p = _params(cfg, key)
    bias = jnp.zeros((cfg.d_model, cfg.n_experts)).at[:, :2].set(4.0)
    return dict(p, router=p["router"] + bias)


def test_no_token_dropped_where_capacity_drops():
    cfg = dataclasses.replace(CFG, top_k=2, experts_held=0, expert_offset=0,
                              n_experts=8, capacity_factor=1.25)
    qa = QArith(F32)
    p = _crowded(cfg)
    x = jnp.abs(_x(3, d=cfg.d_model))          # every row leans one way
    with jax.default_matmul_precision("highest"):
        y, stats = M.dropless_apply(qa, p, x, cfg)
        want = plain_layer(p, x, cfg)
        capped = M.moe_apply(qa, p, x, dataclasses.replace(
            cfg, moe_strategy="gather"))
    # the load sits on two of eight experts: far above their capacity
    assert float(stats["max_load"]) > 3.0
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    dropped = np.abs(np.asarray(capped - want)).max(axis=-1) > 1e-4
    assert dropped.sum() > T // 4


def test_counters_and_router_loss_reach_the_step():
    """The train step's loss is the cross entropy plus the router loss at
    its coefficient, and it reports the load counters."""
    params = R.init(CFG, jax.random.PRNGKey(0), BF16.param_dtype)
    opt = fused_adamw_optimizer(BF16, b2=0.997)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                                          CFG.vocab)}
    batch["labels"] = jnp.roll(batch["tokens"], -1, axis=1)
    losses = {}
    for coef in (0.0, 1.0):
        cfg = dataclasses.replace(CFG, router_aux_coef=coef)
        step = jax.jit(make_train_step(cfg, BF16, opt, constant(0.0),
                                       attn_chunk=16))
        _, m = step(make_train_state(params, opt), batch, 0)
        losses[coef] = float(m["loss"])
    assert 0.0 < float(m["expert_held_share"]) < 1.0
    assert float(m["expert_max_load"]) >= 1.0
    # the router loss is k = 4 at perfect balance, and near it here
    assert 3.0 < losses[1.0] - losses[0.0] < 8.0


def plain_qk_norm_attention(p, x, cfg):
    hp = jax.lax.Precision.HIGHEST
    B, S, _ = x.shape
    hd, H, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads

    def proj(name, heads):
        return jnp.dot(x, p[name]["kernel"], precision=hp).reshape(
            B, S, heads, hd)

    def rms(z, scale):
        return z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True)
                                 + 1e-6) * scale

    pos = jnp.arange(S)
    q = L.rope(rms(proj("wq", H), p["q_norm"]["scale"]), pos, cfg.rope_theta)
    k = L.rope(rms(proj("wk", Hkv), p["k_norm"]["scale"]), pos,
               cfg.rope_theta)
    v = proj("wv", Hkv)
    k, v = jnp.repeat(k, H // Hkv, 2), jnp.repeat(v, H // Hkv, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hp) / math.sqrt(hd)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=hp)
    return jnp.dot(o.reshape(B, S, H * hd), p["wo"]["kernel"], precision=hp)


def test_qk_norm_attention_matches_plain():
    p = L.attention_init(jax.random.PRNGKey(0), CFG, jnp.float32)
    # scales other than 1, so that a norm applied to the wrong side shows
    for name in ("q_norm", "k_norm"):
        p[name]["scale"] = 1.0 + 0.5 * jax.random.normal(
            jax.random.PRNGKey(len(name)), (CFG.head_dim,))
    x = _x(4, n=32)
    pos = jnp.broadcast_to(jnp.arange(32)[None], (1, 32))
    with jax.default_matmul_precision("highest"):
        y, _ = L.attention_apply(QArith(F32), p, x, CFG, positions=pos,
                                 chunk=16)
    np.testing.assert_allclose(y, plain_qk_norm_attention(p, x, CFG),
                               rtol=1e-4, atol=1e-5)
    off = dataclasses.replace(CFG, qk_norm=False)
    assert "q_norm" not in L.attention_init(jax.random.PRNGKey(0), off)


def test_every_leaf_in_param_dtype_and_experts_native():
    tree = jax.eval_shape(lambda k: R.init(CFG, k, BF16.param_dtype),
                          jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    assert all(x.dtype == jnp.bfloat16 for _, x in leaves)
    ffn = tree["layers"]["b0"]["ffn"]
    assert ffn["router"].shape == (CFG.n_layers, CFG.d_model, CFG.n_experts)
    assert ffn["we_gate"].shape == (CFG.n_layers, CFG.n_held, CFG.d_model,
                                    CFG.d_ff)
    # SDAR-30B-A3B's share: (L, 32, 2048, 768) and (L, 32, 768, 2048)
    for shape in ((4, 32, 2048, 768), (4, 32, 768, 2048), (4, 2048, 128),
                  (4, 128)):
        assert tiling(shape)[0], shape
    # the capacity paths keep every expert and an f32 router
    capped = dataclasses.replace(CFG, moe_strategy="gather", experts_held=0,
                                 expert_offset=0)
    p = M.moe_init(jax.random.PRNGKey(0), capped, jnp.bfloat16)
    assert p["router"].dtype == jnp.float32
    assert p["we_up"].shape[0] == capped.n_experts
    with pytest.raises(ValueError, match="dropless"):
        M.moe_init(jax.random.PRNGKey(0),
                   dataclasses.replace(CFG, moe_strategy="gather"))
