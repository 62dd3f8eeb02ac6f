"""Mixture-of-Experts feed-forward: capacity-based dispatch, or dropless
over the share of the experts a layer holds.

Capacity strategies (selectable; the §Perf hillclimb compares them):

* ``onehot`` — GShard/Switch-style dispatch/combine einsums against a
  (tokens, experts, capacity) one-hot tensor. Simple, SPMD-friendly
  (all-to-all appears when the expert axis is sharded), but pays
  O(T·E·C·D) dispatch FLOPs — the classic baseline.
* ``gather``  — index-based dispatch (take/scatter-add). Removes the
  dispatch-matmul FLOPs; the beyond-paper optimized path.

Routing is computed in f32 (router logits are numerically delicate — this
matches production MoE stacks and the paper's fused-op convention).
Over-capacity tokens are dropped (their expert contribution is zero), the
standard trade-off at scale. These paths hold every expert.

* ``dropless`` — the layer holds experts ``[expert_offset, expert_offset
  + experts_held)`` of the router's ``n_experts`` (one chip's share under
  expert parallelism). The router keeps its full width; every (token,
  expert) assignment to a held expert is computed, none is dropped, by
  grouped matmuls whose work scales with the rows routed here
  (:meth:`QArith.grouped_matmul`). Assignments to experts held elsewhere
  add nothing here. The router weight is a leaf in the param dtype, like
  every other; its logits stay in the f32 accumulator into the softmax.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.qarith import QArith
from repro.profile import scopes

__all__ = ["moe_init", "moe_apply", "dropless_apply", "mlp_init",
           "mlp_apply"]


def mlp_init(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_ff = 1 / math.sqrt(d_model), 1 / math.sqrt(d_ff)
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * s_ff).astype(dtype),
    }


def mlp_apply(qa: QArith, p, x, act: str = "silu"):
    g = qa.einsum("...d,df->...f", x, p["w_gate"])
    u = qa.einsum("...d,df->...f", x, p["w_up"])
    a = qa.silu(g) if act == "silu" else qa.gelu(g)
    h = qa.mul(a, u)
    return qa.einsum("...f,fd->...d", h, p["w_down"])


def moe_init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    dropless = cfg.moe_strategy == "dropless"
    if cfg.n_held != E and not dropless:
        raise ValueError("only moe_strategy='dropless' holds a share of "
                         "the experts")
    H = cfg.n_held
    s_in, s_ff = 1 / math.sqrt(D), 1 / math.sqrt(F)
    router_dtype = dtype if dropless else jnp.float32
    p = {
        "router": (jax.random.normal(ks[0], (D, E)) * s_in).astype(router_dtype),
        "we_gate": (jax.random.normal(ks[1], (H, D, F)) * s_in).astype(dtype),
        "we_up": (jax.random.normal(ks[2], (H, D, F)) * s_in).astype(dtype),
        "we_down": (jax.random.normal(ks[3], (H, F, D)) * s_ff).astype(dtype),
    }
    if cfg.shared_expert:
        p["shared"] = mlp_init(ks[4], D, F, dtype)
    return p


def _route(x, router, top_k: int, capacity: int):
    """Top-k routing with capacity. Returns (dispatch, combine) one-hots.

    x: (T, D) → dispatch: (T, E, C) bool-ish, combine: (T, E, C) f32 weights.
    """
    T, _ = x.shape
    E = router.shape[-1]
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)      # (T,k)
    # queue position of each (token, k) claim within its expert, token-major
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (T,k,E)
    claims = onehot.reshape(T * top_k, E)
    pos = jnp.cumsum(claims, axis=0) - claims              # (T·k, E)
    pos_tk = (pos.reshape(T, top_k, E) * onehot).sum(-1)   # (T,k) queue slot
    keep = (pos_tk < capacity).astype(jnp.float32)
    slot_oh = jax.nn.one_hot(pos_tk, capacity, dtype=jnp.float32)   # (T,k,C)
    exp_oh = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)         # (T,k,E)
    disp = jnp.einsum("tke,tkc->tkec", exp_oh, slot_oh * keep[..., None])
    dispatch = disp.sum(axis=1)                            # (T,E,C)
    combine = jnp.einsum("tkec,tk->tec", disp, gate_vals)  # (T,E,C)
    return dispatch, combine


def _experts_ffn(qa, p, xe, act):
    """(…,C,D) expert inputs → (…,C,D) expert outputs (bf16 FMAC einsums).
    Leading dims: (E,) or (G,E)."""
    spec_in = "...ecd,edf->...ecf"
    g = qa.einsum(spec_in, xe, p["we_gate"])
    u = qa.einsum(spec_in, xe, p["we_up"])
    a = qa.silu(g) if act == "silu" else qa.gelu(g)
    h = qa.mul(a, u)
    return qa.einsum("...ecf,efd->...ecd", h, p["we_down"])


def _moe_onehot_global(qa, p, x, cfg, capacity):
    """GShard-style one-hot dispatch over ALL tokens at once — the naive
    baseline. Dispatch einsum cost is O(T²·k·cf/E·D): quadratic in tokens.
    Kept as the recorded §Perf baseline; do not use at scale."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    dispatch, combine = _route(xt, p["router"], cfg.top_k, capacity)
    xe = qa.einsum("tec,td->ecd", dispatch, xt)
    ye = _experts_ffn(qa, p, xe, cfg.act_fn)
    y = qa.einsum("tec,ecd->td", combine, ye)
    return y.reshape(B, S, D)


def _moe_onehot_grouped(qa, p, x, cfg):
    """One-hot dispatch per token GROUP, the production GShard/MaxText
    form: dispatch cost O(T·G·k·cf/E·D), linear in tokens — G is
    ``cfg.moe_group_size`` (dispatch overhead ≈ 2·G·cf/(3·d_ff); shrink G
    to taste, but too-small groups raise capacity-drop variance). Under EP
    sharding the (…,E,C) dispatch einsums lower to expert all-to-alls."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    G = min(cfg.moe_group_size or S, S)
    if S % G:
        G = S
    n_groups = (B * S) // G
    xg = x.reshape(n_groups, G, D)
    cap = max(1, int(cfg.capacity_factor * G * k / E))
    disp, comb = jax.vmap(lambda xt: _route(xt, p["router"], k, cap))(xg)
    xe = qa.einsum("gtec,gtd->gecd", disp, xg)
    ye = _experts_ffn(qa, p, xe, cfg.act_fn)
    y = qa.einsum("gtec,gecd->gtd", comb, ye)
    return y.reshape(B, S, D)


def _moe_gather(qa, p, x, cfg, capacity):
    """Index-based dispatch (beyond-paper optimized path): scatter token
    ids into an (E,C) slot table, gather expert inputs, scatter-combine
    back. Removes the dispatch matmuls entirely — O(T·k·D) memory traffic,
    zero dispatch FLOPs. Best when experts are NOT expert-sharded (TP-in-
    expert MoE, e.g. mixtral under a 16-way model axis)."""
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    C = capacity
    xt = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)          # (T,k)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)
    claims = onehot.reshape(T * k, E)
    pos = (jnp.cumsum(claims, axis=0) - claims).reshape(T, k, E)
    pos_tk = (pos * onehot).sum(-1)                        # (T,k)
    keep = pos_tk < C
    slot = jnp.where(keep, pos_tk, C)                      # C = drop bucket
    token_ids = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k))
    idx = jnp.zeros((E, C), jnp.int32).at[
        gate_idx.reshape(-1), slot.reshape(-1)].set(
        token_ids.reshape(-1), mode="drop")
    filled = jnp.zeros((E, C), bool).at[
        gate_idx.reshape(-1), slot.reshape(-1)].set(True, mode="drop")
    xe = jnp.take(xt, idx.reshape(-1), axis=0).reshape(E, C, D)
    xe = xe * filled[..., None].astype(xe.dtype)
    ye = _experts_ffn(qa, p, xe, cfg.act_fn)
    # combine: gather each (t,k) claim's expert output back
    slot_c = jnp.minimum(slot, C - 1)
    flat = ye.reshape(E * C, D)
    back = jnp.take(flat, (gate_idx * C + slot_c).reshape(-1), axis=0)
    back = back.reshape(T, k, D).astype(jnp.float32)
    w = (gate_vals * keep.astype(jnp.float32))[..., None]
    y = qa.cast((back * w).sum(axis=1))
    return y.reshape(B, S, D)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, idx, inv, fold: int):
    """``x[idx]``, where ``idx`` takes each row of ``x`` ``fold`` times
    and ``inv[i·fold : (i+1)·fold]`` says where row i's copies went. The
    gradient is gathered back through ``inv`` and summed over the copies
    in f32, rounded once: no scatter."""
    return jnp.take(x, idx, axis=0)


def _take_rows_fwd(x, idx, inv, fold):
    return _take_rows(x, idx, inv, fold), inv


def _take_rows_bwd(fold, inv, g):
    d = jnp.take(g, inv, axis=0).astype(jnp.float32)
    d = d.reshape(-1, fold, g.shape[-1]).sum(axis=1).astype(g.dtype)
    return d, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def dropless_apply(qa: QArith, p, x, cfg):
    """x: (B,S,D) → ((B,S,D), stats). The layer holds experts
    ``cfg.expert_offset ..`` (``cfg.n_held`` of them) of the router's
    ``cfg.n_experts``. Softmax over all of them, top-k, renormalised over
    the k chosen when ``cfg.norm_topk_prob``; the T·k assignments sorted
    by expert, the held experts' rows through SwiGLU by grouped matmuls,
    unsorted and summed per token with their gates (in f32, rounded
    once). No token is dropped: the sorted buffer holds all T·k rows.

    ``stats`` (f32 scalars): ``router_loss``, the Switch load-balancing
    loss over all the router's experts, E·Σ_e (assignments to e / T)·
    (mean probability of e) (k at balance); ``held_share``, the share of
    the assignments held here; ``max_load``, the busiest held expert's
    assignments over the held experts' mean."""
    B, S, D = x.shape
    T = B * S
    E, k, H = cfg.n_experts, cfg.top_k, cfg.n_held
    first = cfg.expert_offset
    xt = x.reshape(T, D)
    with jax.named_scope(scopes.ROUTER):
        probs = jax.nn.softmax(qa.matmul_f32out(xt, p["router"]), axis=-1)
        gate, expert = jax.lax.top_k(probs, k)             # (T,k)
        if cfg.norm_topk_prob:
            gate = gate / gate.sum(axis=-1, keepdims=True)
        sizes = jnp.bincount(expert.reshape(-1), length=E).astype(jnp.int32)
        router_loss = E * jnp.sum(sizes.astype(jnp.float32) / T
                                  * probs.mean(axis=0))
        held = jax.lax.dynamic_slice_in_dim(sizes, first, H)
    with jax.named_scope(scopes.DISPATCH):
        # assignment j = t·k + i sits at row slot[j] of the sorted buffer,
        # whose row s holds assignment order[s]: by expert, then token
        order = jnp.argsort(expert.reshape(-1), stable=True)
        slot = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
        xs = _take_rows(xt, order // k, slot, k)           # (T·k, D)
    with jax.named_scope(scopes.EXPERTS):
        g = qa.grouped_matmul(xs, p["we_gate"], sizes, first)
        u = qa.grouped_matmul(xs, p["we_up"], sizes, first)
        h = qa.mul(qa.silu(g) if cfg.act_fn == "silu" else qa.gelu(g), u)
        # rows of experts held elsewhere come back 0 from the kernel
        ys = qa.grouped_matmul(h, p["we_down"], sizes, first)
    with jax.named_scope(scopes.COMBINE):
        yt = _take_rows(ys, slot, order, 1).reshape(T, k, D)
        y = qa.cast((yt.astype(jnp.float32) * gate[..., None]).sum(axis=1))
    y = y.reshape(B, S, D)
    if cfg.shared_expert:
        y = qa.add(y, mlp_apply(qa, p["shared"], x, cfg.act_fn))
    n_held = held.sum().astype(jnp.float32)
    stats = {"router_loss": router_loss, "held_share": n_held / (T * k),
             "max_load": held.max() * H / jnp.maximum(n_held, 1.0)}
    return y, stats


def moe_apply(qa: QArith, p, x, cfg, *, strategy: str | None = None):
    """x: (B,S,D) → (B,S,D). Strategy (see module docstring):
    ``onehot`` (global baseline) | ``grouped`` (production GShard) |
    ``gather`` (index-based, no dispatch FLOPs). A ``dropless`` layer
    runs :func:`dropless_apply`."""
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    strategy = strategy or cfg.moe_strategy
    if S == 1:
        # decode: per-step token count is tiny — no-drop capacity so that
        # decode is deterministic and prefill≡decode in the drop-free regime
        out = _moe_onehot_global(qa, p, x, cfg, capacity=T * k)
    elif strategy == "grouped" and B > 1:
        out = _moe_onehot_grouped(qa, p, x, cfg)
    elif strategy == "gather":
        cap = max(1, int(cfg.capacity_factor * T * k / E))
        out = _moe_gather(qa, p, x, cfg, cap)
    else:
        cap = max(1, int(cfg.capacity_factor * T * k / E))
        out = _moe_onehot_global(qa, p, x, cfg, cap)
    if cfg.shared_expert:
        out = qa.add(out, mlp_apply(qa, p["shared"], x, cfg.act_fn))
    return out
