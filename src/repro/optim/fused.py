"""Kernel-backed optimizers: the Pallas fused-update path.

Same functional interface as :func:`repro.optim.sgd` / :func:`adamw`, but
each leaf update is ONE fused kernel call (one HBM pass — Appendix B's
efficiency argument). Only valid for native-bf16 policies (the kernels
implement the bf16 grid); numerics match the reference optimizers up to
the documented 1-ulp FMA ties (tests/test_optim_fused.py).

Shard-local mode: pass ``mesh=``/``pspecs=`` and the update runs inside
``jax.shard_map`` — every kernel call operates directly on the *local*
FSDP/TP shard of (w, m, v, g, c), so the one-HBM-pass property holds
per device and no gathered or f32 working copy of the optimizer state is
ever materialized. SR bits are decorrelated across shards by folding the
per-leaf key with the shard's linearised index over exactly the mesh
axes named in that leaf's PartitionSpec — replicated leaves (and the
replicated copies of TP/FSDP leaves along unnamed axes) therefore draw
*identical* bits everywhere, preserving the replication invariant that
GSPMD relies on.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.policy import PrecisionPolicy
from repro.kernels.fused_adamw import fused_adamw
from repro.kernels.fused_sgd import fused_sgd
from repro.optim.adamw import AdamWState
from repro.optim.base import Optimizer, state_ops
from repro.optim.sgd import SGDState
from repro.profile import scopes

__all__ = ["fused_sgd_optimizer", "fused_adamw_optimizer"]

_is_spec = lambda x: isinstance(x, P)  # noqa: E731 — tree_map leaf predicate


def _check(policy: PrecisionPolicy):
    if policy.param_format.name != "bf16" or policy.update_rounding == "exact":
        raise ValueError(
            f"fused kernels implement the bf16 16-bit-FPU recipe; "
            f"policy {policy.name!r} is not supported")


@jax.named_scope(scopes.SR_BITS)
def _sr_bits(key, w):
    """32 random bits per element of ``w``, for stochastic rounding."""
    return jax.random.bits(key, shape=w.shape, dtype=jnp.uint32)


def _spec_axes(spec: P) -> tuple[str, ...]:
    """Mesh axis names a PartitionSpec shards over, in dim order."""
    out: list[str] = []
    for entry in spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            if ax is not None:
                out.append(ax)
    return tuple(out)


def _shard_key(key, spec: P, mesh):
    """Fold ``key`` with the linearised shard index over the axes in
    ``spec`` — distinct bits per shard, identical bits across replicas."""
    axes = _spec_axes(spec)
    if not axes:
        return key
    idx = jnp.int32(0)
    for ax in axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return jax.random.fold_in(key, idx)


def _shard_local_update(leaf_update, mesh, pspecs, treedef, n_state: int):
    """Wrap a per-leaf-list update in shard_map over the parameter specs.

    ``leaf_update(w_l, g_l, state_ls, keys, scalars)`` consumes flat leaf
    lists plus replicated scalars and returns ``(new_w_l, *new_state_ls)``;
    here every list element is the *local shard* of its leaf and ``keys``
    are already shard-folded. ``n_state`` is the number of param-shaped
    state lists (SGD: m[, c]; AdamW: m, v[, c]).
    """
    specs_l = treedef.flatten_up_to(pspecs)

    def run(w_l, g_l, state_ls, key, scalars):
        with jax.named_scope(scopes.SR_BITS):
            keys = list(jax.random.split(key, len(w_l)))

        def body(w_l, g_l, state_ls, keys, scalars):
            folded = [_shard_key(k, s, mesh) for k, s in zip(keys, specs_l)]
            return leaf_update(w_l, g_l, state_ls, folded, scalars)

        state_specs = [list(specs_l) for _ in range(n_state)]
        out_specs = tuple([list(specs_l)] * (1 + n_state))
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(list(specs_l), list(specs_l), state_specs,
                      [P()] * len(keys), [P()] * len(scalars)),
            out_specs=out_specs, check_vma=False,
        )(w_l, g_l, state_ls, keys, list(scalars))

    return run


def fused_sgd_optimizer(policy: PrecisionPolicy, *, momentum: float = 0.9,
                        weight_decay: float = 0.0, mesh=None,
                        pspecs=None) -> Optimizer:
    _check(policy)
    if (mesh is None) != (pspecs is None):
        raise ValueError("shard-local mode needs both mesh= and pspecs=")
    sops = state_ops(policy)
    stochastic = policy.update_rounding == "stochastic"

    def init(params):
        m = jax.tree_util.tree_map(sops.zeros_like, params)
        c = jax.tree_util.tree_map(sops.zeros_like, params) if policy.kahan else None
        return SGDState(m, c)

    def leaf_update(w_l, g_l, state_ls, keys, scalars):
        (lr,) = scalars
        m_l = state_ls[0]
        c_l = state_ls[1] if policy.kahan else [None] * len(w_l)
        new_w, new_m, new_c = [], [], []
        for w, g, m, c, k in zip(w_l, g_l, m_l, c_l, keys):
            bits = _sr_bits(k, w) if stochastic else None
            w2, m2, c2 = fused_sgd(
                w, m, g.astype(jnp.bfloat16), c=c, bits=bits,
                stochastic=stochastic, lr=lr, momentum=momentum,
                wd=weight_decay)
            new_w.append(w2)
            new_m.append(m2)
            new_c.append(c2)
        if policy.kahan:
            return new_w, new_m, new_c
        return new_w, new_m

    def update(grads, state, params, *, step, key, lr):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        g_l = treedef.flatten_up_to(grads)
        state_ls = [treedef.flatten_up_to(state.momentum)]
        if policy.kahan:
            state_ls.append(treedef.flatten_up_to(state.kahan_c))
        lr = jnp.asarray(lr, jnp.float32)
        if mesh is not None:
            run = _shard_local_update(leaf_update, mesh, pspecs, treedef,
                                      len(state_ls))
            out = run(leaves, g_l, state_ls, key, (lr,))
        else:
            with jax.named_scope(scopes.SR_BITS):
                keys = list(jax.random.split(key, len(leaves)))
            out = leaf_update(leaves, g_l, state_ls, keys, (lr,))
        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        new_w, new_m = out[0], out[1]
        new_c = out[2] if policy.kahan else None
        return unf(new_w), SGDState(unf(new_m),
                                    unf(new_c) if policy.kahan else None)

    return Optimizer(f"fused_sgd[{policy.name}]", policy, init, update)


def fused_adamw_optimizer(policy: PrecisionPolicy, *, b1: float = 0.9,
                          b2: float = 0.99609375, eps: float = 1e-8,
                          weight_decay: float = 0.01, mesh=None,
                          pspecs=None) -> Optimizer:
    _check(policy)
    if (mesh is None) != (pspecs is None):
        raise ValueError("shard-local mode needs both mesh= and pspecs=")
    sops = state_ops(policy)
    stochastic = policy.update_rounding == "stochastic"
    b1q = float(jax.device_get(sops.f32(sops.q(jnp.float32(b1)))))
    b2q = float(jax.device_get(sops.f32(sops.q(jnp.float32(b2)))))

    def init(params):
        m = jax.tree_util.tree_map(sops.zeros_like, params)
        v = jax.tree_util.tree_map(sops.zeros_like, params)
        one = jnp.ones((), sops.dtype)
        c = jax.tree_util.tree_map(sops.zeros_like, params) if policy.kahan else None
        return AdamWState(m, v, one, one, c)

    def leaf_update(w_l, g_l, state_ls, keys, scalars):
        lr, c1f, c2f = scalars
        m_l, v_l = state_ls[0], state_ls[1]
        c_l = state_ls[2] if policy.kahan else [None] * len(w_l)
        new_w, new_m, new_v, new_c = [], [], [], []
        for w, g, m, v, c, k in zip(w_l, g_l, m_l, v_l, c_l, keys):
            bits = _sr_bits(k, w) if stochastic else None
            w2, m2, v2, c2_ = fused_adamw(
                w, m, v, g.astype(jnp.bfloat16), c=c, bits=bits,
                stochastic=stochastic, lr=lr, b1=b1q, b2=b2q, eps=eps,
                wd=weight_decay, c1=c1f, c2=c2f)
            new_w.append(w2)
            new_m.append(m2)
            new_v.append(v2)
            new_c.append(c2_)
        if policy.kahan:
            return new_w, new_m, new_v, new_c
        return new_w, new_m, new_v

    def update(grads, state, params, *, step, key, lr):
        c1 = sops.q(sops.f32(state.c1) * b1q)
        c2 = sops.q(sops.f32(state.c2) * b2q)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        g_l = treedef.flatten_up_to(grads)
        state_ls = [treedef.flatten_up_to(state.m),
                    treedef.flatten_up_to(state.v)]
        if policy.kahan:
            state_ls.append(treedef.flatten_up_to(state.kahan_c))
        scalars = (jnp.asarray(lr, jnp.float32), sops.f32(c1), sops.f32(c2))
        if mesh is not None:
            run = _shard_local_update(leaf_update, mesh, pspecs, treedef,
                                      len(state_ls))
            out = run(leaves, g_l, state_ls, key, scalars)
        else:
            with jax.named_scope(scopes.SR_BITS):
                keys = list(jax.random.split(key, len(leaves)))
            out = leaf_update(leaves, g_l, state_ls, keys, scalars)
        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        new_w, new_m, new_v = out[0], out[1], out[2]
        new_c = out[3] if policy.kahan else None
        return unf(new_w), AdamWState(unf(new_m), unf(new_v), c1, c2,
                                      unf(new_c) if policy.kahan else None)

    return Optimizer(f"fused_adamw[{policy.name}]", policy, init, update)
