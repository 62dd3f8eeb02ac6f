"""Pallas TPU kernel: fused AdamW update with SR / Kahan weight rounding.

The paper's Appendix-B efficiency claim made concrete: the optimizer step
is memory-bound (~zero arithmetic intensity), so the win is ONE pass over
HBM — read w, m, v, g (+ Kahan c), do the full Algorithm-4/5 arithmetic in
f32 registers, write bf16 states back with the selected rounding. An
unfused implementation re-reads/re-writes each tensor per op (the ~10
HLO ops of Alg. 4); the fusion removes that traffic (see
benchmarks/bench_kernels.py).

Variants (compile-time flags): update_rounding ∈ {nearest, stochastic},
kahan ∈ {off, on}. All tensors bf16 except the f32 scalars (lr, betas,
eps, wd, bias corrections), which ride in SMEM as one (1, 7) array.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.profile import scopes

__all__ = ["fused_adamw", "fused_adamw_kernel"]

LANE = 128
BLOCK_ROWS = 256


def _sr_to_bf16(val_f32, bits):
    raw = jax.lax.bitcast_convert_type(val_f32, jnp.uint32)
    rounded = (raw + (bits & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    y = jax.lax.bitcast_convert_type(rounded, jnp.float32)
    return jnp.where(jnp.isfinite(val_f32), y, val_f32).astype(jnp.bfloat16)


def fused_adamw_kernel(w_ref, m_ref, v_ref, g_ref, c_ref, bits_ref,
                       scalars_ref, w_out, m_out, v_out, c_out, *,
                       stochastic: bool, kahan: bool):
    # scalars: [lr, b1, b2, eps, wd, one_m_c1, one_m_c2]
    lr = scalars_ref[0, 0]
    b1 = scalars_ref[0, 1]
    b2 = scalars_ref[0, 2]
    eps = scalars_ref[0, 3]
    wd = scalars_ref[0, 4]
    om_c1 = scalars_ref[0, 5]
    om_c2 = scalars_ref[0, 6]

    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    # moment updates — one FMAC each, rounded once to bf16 (paper Alg. 4)
    m = (b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g).astype(jnp.bfloat16)
    v = (b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * g * g).astype(jnp.bfloat16)
    m_hat = (m.astype(jnp.float32) / om_c1).astype(jnp.bfloat16).astype(jnp.float32)
    v_hat = jnp.sqrt(v.astype(jnp.float32) / om_c2).astype(jnp.bfloat16).astype(jnp.float32)
    u = (lr * m_hat / (v_hat + eps) + lr * wd * w).astype(jnp.bfloat16)

    m_out[...] = m
    v_out[...] = v
    if not kahan:
        step_val = w - u.astype(jnp.float32)
        if stochastic:
            w_out[...] = _sr_to_bf16(step_val, bits_ref[...])
        else:
            w_out[...] = step_val.astype(jnp.bfloat16)
        c_out[...] = c_ref[...]
        return
    # Kahan (Alg. 5): nearest rounding on every op, c tracks the residual
    c = c_ref[...].astype(jnp.float32)
    u_neg = (-u.astype(jnp.float32)).astype(jnp.bfloat16)
    y = (u_neg.astype(jnp.float32) - c).astype(jnp.bfloat16)
    s_val = w + y.astype(jnp.float32)
    if stochastic:
        s = _sr_to_bf16(s_val, bits_ref[...])
    else:
        s = s_val.astype(jnp.bfloat16)
    diff = (s.astype(jnp.float32) - w).astype(jnp.bfloat16)
    c_new = (diff.astype(jnp.float32) - y.astype(jnp.float32)).astype(jnp.bfloat16)
    w_out[...] = s
    c_out[...] = c_new


@jax.named_scope(scopes.LAYOUT)
def _pad2(x, rows, cols, dtype):
    flat = jnp.ravel(x).astype(dtype)
    total = rows * cols
    if total != flat.size:
        flat = jnp.pad(flat, (0, total - flat.size))
    return flat.reshape(rows, cols)


def fused_adamw(w, m, v, g, *, c=None, bits=None, lr, b1, b2, eps, wd,
                c1, c2, stochastic: bool = True,
                interpret: bool | None = None, block_rows: int = BLOCK_ROWS):
    """One fused AdamW step on a flattened tensor. Returns (w', m', v', c').

    c (Kahan) and bits (SR) are optional; pass both for SR+Kahan (Fig 11).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kahan = c is not None
    n = w.size
    rows = max(1, -(-n // LANE))
    grid_rows = -(-rows // block_rows) * block_rows
    shape2 = (grid_rows, LANE)
    wp = _pad2(w, *shape2, jnp.bfloat16)
    mp = _pad2(m, *shape2, jnp.bfloat16)
    vp = _pad2(v, *shape2, jnp.bfloat16)
    gp = _pad2(g, *shape2, jnp.bfloat16)
    cp = _pad2(c if kahan else jnp.zeros_like(w), *shape2, jnp.bfloat16)
    bp = _pad2(bits if bits is not None else jnp.zeros(w.shape, jnp.uint32),
               *shape2, jnp.uint32)
    scalars = jnp.array([[lr, b1, b2, eps, wd, 1.0 - c1, 1.0 - c2]], jnp.float32)
    grid = (grid_rows // block_rows,)
    bs = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_sds = jax.ShapeDtypeStruct(shape2, jnp.bfloat16)
    w2, m2, v2, c2_ = pl.pallas_call(
        partial(fused_adamw_kernel, stochastic=stochastic, kahan=kahan),
        grid=grid,
        in_specs=[bs, bs, bs, bs, bs, bs,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bs, bs, bs, bs],
        out_shape=[out_sds, out_sds, out_sds, out_sds],
        interpret=interpret,
        name=scopes.FUSED_ADAMW,
    )(wp, mp, vp, gp, cp, bp, scalars)

    @jax.named_scope(scopes.LAYOUT)
    def unpad(a):
        return a.reshape(-1)[:n].reshape(w.shape)
    return unpad(w2), unpad(m2), unpad(v2), (unpad(c2_) if kahan else None)
