"""Pallas TPU kernel: fused SGD-momentum update with SR / Kahan rounding.

Same single-HBM-pass rationale as fused_adamw (paper Algorithms 2–3), and
the same layout: ``fused_adamw.tiling`` runs the kernel where a leaf lies
when its rank is ≥ 2 and its last dim a multiple of 128, and copies any
other leaf into ``(rows, 128)`` tiles and back (scope ``layout``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_adamw import BLOCK_ROWS, _sr_to_bf16, update_call
from repro.profile import scopes

__all__ = ["fused_sgd", "fused_sgd_kernel"]


def fused_sgd_kernel(w_ref, m_ref, g_ref, c_ref, bits_ref, scalars_ref,
                     w_out, m_out, c_out, *, stochastic: bool, kahan: bool):
    # scalars: [lr, momentum, weight_decay]
    lr = scalars_ref[0, 0]
    mu = scalars_ref[0, 1]
    wd = scalars_ref[0, 2]
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    g = (g + wd * w).astype(jnp.bfloat16).astype(jnp.float32)   # g ← g + d·w
    m = (mu * m_ref[...].astype(jnp.float32) + g).astype(jnp.bfloat16)
    u = (lr * m.astype(jnp.float32)).astype(jnp.bfloat16)       # η·m
    m_out[...] = m
    if not kahan:
        step_val = w - u.astype(jnp.float32)
        w_out[...] = _sr_to_bf16(step_val, bits_ref[...]) if stochastic \
            else step_val.astype(jnp.bfloat16)
        c_out[...] = c_ref[...]
        return
    c = c_ref[...].astype(jnp.float32)
    u_neg = (-u.astype(jnp.float32)).astype(jnp.bfloat16)
    y = (u_neg.astype(jnp.float32) - c).astype(jnp.bfloat16)
    s_val = w + y.astype(jnp.float32)
    s = _sr_to_bf16(s_val, bits_ref[...]) if stochastic \
        else s_val.astype(jnp.bfloat16)
    diff = (s.astype(jnp.float32) - w).astype(jnp.bfloat16)
    c_out[...] = (diff.astype(jnp.float32) - y.astype(jnp.float32)).astype(jnp.bfloat16)
    w_out[...] = s


def fused_sgd(w, m, g, *, c=None, bits=None, lr, momentum=0.9, wd=0.0,
              stochastic: bool = True, interpret: bool | None = None,
              block_rows: int = BLOCK_ROWS):
    """One fused SGD step on one leaf. Returns (w', m', c')."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kahan = c is not None
    bf16 = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    operands = [bf16(w), bf16(m), bf16(g),
                bf16(c) if kahan else jnp.zeros_like(w, jnp.bfloat16),
                (bits if bits is not None
                 else jnp.zeros(w.shape, jnp.uint32)).astype(jnp.uint32)]
    scalars = jnp.array([[lr, momentum, wd]], jnp.float32)
    w2, m2, c2 = update_call(
        partial(fused_sgd_kernel, stochastic=stochastic, kahan=kahan),
        operands, scalars, (0, 1, 3), name=scopes.FUSED_SGD,
        interpret=interpret, block_rows=block_rows)
    return w2, m2, (c2 if kahan else None)
