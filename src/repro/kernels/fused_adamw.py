"""Pallas TPU kernel: fused AdamW update with SR / Kahan weight rounding.

The paper's Appendix-B efficiency claim made concrete: the optimizer step
is memory-bound (~zero arithmetic intensity), so the win is ONE pass over
HBM — read w, m, v, g (+ Kahan c), do the full Algorithm-4/5 arithmetic in
f32 registers, write bf16 states back with the selected rounding. An
unfused implementation re-reads/re-writes each tensor per op (the ~10
HLO ops of Alg. 4); the fusion removes that traffic (see
benchmarks/bench_kernels.py).

Variants (compile-time flags): update_rounding ∈ {nearest, stochastic},
kahan ∈ {off, on}. All tensors bf16 except the uint32 SR bits and the
f32 scalars (lr, betas, eps, wd, bias corrections), which ride in SMEM
as one (1, 7) array.

Layout (:func:`tiling`, shared with ``fused_sgd``): the kernel is
elementwise, so a leaf of rank ≥ 2 whose last dim is a multiple of 128
(every matrix of the dense models: embedding, head, the stacked
projections, the stacked norm scales) is tiled where it lies, with no
copy. Only the others (rank-1 leaves, or a last dim such as 5 or 16)
are raveled and padded into ``(rows, 128)`` tiles and sliced back,
under the scope ``layout``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.profile import scopes

__all__ = ["fused_adamw", "fused_adamw_kernel", "tiling", "update_call"]

LANE = 128
BLOCK_ROWS = 256        # flat path: (256, 128) tiles
BLOCK = (256, 1024)     # native path: the largest block over the last two dims


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


def tiling(shape, block_rows=BLOCK_ROWS):
    """How an elementwise update kernel tiles a leaf of ``shape``.

    Returns ``(native, kernel_shape, block_shape, grid)``. A leaf of rank
    2 or more whose last dim is a multiple of 128 is ``native``: the
    kernel runs on it where it lies, in blocks of up to ``BLOCK`` over
    its last two dims (leading dims squeezed, one per grid step; blocks
    at the far edges ragged). Any other leaf is raveled and padded into
    ``(rows, 128)`` tiles of ``block_rows`` rows.
    """
    if len(shape) >= 2 and shape[-1] % LANE == 0:
        rows, cols = shape[-2:]
        br, bc = min(BLOCK[0], rows), min(BLOCK[1], cols)
        lead = tuple(shape[:-2])
        return (True, tuple(shape), (pl.squeezed,) * len(lead) + (br, bc),
                lead + (-(-rows // br), -(-cols // bc)))
    rows = max(1, -(-math.prod(shape) // LANE))
    grid_rows = -(-rows // block_rows) * block_rows
    return (False, (grid_rows, LANE), (block_rows, LANE),
            (grid_rows // block_rows,))


@jax.named_scope(scopes.LAYOUT)
def _pad2(x, shape2):
    flat = jnp.ravel(x)
    total = math.prod(shape2)
    if total != flat.size:
        flat = jnp.pad(flat, (0, total - flat.size))
    return flat.reshape(shape2)


def update_call(kernel, operands, scalars, aliases, *, name, interpret,
                block_rows=BLOCK_ROWS):
    """Run the elementwise update ``kernel`` over ``operands``, arrays of
    one leaf's shape in the kernel's dtypes, with the f32 ``scalars`` as
    one ``(1, k)`` array in SMEM. Output ``j`` is bf16 and overwrites
    operand ``aliases[j]`` in place, so a donated state is updated where
    it lies (without the alias XLA copies the state to free its buffer
    for the step's output). One ``pallas_call`` named ``name`` per leaf;
    only a leaf that :func:`tiling` does not take natively is copied into
    tiles and back (scope ``layout``)."""
    shape = operands[0].shape
    native, kshape, bshape, grid = tiling(shape, block_rows)
    if not native:
        operands = [_pad2(x, kshape) for x in operands]
    bs = pl.BlockSpec(bshape, (lambda *i: i) if native
                      else (lambda i: (i, 0)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_sds = jax.ShapeDtypeStruct(kshape, jnp.bfloat16)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[bs] * len(operands) + [smem],
        out_specs=[bs] * len(aliases),
        out_shape=[out_sds] * len(aliases),
        input_output_aliases={i: j for j, i in enumerate(aliases)},
        interpret=interpret,
        name=name,
    )(*operands, scalars)
    if native:
        return outs
    n = math.prod(shape)
    with jax.named_scope(scopes.LAYOUT):
        return [a.reshape(-1)[:n].reshape(shape) for a in outs]


def fused_adamw(w, m, v, g, *, c=None, bits=None, lr, b1, b2, eps, wd,
                c1, c2, stochastic: bool = True,
                interpret: bool | None = None, block_rows: int = BLOCK_ROWS):
    """One fused AdamW step on one leaf. Returns (w', m', v', c').

    c (Kahan) and bits (SR) are optional; pass both for SR+Kahan (Fig 11).
    The leaf is tiled as :func:`tiling` says.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kahan = c is not None
    bf16 = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    operands = [bf16(w), bf16(m), bf16(v), bf16(g),
                bf16(c) if kahan else jnp.zeros_like(w, jnp.bfloat16),
                (bits if bits is not None
                 else jnp.zeros(w.shape, jnp.uint32)).astype(jnp.uint32)]
    scalars = jnp.array([[lr, b1, b2, eps, wd, 1.0 - c1, 1.0 - c2]], jnp.float32)
    w2, m2, v2, c2_ = update_call(
        partial(fused_adamw_kernel, stochastic=stochastic, kahan=kahan),
        operands, scalars, (0, 1, 2, 4), name=scopes.FUSED_ADAMW,
        interpret=interpret, block_rows=block_rows)
    return w2, m2, v2, (c2_ if kahan else None)
