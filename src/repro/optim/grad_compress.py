"""SR-compressed gradient collectives with Kahan error feedback.

Beyond-paper distributed-optimization trick that *reuses the paper's two
primitives at the collective layer*: gradients are stochastically rounded to
a low wire format before the cross-replica all-reduce (halving or better the
DP gradient traffic vs fp32 reduce), and the per-shard quantization residual
is carried to the next step by a Kahan-style error-feedback buffer (so the
compression error is compensated rather than accumulated — the same
mechanism as Algorithm 3, applied to communication instead of weight
storage).

The wire format is any :class:`repro.core.formats.FloatFormat`:

* ``bf16`` (the default) uses the native-bfloat16 fast path — bit-identical
  to the original hard-coded wire.
* sub-bf16 e8 formats (bf14/bf12/bf10) ride a bfloat16 *carrier* (their
  grids are exact bf16 subsets); fp16/e5m2/e4m3 ride float16. The carrier
  is a CPU/simulation artifact — accounted wire bytes are ``fmt.bits``-based
  (see bench_grad_wire).
* the narrow formats carry no ±inf, so payloads are saturated at
  ``max_finite`` before rounding (``clamp_finite``) — an overflowing
  gradient clamps instead of poisoning the all-reduce with inf.
* ``fp32`` per-leaf passthrough exists for the per-leaf keep policy
  (small/sensitive leaves ride fp32 while bulk leaves take the low format).

On an FSDP/DP mesh this composes with pjit: the function is applied
per-gradient-leaf *before* ``psum`` inside ``shard_map``-based data
parallelism, or standalone for manual DP loops. SR keeps the reduce unbiased
(E[q(g)] = g), which is the property the paper proves makes SGD tolerate the
rounding.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core.formats import (BF16, FloatFormat, clamp_finite,
                                round_stochastic, stochastic_round_bf16,
                                wire_carrier_dtype)

__all__ = ["compress_leaf", "compressed_psum", "init_residual"]

PyTree = Any


def init_residual(grads: PyTree) -> PyTree:
    """Zero error-feedback buffers (f32, one per gradient leaf)."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compress_leaf(g: jax.Array, residual: jax.Array, key: jax.Array,
                  fmt: FloatFormat = BF16) -> tuple[jax.Array, jax.Array]:
    """Quantize ``g + residual`` onto ``fmt`` with SR; return (q, new_residual).

    ``q`` comes back in the format's carrier dtype (bf16 for e8 formats,
    f16 for fp16/e5m2/e4m3, f32 passthrough for fp32). The fp32 branch
    returns a zero residual: nothing was dropped, so error feedback would
    only re-inject stale state.
    """
    corrected = g.astype(jnp.float32) + residual
    if fmt.name == "fp32":
        return corrected, jnp.zeros_like(corrected)
    if fmt.name == "bf16":
        # native fast path — bit-identical to the original SR-bf16 wire
        # (same key, same noise draw)
        q = stochastic_round_bf16(corrected, key)
    else:
        q = round_stochastic(clamp_finite(corrected, fmt), key, fmt) \
            .astype(wire_carrier_dtype(fmt))
    new_residual = corrected - q.astype(jnp.float32)
    return q, new_residual


def compressed_psum(grads: PyTree, residuals: PyTree, key: jax.Array,
                    axis_name: str,
                    fmts: Sequence[FloatFormat] | None = None
                    ) -> tuple[PyTree, PyTree]:
    """Low-format SR all-reduce with error feedback. Call inside shard_map/pmap.

    ``fmts`` gives the wire format per flattened gradient leaf (the per-leaf
    keep policy resolves them *outside* shard_map, from global shapes);
    ``None`` means bf16 everywhere, matching the original wire bit-for-bit.

    Returns (mean-reduced f32 gradients, updated residuals).
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    res_leaves = treedef.flatten_up_to(residuals)
    if fmts is None:
        fmts = [BF16] * len(leaves)
    keys = jax.random.split(jax.random.fold_in(key, jax.lax.axis_index(axis_name)),
                            len(leaves))
    # replica count once for the whole tree, not once per leaf (a scalar
    # psum per gradient leaf was a redundant collective ×|leaves|); psum
    # of a Python literal is resolved at trace time — no collective at all
    n = jax.lax.psum(1.0, axis_name)
    out, new_res = [], []
    for g, r, k, fmt in zip(leaves, res_leaves, keys, fmts):
        q, nr = compress_leaf(g, r, k, fmt)
        # the psum operand dtype is the carrier; the accounted wire width
        # is fmt.bits (sub-carrier formats are simulated on CPU)
        summed = jax.lax.psum(q.astype(wire_carrier_dtype(fmt)), axis_name)
        out.append(summed.astype(jnp.float32) / n)
        new_res.append(nr)
    return (jax.tree_util.tree_unflatten(treedef, out),
            jax.tree_util.tree_unflatten(treedef, new_res))
