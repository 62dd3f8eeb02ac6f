"""Grouped matrix multiplication over the experts a layer holds.

The rows of ``x`` come sorted by group (expert), ``group_sizes[g]`` rows
for each of the router's groups; ``w`` holds the matrices of groups
``group_offset .. group_offset + len(w) - 1`` only, the layer's share.
Each held group's rows are multiplied by that group's matrix; the rows
of the groups not held come back as zeros. The work scales with the
rows of the held groups, not with the rows of ``x``.

This is JAX's Pallas ``megablox`` kernel (``jax.experimental.pallas.ops.
tpu.megablox``, with its custom VJP: the backward is one more grouped
matmul for ``x`` and a transposed one, ``tgmm``, for ``w``), in
interpret mode off the TPU. It accumulates in float32 and rounds its
output once to ``out_dtype``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

__all__ = ["gmm", "TILING"]

# (rows, contraction, output) tile; the rows' tile shrinks to one that
# divides the rows, and the others are clipped to the matrices' dims
TILING = (512, 1024, 1024)


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    tm = next(t for t in (TILING[0], 256, 128, 64, 32, 16, 8, 1)
              if m % t == 0)
    return tm, min(TILING[1], k), min(TILING[2], n)


def gmm(x, w, group_sizes, group_offset, *, out_dtype=jnp.float32,
        interpret: bool | None = None):
    """``x`` (m, k) rows sorted by group, ``w`` (held, k, n),
    ``group_sizes`` (groups,) int32, ``group_offset`` the first held
    group. Returns (m, n) in ``out_dtype``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    return megablox.gmm(x, w, group_sizes.astype(jnp.int32),
                        jnp.dtype(out_dtype), _tiling(m, k, w.shape[-1]),
                        jnp.asarray(group_offset, jnp.int32), None, False,
                        interpret)
