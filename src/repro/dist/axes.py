"""Logical-axis activation sharding helpers.

Model code calls :func:`shard_batch` / :func:`shard_heads` at the points
where GSPMD's propagation needs a hint (embeddings, residual-stream
re-entry, flash-attention scan carries). The helpers read a thread-local
:class:`ActivationSharding` installed by the :func:`activation_sharding`
context manager — the train launcher and the dry-run compiler enter it
together with the mesh:

    with jax.set_mesh(mesh), activation_sharding(("data",), 4, "model", 2):
        jax.jit(step_fn)(state, batch, 0)

Outside the context (or outside any active mesh) every helper returns its
input unchanged, so the same model code traces identically for the
single-device smoke tests. Constraints pin only the named dimension(s)
and leave the rest ``UNCONSTRAINED`` so the compiler keeps whatever
layout propagation already chose.

Head-count padding: when the model axis does not divide the head count,
:func:`padded_head_count` rounds it up to the next multiple so attention
still shards (callers zero-pad heads and slice the outputs back — exact
semantics, see ``repro.models.layers.flash_attention``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["ActivationSharding", "activation_sharding", "current_sharding",
           "in_mesh", "shard_batch", "shard_heads", "padded_head_count"]


@dataclasses.dataclass(frozen=True)
class ActivationSharding:
    dp_axes: tuple[str, ...]   # mesh axes carrying data parallelism
    dp_size: int               # product of their sizes
    model_axis: str            # mesh axis carrying tensor/expert parallelism
    mp_size: int               # its size


_local = threading.local()


def current_sharding() -> Optional[ActivationSharding]:
    """The innermost active :func:`activation_sharding`, or ``None``."""
    return getattr(_local, "ctx", None)


@contextlib.contextmanager
def activation_sharding(dp_axes, dp_size: int, model_axis: str, mp_size: int):
    """Scope the activation-sharding hints to the enclosed trace/compile."""
    prev = current_sharding()
    _local.ctx = ActivationSharding(tuple(dp_axes), int(dp_size),
                                    str(model_axis), int(mp_size))
    try:
        yield _local.ctx
    finally:
        _local.ctx = prev


def in_mesh() -> bool:
    """True inside ``jax.set_mesh(mesh)``."""
    return not jax.sharding.get_abstract_mesh().empty


def _constrain(x, pinned: dict[int, object]):
    spec = [P.UNCONSTRAINED] * x.ndim
    for dim, axes in pinned.items():
        spec[dim] = axes
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_batch(x, axis: int = 0):
    """Pin dimension ``axis`` (the batch) to the data-parallel axes."""
    ctx = current_sharding()
    if (ctx is None or ctx.dp_size <= 1 or not in_mesh()
            or x.ndim <= axis or x.shape[axis] % ctx.dp_size):
        return x
    return _constrain(x, {axis: ctx.dp_axes})


def shard_heads(x, axis: int):
    """Pin dimension ``axis`` (the head axis) to the model axis.

    Also pins dim 0 to the data axes when it is a batch dim (divisible by
    dp_size), which keeps flash-attention scan carries from collapsing to
    a replicated fixed point. No-op when the head count does not divide.
    """
    ctx = current_sharding()
    if ctx is None or not in_mesh():
        return x
    pinned: dict[int, object] = {}
    if ctx.mp_size > 1 and x.shape[axis] % ctx.mp_size == 0:
        pinned[axis] = ctx.model_axis
    if (axis != 0 and ctx.dp_size > 1 and x.ndim
            and x.shape[0] % ctx.dp_size == 0):
        pinned[0] = ctx.dp_axes
    if not pinned:
        return x
    return _constrain(x, pinned)


def padded_head_count(n_heads: int) -> int:
    """Head count rounded up to a multiple of the active model-axis size."""
    ctx = current_sharding()
    if ctx is None or ctx.mp_size <= 1:
        return n_heads
    return -(-n_heads // ctx.mp_size) * ctx.mp_size
