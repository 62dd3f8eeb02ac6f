"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Axis names are validated against the :mod:`repro.dist.partition`
constants so a typo'd mesh can never silently replicate what the
placement meant to shard. Every axis is ``AxisType.Auto``: the
placement rules and the ``with_sharding_constraint`` hints leave the
unpinned dimensions to GSPMD propagation, which ``Explicit`` axes (the
``jax.make_mesh`` default) refuse.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

from repro.dist import partition as PT

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with validated axis names and ``Auto`` axis types."""
    unknown = [a for a in axes if a not in PT.KNOWN_AXES]
    if unknown:
        raise ValueError(
            f"unknown mesh axis name(s) {unknown}; the partition rules "
            f"understand {list(PT.KNOWN_AXES)}")
    if len(set(axes)) != len(axes):
        raise ValueError(f"duplicate mesh axis names: {axes}")
    want = math.prod(shape)
    have = len(devices) if devices is not None else jax.device_count()
    if want > have:
        # a *smaller* mesh is fine (jax.make_mesh takes the first `want`
        # devices — single-process tests rely on it); an oversized one
        # fails here with the process topology instead of deep in XLA
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {want} devices but "
            f"only {have} are visible ({jax.process_count()} process(es) "
            f"× {jax.local_device_count()} local) — under jax.distributed "
            f"the mesh spans every host's devices; size the axes to the "
            f"global count")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, fsdp: int = 1):
    """Single-pod (16×16 = 256 chips) or 2-pod (2×16×16 = 512 chips) mesh.

    Axes: ``data`` carries DP, ``model`` carries TP/EP, ``pod`` is pure DP
    across ICI domains (gradient all-reduce rides DCN). ``fsdp > 1``
    carves an ``fsdp`` axis of that size out of the 16-wide data dim —
    batches still shard over ``data × fsdp`` (both are data axes), while
    params + optimizer state shard over ``fsdp`` under an FSDP placement.
    """
    if fsdp > 1:
        if 16 % fsdp:
            raise ValueError(f"fsdp={fsdp} must divide the 16-wide data dim")
        shape = (16 // fsdp, fsdp, 16)
        axes = (PT.DATA_AXIS, PT.FSDP_AXIS, PT.MODEL_AXIS)
    else:
        shape = (16, 16)
        axes = (PT.DATA_AXIS, PT.MODEL_AXIS)
    if multi_pod:
        shape = (2,) + shape
        axes = (PT.POD_AXIS,) + axes
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, fsdp: int = 1,
                    pods: int = 1, *, devices=None):
    """Small mesh over whatever devices exist (tests / CPU / multi-host).

    Under ``jax.distributed`` the default device set is *global* — one
    axis of size ``process_count × local_devices`` gives cross-host data
    parallelism (gradient collectives ride gloo/DCN). ``devices``
    overrides the set explicitly (order defines mesh position).

    ``fsdp > 1`` adds a dedicated ``fsdp`` axis between ``data`` and
    ``model`` (e.g. ``make_local_mesh(2, 2, fsdp=2)`` is the 8-device
    2 data × 2 fsdp × 2 model test topology); ``pods > 1`` prepends a
    ``pod`` axis — the virtual stand-in for DCN-connected ICI domains,
    the axis the gradient-wire strategies (``--grad-wire``) reduce over.
    Otherwise the historic two-axis layout is kept so existing callers
    see the same mesh.
    """
    shape: tuple = (data, model)
    axes: tuple = (PT.DATA_AXIS, PT.MODEL_AXIS)
    if fsdp > 1:
        shape = (data, fsdp, model)
        axes = (PT.DATA_AXIS, PT.FSDP_AXIS, PT.MODEL_AXIS)
    if pods > 1:
        shape = (pods,) + shape
        axes = (PT.POD_AXIS,) + axes
    return make_mesh(shape, axes, devices)
