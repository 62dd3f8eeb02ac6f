"""Plain float32 AdamW training steps over a reference model, the one
that the configuration's ``reference`` key names (``reference/<name>.py``,
found through ``harness.arch``).

The optimizer state (change, m, v) lives on the host as float32 arrays,
so that the devices hold only the weights, one gradient and one step's
activations: a whole float32 state of a one-chip cell would not fit
beside them. The update runs on the devices leaf by leaf, so the host
holds the state and one leaf in flight, and nothing else.

On several devices (a cell's chips) every weight and gradient leaf is
split over a 1-D mesh of them (:func:`shardings`), and the update runs
on that layout; on one device each leaf lies whole on it.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from harness import arch

F32 = jnp.float32


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then a cosine to 0
    at ``schedule_steps``; ``step`` counts from 0."""
    peak, warm = opt["lr"], max(opt["warmup_steps"], 1)
    if step < warm:
        return peak * step / warm
    total = max(opt["schedule_steps"] - warm, 1)
    frac = min(max((step - warm) / total, 0.0), 1.0)
    return 0.5 * peak * (1 + math.cos(math.pi * frac))


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


def leaf_norms(leaves) -> np.ndarray:
    """float32 L2 norm of every leaf, in order."""
    return np.asarray([float(_norm(x)) for x in leaves])


def _adamw(opt: dict):
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    @partial(jax.jit, donate_argnums=(2,))
    def update(w0, d, g, m, v, lr, t):
        """AdamW on ``w = w0 + d``; the change ``d`` is kept apart from
        ``w0``, so an update far below ``w0``'s float32 spacing (early
        warm-up steps) is not lost to rounding."""
        w0 = w0.astype(F32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        d = d - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * (w0 + d))
        return d, m, v

    return update


@jax.jit
def _weight(w0, d):
    return w0.astype(F32) + d


@jax.jit
def _diff_norm(a, scale, b):
    return jnp.sqrt(jnp.sum(jnp.square(scale * a.astype(F32)
                                       - b.astype(F32))))


def _layout(devices):
    """The sharding of an array that every device holds whole, and a
    function from a leaf's shape to its sharding: split along its largest
    axis that the device count divides, whole on every device where no
    axis does."""
    if len(devices) == 1:
        one = SingleDeviceSharding(devices[0])
        return one, lambda shape: one
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("d",))

    def split(shape):
        spec = [None] * len(shape)
        axes = [a for a, size in enumerate(shape) if size % n == 0]
        if axes:
            spec[max(axes, key=lambda a: shape[a])] = "d"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return NamedSharding(mesh, PartitionSpec()), split


def shardings(shapes, devices):
    """Where :func:`run` keeps each leaf of ``shapes`` on ``devices``."""
    _, split = _layout(devices)
    return jax.tree_util.tree_map(lambda s: split(s.shape), shapes)


def run(cfg: dict, w0, batches, opt: dict, *, steps: int, devices,
        mm: str = "exact", half_batch: bool = False, compare=None,
        keep_grad: bool = False, log=None) -> dict:
    """``steps`` AdamW steps from the weights ``w0`` on ``batches`` (a list
    of {"tokens", "labels"}). Returns each step's loss, the per-leaf norm
    of the first gradient and of the weights' change over all steps.
    ``half_batch`` plants a fault: the loss is the mean over the first
    half of each row's tokens only. ``compare`` maps a name to another
    run's first gradient, as host leaves and a factor to scale them by;
    the result then holds, under ``grad_diff``, the per-leaf norm of each
    one's difference from this run's. ``keep_grad`` returns this run's
    first gradient as bf16 host leaves (``grad``). The host holds three
    float32 copies (change, m, v); ``devices`` hold ``w0``, the float32
    weights and the gradient, each leaf where :func:`shardings` puts it."""
    model = arch.module(cfg, "reference")
    items = model.frozen(cfg)
    whole, split = _layout(devices)
    base, treedef = jax.tree_util.tree_flatten(w0)
    place = [split(x.shape) for x in base]
    base = [jax.device_put(x, s) for x, s in zip(base, place)]

    @partial(jax.jit, out_shardings=(whole, treedef.unflatten(place)))
    def grad_fn(p, tokens, labels):
        if half_batch:
            keep = tokens.shape[1] // 2
            tokens, labels = tokens[:, :keep], labels[:, :keep]
        return jax.value_and_grad(lambda p: model.loss(
            dict(items), p, tokens, labels, model.MATMULS[mm]))(p)

    def put(leaves):
        return (jax.device_put(x, s) for x, s in zip(leaves, place))

    w_dev = treedef.unflatten([x.astype(F32) for x in base])
    d = [np.zeros(x.shape, np.float32) for x in base]
    m = [np.zeros(x.shape, np.float32) for x in base]
    v = [np.zeros(x.shape, np.float32) for x in base]
    update = _adamw(opt)
    losses, g1, out = [], None, {}
    for t in range(steps):
        t0 = time.perf_counter()
        tokens, labels = (jax.device_put(batches[t][k], whole)
                          for k in ("tokens", "labels"))
        loss, g = grad_fn(w_dev, tokens, labels)
        losses.append(float(loss))
        del w_dev
        g = jax.tree_util.tree_leaves(g)
        if t == 0:
            g1 = leaf_norms(g)
            if compare:
                out["grad_diff"] = {name: np.asarray([
                    float(_diff_norm(o, scale, gi))
                    for o, gi in zip(put(other), g)])
                    for name, (other, scale) in compare.items()}
            if keep_grad:
                out["grad"] = [np.asarray(x.astype(jnp.bfloat16)) for x in g]
        t1 = time.perf_counter()
        lr = float(lr_at(opt, t))
        for i in range(len(base)):
            gi, g[i] = g[i], None
            s = place[i]
            new = update(base[i], jax.device_put(d[i], s), gi,
                         jax.device_put(m[i], s), jax.device_put(v[i], s),
                         lr, t + 1)
            del gi
            d[i], m[i], v[i] = (np.asarray(x) for x in new)
            del new
        t2 = time.perf_counter()
        if t + 1 < steps:
            w_dev = treedef.unflatten([_weight(b, dd)
                                       for b, dd in zip(base, put(d))])
        if log:
            log(f"reference {mm} step {t}: grad {t1 - t0:.2f}s, update "
                f"{t2 - t1:.2f}s, to device {time.perf_counter() - t2:.2f}s")
    out.update(losses=losses, grad_norms=g1, change_norms=leaf_norms(put(d)))
    return out
