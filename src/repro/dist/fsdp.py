"""Fully-sharded data parallelism over the paper's 16-bit training state.

The point of FSDP *here* (vs the generic ZeRO-3 recipe): Algorithm 4/5
training doubles per-weight optimizer state (Kahan compensation, SR
residuals) to stay in pure bf16 — the same memory an fp32-master-copy
scheme spends on 32-bit weights. Sharding parameters *and* every
optimizer buffer over the data axis makes bf16+Kahan strictly cheaper per
device than mixed-precision, and the wire cost is halved too: the
all-gather moves the bf16 *working copy* (2 bytes/weight), never an fp32
master.

Mechanics (GSPMD, not shard_map): parameters and optimizer state live
sharded per :func:`repro.dist.partition.param_specs` with an FSDP
placement. Inside the jitted step,

* :func:`all_gather_params` drops the FSDP axis from each leaf's spec via
  ``with_sharding_constraint`` — XLA materializes the all-gather, in the
  compute dtype of whatever the caller passes (cast to bf16 *first* so
  the gather is 16-bit on the wire);
* :func:`reduce_scatter_grads` constrains gradients back onto the
  parameter specs, so the optimizer update partitions over the FSDP axis
  and the cross-replica gradient sum *may* lower to a reduce-scatter
  (backend/pass dependent — see the function docstring);
* the optimizer update then runs leafwise on co-sharded (param, moment,
  Kahan) shards — the compensation term accumulates against the *local*
  shard, never the gathered copy, which is what keeps Algorithm 5's
  ``c`` buffer exact under sharding.

Every helper is a no-op outside an active mesh or under a placement with
no FSDP axis, so the same step code serves single-device runs.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import partition as PT
from repro.dist.axes import in_mesh
from repro.dist.partition import Placement

__all__ = ["unshard_spec", "gather_specs", "all_gather_params",
           "reduce_scatter_grads", "constrain", "train_state_shardings",
           "per_device_bytes"]

PyTree = Any

_is_spec = lambda x: isinstance(x, P)  # noqa: E731 — tree_map leaf predicate


def unshard_spec(spec: P, placement: Placement) -> P:
    """``spec`` with the FSDP axis removed from every dimension entry."""
    axis = placement.fsdp_axis

    def drop(entry):
        if entry == axis:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a != axis)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return entry

    return P(*(drop(e) for e in spec))


def gather_specs(pspecs: PyTree, placement: Placement) -> PyTree:
    """Specs of the gathered working copy: FSDP axis dropped leaf-for-leaf."""
    return jax.tree_util.tree_map(
        lambda s: unshard_spec(s, placement), pspecs, is_leaf=_is_spec)


def constrain(tree: PyTree, specs: PyTree) -> PyTree:
    """``with_sharding_constraint`` leaf-for-leaf; no-op outside a mesh."""
    if not in_mesh():
        return tree
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, specs)


def all_gather_params(params: PyTree, pspecs: PyTree,
                      placement: Placement) -> PyTree:
    """Gather the FSDP shards into a full working copy for forward/backward.

    Pass the *compute-format* copy (``compute_params``'s output): the
    all-gather then moves bf16 on the wire, half the bytes of gathering
    storage-format masters.
    """
    if placement.fsdp_axis is None or not in_mesh():
        return params
    return constrain(params, gather_specs(pspecs, placement))


def reduce_scatter_grads(grads: PyTree, pspecs: PyTree,
                         placement: Placement) -> PyTree:
    """Land each gradient leaf on its parameter's shard layout.

    Constraining the backward cotangents onto the FSDP'd parameter specs
    is what *allows* XLA to lower the cross-replica gradient sum to a
    reduce-scatter and guarantees the optimizer update downstream is
    partitioned: every device's update reads only its gradient shard.
    Whether the scattered form is actually emitted is backend/pass
    dependent (TPU's reduce-scatter-creator takes it; the CPU test
    backend keeps all-reduce + slice, which is numerically identical but
    transiently materializes the unsharded gradient).
    """
    if placement.fsdp_axis is None or not in_mesh():
        return grads
    return constrain(grads, pspecs)


def train_state_shardings(state, cfg, mesh,
                          placement: Placement | None = None,
                          transport=None):
    """NamedSharding tree matching a :class:`TrainState`.

    ``step`` replicates, ``params`` follow :func:`PT.param_specs` under
    ``placement``, and the optimizer state — moments, Kahan compensation,
    SR residuals, bias-correction scalars — follows
    :func:`PT.state_shardings`, i.e. co-shards leaf-for-leaf with its
    parameters. When the state carries gradient-transport error-feedback
    residuals (``wire_residuals``), their specs come from
    ``transport.residual_specs`` — the parameter specs with the leading
    wire-replica dim on the transport's wire axis, so each wire replica
    owns its buffer and the trailing dims co-shard with the parameter.
    (Without a ``transport`` the leading dim replicates — only correct
    for single-replica wires.) The result serves three callers: the
    initial ``device_put`` in the launcher, the jit ``out_shardings`` if
    wanted, and the elastic checkpoint-resume path
    (``run_training(state_shardings=...)``), which re-shards restored
    state onto the *current* mesh instead of restoring it unsharded.
    """
    pspecs = PT.param_specs(state.params, cfg, mesh, placement)
    ospecs = PT.state_shardings(pspecs, state.opt_state, mesh)
    rspecs = None
    if getattr(state, "wire_residuals", None) is not None:
        if transport is not None:
            rspecs = transport.residual_specs(pspecs)
        else:
            rspecs = jax.tree_util.tree_map(
                lambda s: P(None, *s), pspecs, is_leaf=_is_spec)
    spec_tree = type(state)(P(), pspecs, ospecs, rspecs)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=_is_spec)


def per_device_bytes(tree: PyTree, device=None) -> int:
    """Bytes of ``tree`` resident on one device (default: first local).

    The number the FSDP factor acts on: params + optimizer state measured
    here shrink by ~|fsdp axis| versus DP replication.
    """
    if device is None:
        device = jax.local_devices()[0]
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        for shard in leaf.addressable_shards:
            if shard.device == device:
                total += shard.data.nbytes
    return total
