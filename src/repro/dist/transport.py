"""Pluggable gradient transport: how gradients cross the wire.

Before this module, gradient-reduction logic was smeared across three
places: ``make_train_step`` carried inline ``if fsdp:`` collective
branches, ``dist/fsdp.py`` owned the gather/scatter helpers, and the
bf16-SR wire (``optim/grad_compress.py``) was orphaned — no train step
called it and its error-feedback residuals lived nowhere. A
:class:`GradientTransport` owns the whole gradient path instead, and the
train step is strategy-agnostic: it calls ``prepare`` (pre-forward
placement of the working copy), ``reduce`` (the cross-replica sum) and
``finalize`` (post-update placement) and never names a collective.

Three concrete strategies, selected **per mesh axis**:

* :class:`Fp32Psum` — the pjit default. With no wire axis this is the
  implicit GSPMD reduction (exactly the pre-transport step). With a wire
  axis (the DCN ``pod`` axis of a multi-pod mesh) the per-pod gradient
  stack is upcast to f32 and mean-reduced explicitly — 4 bytes/grad
  element on the DCN wire, the fp32-reduction baseline of "A Study of
  BFLOAT16 for Deep Learning Training".
* :class:`ReduceScatter` — the FSDP path: all-gather the bf16 working
  copy before forward, constrain gradients back onto the parameter shard
  layout so the cross-replica sum may lower to a reduce-scatter, keep
  parameters sharded after the update (see :mod:`repro.dist.fsdp`).
* :class:`CompressedWire` — the paper's two primitives applied to
  communication: each wire replica stochastically rounds its gradient
  contribution to bf16 (2 bytes/element on the wire — half of fp32) and
  carries the quantization error in a per-leaf Kahan-style
  **error-feedback residual** to the next step
  (``optim/grad_compress.py::compressed_psum`` inside ``shard_map``).
  SR keeps the reduce unbiased (E[q(g)] = g); error feedback keeps the
  compression error compensated instead of accumulated. Residuals are
  training state: they persist in ``TrainState.wire_residuals``, are
  checkpointed, and re-shard elastically on resume.

Hierarchical reduction falls out of composition: a 2-pod mesh runs
reduce-scatter (or plain psum) on the ICI ``data``/``fsdp`` axes —
that reduction happens *inside* each pod's backward pass, per wire
chunk — and the compressed bf16 wire only on the DCN ``pod`` axis,
where bytes are expensive. The ``inner`` transport handles the ICI
axes; the wire strategy handles the wire axis.

Wire-axis mechanics (how a jit-visible per-replica quantity exists at
all): when a transport has a wire axis of size n > 1, the train step
splits the batch into n chunks along the batch dim and vmaps
forward/backward over the chunks (``spmd_axis_name`` pins the chunk dim
to the wire axis), so gradients arrive *stacked* — leaf shape
``(n, *param_shape)``, sharded over the wire axis on dim 0 — and the
wire reduction over that leading dim is explicit and replaceable rather
than fused invisibly into the backward all-reduce. Residual leaves carry
the same leading wire dim.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.formats import BF16, FORMATS, FP32, FloatFormat
from repro.dist import fsdp as F
from repro.dist import partition as PT
from repro.dist.partition import Placement
from repro.optim import grad_compress as GC

__all__ = ["GradientTransport", "Fp32Psum", "ReduceScatter",
           "CompressedWire", "WirePolicy", "make_transport"]

PyTree = Any

_is_spec = lambda x: isinstance(x, P)  # noqa: E731 — tree_map leaf predicate


def _wire_size(mesh, axis: Optional[str]) -> int:
    if mesh is None or axis is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


@dataclasses.dataclass(frozen=True)
class WirePolicy:
    """Per-leaf wire-format selection: which gradients skip compression.

    "A Study of BFLOAT16 for Deep Learning Training" (PAPERS.md) keeps
    small/sensitive tensors at higher precision; this is that idea on the
    wire. Leaves with fewer than ``keep_below`` elements, or whose tree
    path contains any of ``keep_patterns`` (embeddings, norms, biases —
    matched case-insensitively against ``jax.tree_util.keystr``), ride
    fp32; everything else (the bulk matmul leaves) takes the configured
    low format. Keeping the small leaves costs almost no bytes — the wire
    is dominated by the matmul weights — but protects exactly the tensors
    whose quantization noise is hardest to average away.
    """

    keep_below: int = 2048
    keep_patterns: tuple[str, ...] = ("embed", "norm", "bias", "scale")

    def format_for(self, name: str, size: int,
                   base_fmt: FloatFormat) -> FloatFormat:
        """Wire format for one leaf: ``base_fmt`` or the fp32 keep."""
        lname = name.lower()
        if size < self.keep_below or \
                any(p in lname for p in self.keep_patterns):
            return FP32
        return base_fmt

    def describe(self) -> str:
        pats = ",".join(self.keep_patterns) or "-"
        return f"keep<{self.keep_below}|{pats}"

    @classmethod
    def parse(cls, spec: str) -> "WirePolicy":
        """Build from a ``--wire-keep-fp32`` spec string.

        Comma-separated tokens: a numeric token sets ``keep_below``,
        every other token is a name pattern. ``"default"`` (or ``""``)
        gives the stock policy; ``"none"`` disables pattern/size keeps
        (every leaf rides the low format).
        """
        spec = (spec or "").strip()
        if spec in ("", "default"):
            return cls()
        if spec == "none":
            return cls(keep_below=0, keep_patterns=())
        keep_below = 0
        patterns: list[str] = []
        for tok in spec.split(","):
            tok = tok.strip()
            if not tok:
                continue
            if tok.isdigit():
                keep_below = int(tok)
            else:
                patterns.append(tok)
        return cls(keep_below=keep_below, keep_patterns=tuple(patterns))


class GradientTransport:
    """Strategy interface for the gradient path of one train step.

    The step calls, in order::

        wc = transport.prepare(compute_params(state.params, policy))
        loss, grads = ...forward/backward...   # stacked when wire_replicas>1
        grads, new_residuals = transport.reduce(grads, state.wire_residuals, key)
        new_params, new_opt = optimizer.update(grads, ...)
        new_params = transport.finalize(new_params)

    ``wire_replicas`` (n) and ``wire_axis`` describe the explicit wire:
    with n > 1 the step hands ``reduce`` gradients stacked on a leading
    wire dim of size n and expects the reduced (unstacked) mean back.
    Stateless transports keep ``init_residuals``/``residual_specs`` at
    ``None`` and pass residuals through untouched.
    """

    name = "base"
    wire_axis: Optional[str] = None
    wire_replicas: int = 1

    def init_residuals(self, params: PyTree) -> PyTree | None:
        """Zero error-feedback state for ``TrainState.wire_residuals``."""
        return None

    def residual_specs(self, pspecs: PyTree) -> PyTree | None:
        """PartitionSpecs matching ``init_residuals``, leaf-for-leaf."""
        return None

    def prepare(self, wc: PyTree) -> PyTree:
        """Pre-forward placement of the compute-format working copy."""
        return wc

    def reduce(self, grads: PyTree, residuals: PyTree | None,
               key: jax.Array) -> tuple[PyTree, PyTree | None]:
        """Cross-replica reduction; returns (mean grads, new residuals)."""
        return grads, residuals

    def finalize(self, params: PyTree) -> PyTree:
        """Post-update placement of the new parameters."""
        return params

    def hint_axes(self, mesh) -> tuple[tuple[str, ...], int]:
        """Activation-sharding hint axes under this transport: every
        data-parallel mesh axis *except* the wire axis (the per-chunk
        vmap carries that one — hinting it too would put the axis twice
        in one constraint), plus their size product. Callers feed the
        pair straight into :func:`repro.dist.axes.activation_sharding`.
        """
        axes = tuple(a for a in PT.dp_axes(mesh) if a != self.wire_axis)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return axes, size


def _wire_specs(pspecs, grads, axis):
    """(in, out) spec trees for a stacked-gradient wire reduce: stack dim
    on the wire axis in, replicated out; trailing dims keep the
    parameter layout."""
    if pspecs is None:
        pspecs = jax.tree_util.tree_map(lambda g: P(), grads)
    g_specs = jax.tree_util.tree_map(
        lambda s: P(axis, *s), pspecs, is_leaf=_is_spec)
    out_specs = jax.tree_util.tree_map(
        lambda s: P(None, *s), pspecs, is_leaf=_is_spec)
    return g_specs, out_specs


class Fp32Psum(GradientTransport):
    """The pjit default, optionally with an explicit f32 wire axis.

    ``axis=None`` (or an axis absent from the mesh): pure pass-through —
    GSPMD's implicit backward reduction, byte-for-byte the historic
    step. With a wire axis of size n > 1: the stacked per-replica
    gradients are upcast to f32 and psum-mean-reduced over the wire axis
    inside ``shard_map`` — 4 bytes/grad element on the DCN wire (an
    explicit collective, so the wire format is measurable in the lowered
    module; a GSPMD-deferred mean would be free to disappear into the
    partitioner).
    """

    name = "fp32_psum"

    def __init__(self, *, axis: Optional[str] = None, mesh=None,
                 pspecs: PyTree | None = None):
        self.wire_axis = axis if _wire_size(mesh, axis) > 1 else None
        self.wire_replicas = _wire_size(mesh, axis)
        self.mesh = mesh
        self.pspecs = pspecs

    def reduce(self, grads, residuals, key):
        if self.wire_replicas == 1:
            return grads, residuals
        g_specs, out_specs = _wire_specs(self.pspecs, grads, self.wire_axis)
        axis = self.wire_axis
        n = float(self.wire_replicas)   # static — no collective to learn it

        def body(g):
            g = jax.tree_util.tree_map(
                lambda x: x[0].astype(jnp.float32), g)
            red = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, axis) / n, g)
            return jax.tree_util.tree_map(lambda x: x[None], red)

        reduced = jax.shard_map(body, mesh=self.mesh, in_specs=(g_specs,),
                                out_specs=out_specs, check_vma=False)(grads)
        return jax.tree_util.tree_map(lambda x: x[0], reduced), residuals


class ReduceScatter(GradientTransport):
    """Today's FSDP path as a transport (see :mod:`repro.dist.fsdp`).

    All-gather the working copy pre-forward, land gradients on the
    parameter shard layout (so the cross-replica sum may lower to a
    reduce-scatter), keep parameters sharded post-update. No explicit
    wire axis: the reduction itself stays inside GSPMD's backward.
    """

    name = "reduce_scatter"

    def __init__(self, pspecs: PyTree, placement: Placement):
        self.pspecs = pspecs
        self.placement = placement

    def prepare(self, wc):
        return F.all_gather_params(wc, self.pspecs, self.placement)

    def reduce(self, grads, residuals, key):
        return F.reduce_scatter_grads(grads, self.pspecs, self.placement), \
            residuals

    def finalize(self, params):
        return F.constrain(params, self.pspecs)


class CompressedWire(GradientTransport):
    """SR-compressed wire with per-leaf Kahan error-feedback residuals.

    Each wire replica quantizes ``g + residual`` onto ``fmt``'s grid with
    stochastic rounding, the quantized values cross the wire (``psum``
    inside ``shard_map`` over the wire axis), and the residual keeps the
    quantization error for the next step. ``fmt`` is any
    :class:`repro.core.formats.FloatFormat` — bf16 (the default, 2
    bytes/element, half of an f32 reduce), the sub-16-bit e8 formats
    bf14/bf12/bf10, or the fp8 wire formats e5m2/e4m3 (clamped at
    ``max_finite``; these grids have no ±inf). On CPU/simulation the
    psum operand rides a *carrier* dtype (bf16 or f16 — the narrowest
    native dtype whose grid contains ``fmt``'s); accounted wire bytes
    are ``fmt.bits``-based, see :meth:`payload_bytes`. With a single
    wire replica (no mesh, or the axis absent) the same arithmetic runs
    locally — SR quantization with error feedback, no collective — so
    the strategy is testable on one device.

    ``policy`` (a :class:`WirePolicy`, optional) selects per-leaf keeps:
    matching leaves ride fp32, the rest ride ``fmt``. Formats are
    resolved at trace time *outside* shard_map from global leaf names
    and sizes (inside the body leaves are local shards — their sizes
    would be wrong).

    ``inner`` (default :class:`Fp32Psum` pass-through) supplies the ICI
    behaviour: under FSDP pass a :class:`ReduceScatter` so
    prepare/finalize gather/scatter the working copy and the per-chunk
    ICI reduction lands on the shard layout — the hierarchical
    composition.

    Residual leaves are f32 with shape ``(wire_replicas, *param_shape)``
    — one error-feedback buffer per wire replica — sharded
    ``P(wire_axis, *param_spec)`` so each replica owns its buffer and
    the trailing dims co-shard leaf-for-leaf with the parameter.
    (fp32-kept leaves keep their residual buffer too — always zero, but
    a format-independent state layout means switching policy or format
    never changes checkpoint shapes; resume-time format *drift* is
    handled by zero-initing, see ``train/loop.py``.)
    """

    name = "compressed_wire"

    def __init__(self, *, axis: str = PT.POD_AXIS, mesh=None,
                 inner: GradientTransport | None = None,
                 pspecs: PyTree | None = None,
                 fmt: FloatFormat = BF16,
                 policy: WirePolicy | None = None):
        if fmt.name == "fp32":
            raise ValueError("CompressedWire with an fp32 format is the "
                             "Fp32Psum transport; use wire='fp32'")
        self.mesh = mesh
        self.inner = inner or Fp32Psum()
        self.pspecs = pspecs
        self.fmt = fmt
        self.policy = policy
        self.wire_replicas = _wire_size(mesh, axis)
        self.wire_axis = axis if self.wire_replicas > 1 else None

    @property
    def wire_format(self) -> str:
        """Stable identity of the wire numerics (checkpoint drift key)."""
        if self.policy is None:
            return self.fmt.name
        return f"{self.fmt.name}+{self.policy.describe()}"

    # -- per-leaf format resolution (trace time, global shapes) ---------
    def leaf_formats(self, tree: PyTree, *,
                     stacked: bool = False) -> list[FloatFormat]:
        """Wire format per flattened leaf of ``tree`` (params or grads).

        ``stacked=True`` when leaves carry the leading wire-replica dim
        (gradients inside ``reduce``): the policy's size threshold is
        about the *parameter*, so the stack dim is divided out.
        """
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        if self.policy is None:
            return [self.fmt] * len(flat)
        div = self.wire_replicas if stacked else 1
        return [self.policy.format_for(jax.tree_util.keystr(path),
                                       leaf.size // div, self.fmt)
                for path, leaf in flat]

    def payload_bytes(self, params: PyTree) -> int:
        """Accounted wire bytes for one reduce: Σ n_elem · bits(fmt)/8.

        This is the *format* width, not the carrier's — sub-bf16 formats
        are simulated on a bf16/f16 carrier on CPU, and counting carrier
        bytes would credit bf12 with bf16's 2 bytes/element (the
        accounting bug this method exists to fix). Fractional-byte
        widths accumulate in bits and round up once at the end.
        """
        fmts = self.leaf_formats(params)
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        bits = sum(leaf.size * f.bits for (_, leaf), f in zip(flat, fmts))
        return -(-bits // 8)

    # -- error-feedback state -------------------------------------------
    def init_residuals(self, params):
        n = self.wire_replicas
        return jax.tree_util.tree_map(
            lambda w: jnp.zeros((n,) + tuple(w.shape), jnp.float32), params)

    def residual_specs(self, pspecs):
        return jax.tree_util.tree_map(
            lambda s: P(self.wire_axis, *s), pspecs, is_leaf=_is_spec)

    # -- placement delegates to the ICI transport -----------------------
    def prepare(self, wc):
        return self.inner.prepare(wc)

    def finalize(self, params):
        return self.inner.finalize(params)

    # -- the wire -------------------------------------------------------
    def reduce(self, grads, residuals, key):
        if residuals is None:
            raise ValueError(
                "CompressedWire needs error-feedback residuals: build the "
                "state with make_train_state(params, opt, transport=...) so "
                "TrainState.wire_residuals is initialized")
        if self.wire_replicas == 1:
            return self._reduce_local(grads, residuals, key)
        return self._reduce_sharded(grads, residuals, key)

    def _reduce_local(self, grads, residuals, key):
        """Single wire replica: SR quantize + error feedback, no psum."""
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        res_leaves = treedef.flatten_up_to(residuals)
        fmts = self.leaf_formats(grads)
        keys = jax.random.split(key, len(leaves))
        out, new_res = [], []
        for g, r, k, fmt in zip(leaves, res_leaves, keys, fmts):
            q, nr = GC.compress_leaf(g, r[0], k, fmt)
            out.append(q.astype(jnp.float32))
            new_res.append(nr[None])
        return (jax.tree_util.tree_unflatten(treedef, out),
                jax.tree_util.tree_unflatten(treedef, new_res))

    def _reduce_sharded(self, grads, residuals, key):
        """n > 1: low-format SR psum over the wire axis inside shard_map.

        ``grads`` arrive stacked ``(n, *shape)``; in/out specs put the
        stack dim on the wire axis so each replica sees exactly its own
        contribution (and its own residual buffer), and the trailing
        dims keep the parameter layout (ICI shards stay local — the
        quantize is elementwise and the psum touches only the wire
        axis). The reduced mean comes back unstacked and replicated
        over the wire axis. Per-leaf formats resolve here, outside the
        body, from the *global* stacked shapes (body leaves are local
        shards) and reach the body by closure.
        """
        axis = self.wire_axis
        g_specs, out_specs = _wire_specs(self.pspecs, grads, axis)
        fmts = self.leaf_formats(grads, stacked=True)

        def body(g, r, k):
            g = jax.tree_util.tree_map(lambda x: x[0], g)
            r = jax.tree_util.tree_map(lambda x: x[0], r)
            red, nr = GC.compressed_psum(g, r, k, axis, fmts)
            add_dim = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            return add_dim(red), add_dim(nr)

        reduced, new_res = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(g_specs, g_specs, P()),
            out_specs=(out_specs, g_specs),
            check_vma=False)(grads, residuals, key)
        return (jax.tree_util.tree_map(lambda x: x[0], reduced), new_res)


def make_transport(*, mesh=None, placement: Placement | None = None,
                   pspecs: PyTree | None = None, wire: str = "fp32",
                   wire_axis: Optional[str] = None,
                   wire_policy: WirePolicy | None = None
                   ) -> GradientTransport:
    """Build the transport for a (mesh, placement) pair.

    ``wire`` selects the cross-pod strategy (``--grad-wire``):

    * ``"fp32"`` — :class:`Fp32Psum`. Gets an explicit f32 wire axis
      only when the mesh has a ``pod`` axis (DCN); otherwise it is the
      implicit GSPMD reduction, i.e. the historic step unchanged.
    * ``"compressed"`` — :class:`CompressedWire` on ``wire_axis``
      (default: the ``pod`` axis when the mesh has one, else ``data``)
      at the historic SR-bf16 format.
    * a format name — ``"bf16"``, ``"bf14"``, ``"bf12"``, ``"bf10"``,
      ``"fp16"``, ``"e5m2"``, ``"e4m3"`` — :class:`CompressedWire` at
      that :class:`~repro.core.formats.FloatFormat`.

    ``wire_policy`` (optional :class:`WirePolicy`) adds the per-leaf
    fp32 keep on any compressed wire; it is ignored for ``"fp32"``
    (everything already rides fp32 there).

    The ICI side is independent: an FSDP placement yields a
    :class:`ReduceScatter` (standalone for ``fp32``, as ``inner`` for
    the compressed wire); otherwise plain psum.
    """
    fsdp_on = (placement is not None and placement.fsdp_axis is not None
               and pspecs is not None)
    inner = ReduceScatter(pspecs, placement) if fsdp_on else Fp32Psum()
    if wire == "fp32":
        axis = wire_axis
        if axis is None and mesh is not None \
                and PT.POD_AXIS in mesh.axis_names:
            axis = PT.POD_AXIS
        if axis is None or _wire_size(mesh, axis) <= 1:
            return inner
        _check_wire_axis_free(axis, mesh, placement)
        if fsdp_on:
            # explicit f32 pod wire over an FSDP inner: pod psum-mean
            # first, then the ReduceScatter constraints — composed like
            # CompressedWire but with the f32 arithmetic
            return _Fp32Wire(axis=axis, mesh=mesh, inner=inner,
                             pspecs=pspecs)
        return Fp32Psum(axis=axis, mesh=mesh, pspecs=pspecs)
    if wire == "compressed" or wire in FORMATS:
        fmt = BF16 if wire == "compressed" else FORMATS[wire]
        axis = wire_axis
        if axis is None:
            axis = (PT.POD_AXIS if mesh is not None
                    and PT.POD_AXIS in mesh.axis_names else PT.DATA_AXIS)
        _check_wire_axis_free(axis, mesh, placement)
        return CompressedWire(axis=axis, mesh=mesh, inner=inner,
                              pspecs=pspecs, fmt=fmt, policy=wire_policy)
    raise ValueError(f"unknown gradient wire {wire!r}; "
                     f"expected 'fp32', 'compressed', or a format name "
                     f"({', '.join(n for n in FORMATS if n != 'fp32')})")


def _check_wire_axis_free(axis, mesh, placement: Placement | None) -> None:
    """A wire axis must not double as a parameter-sharding axis: residual
    specs are ``P(wire_axis, *param_spec)``, so an axis the placement
    already claims (FSDP over ``data`` is the common collision) would
    appear twice in one PartitionSpec — rejected here with guidance
    instead of failing later inside NamedSharding construction."""
    if _wire_size(mesh, axis) <= 1 or placement is None:
        return
    if axis in (placement.fsdp_axis, placement.tp_axis):
        raise ValueError(
            f"gradient wire axis {axis!r} is already claimed by the "
            f"placement ({placement}); give the wire its own data axis — "
            f"a pod axis (--pods) or a dedicated fsdp axis "
            f"(--fsdp-parallel) so the wire can ride 'data'")


class _Fp32Wire(Fp32Psum):
    """f32 pod wire stacked on an ICI transport (FSDP under multi-pod)."""

    def __init__(self, *, axis: str, mesh, inner: GradientTransport,
                 pspecs: PyTree | None = None):
        super().__init__(axis=axis, mesh=mesh, pspecs=pspecs)
        self.inner = inner

    def prepare(self, wc):
        return self.inner.prepare(wc)

    def reduce(self, grads, residuals, key):
        grads, residuals = super().reduce(grads, residuals, key)
        return self.inner.reduce(grads, residuals, key)

    def finalize(self, params):
        return self.inner.finalize(params)
