"""Train / eval / serve step builders.

``make_train_step`` returns a pure jit-able function
``(state, batch, seed) -> (state, metrics)`` closed over config, policy and
optimizer. Precision flows per the paper: forward/backward run in the
policy's compute format (master-copy policies cast a bf16 working copy of
the weights for compute), gradients land in bf16 and feed the quantized
optimizer update (Algorithms 2–5).

Every gradient collective goes through a pluggable
:class:`repro.dist.transport.GradientTransport`: the step calls
``transport.prepare`` (e.g. the FSDP all-gather of the working copy),
``transport.reduce`` (fp32 psum / reduce-scatter constraint /
SR-compressed bf16 wire with error feedback) and ``transport.finalize``
(e.g. keep parameters sharded) and itself contains no
placement-specific branches. ``grad_accum=k`` scans k microbatches over
one prepared working copy — amortizing the FSDP all-gather — before a
single reduce + optimizer update.

``make_fsdp_train_step`` is a thin delegation that selects the
reduce-scatter transport from ``pspecs``/``placement``.

Each part of the step runs under a ``jax.named_scope`` named in
:mod:`repro.profile.scopes` (``model``, ``transport``, ``optimizer``,
``step_metrics``), so a device trace can say where a step's time goes
(``docs/profiling.md``).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.formats import round_nearest
from repro.core.policy import PrecisionPolicy
from repro.core.qarith import QArith
from repro.dist import transport as T
from repro.dist.partition import Placement
from repro.models import registry as R
from repro.profile import scopes as S
from repro.train.train_state import TrainState, softmax_xent

__all__ = ["make_train_step", "make_fsdp_train_step", "make_eval_step",
           "make_serve_step", "compute_params"]

PyTree = Any

#: an expert layer's per-step counters, reported as ``expert_<name>``:
#: the share of the token-expert assignments held here, and the busiest
#: held expert's assignments over the held experts' mean (averaged over
#: microbatches; the load the largest over layers)
LOAD_COUNTERS = ("held_share", "max_load")


def compute_params(params: PyTree, policy: PrecisionPolicy) -> PyTree:
    """Working copy of the weights in the compute format.

    * pure-16-bit policies: storage *is* the compute copy (no-op)
    * master-copy policies (fp32 / mixed / ablation): one RNE cast per tensor
    * simulated sub-16-bit: already grid-snapped f32, used as-is
    """
    if not policy.master_weights or policy.compute_format.name == "fp32":
        return params
    if policy.compute_format.name == "bf16":
        return jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16), params)
    return jax.tree_util.tree_map(
        lambda w: round_nearest(w, policy.compute_format), params)


def _batch_dim(path) -> int:
    """Batch dim of a batch leaf: 1 for ``mrope_positions`` ((3, B, S)
    layout — see :func:`repro.dist.partition.batch_specs`), else 0."""
    names = [str(getattr(k, "key", getattr(k, "name", ""))) for k in path]
    return 1 if names and names[-1] == "mrope_positions" else 0


def _split_microbatches(batch: PyTree, k: int, what: str) -> PyTree:
    """Split every leaf's batch dim into k chunks, chunk dim leading.

    The reshape that folds the batch dim into (k, B/k) erases the
    batch-dim sharding hint the input pipeline placed on the leaves, and
    row-major propagation would naturally land on a *chunk*-sharded
    layout (rows 8i..8i+7 of a [4]-sharded 32-row batch ARE chunk i) —
    under which every scan iteration's slice lives on one device. The
    explicit re-pin of the per-microbatch batch dim (now at ``bdim + 1``)
    makes the layout the scan body needs part of the program rather than
    a propagation outcome. Measured on the 2×2×2 CPU mesh at batch=32 the
    pin is currently a no-op (GSPMD already reshards once, before the
    loop — identical collective counts with and without); the regression
    that matters is guarded in tests/test_fsdp.py: grad_accum must not
    multiply the FSDP working-copy all-gather bytes.
    """
    from repro.dist.axes import shard_batch

    def split(path, x):
        bdim = _batch_dim(path)
        if x.shape[bdim] % k:
            raise ValueError(
                f"global batch {x.shape[bdim]} not divisible by {what}={k}")
        parts = x.shape[:bdim] + (k, x.shape[bdim] // k) + x.shape[bdim + 1:]
        return shard_batch(jnp.moveaxis(x.reshape(parts), bdim, 0), bdim + 1)

    return jax.tree_util.tree_map_with_path(split, batch)


def make_train_step(cfg, policy: PrecisionPolicy, optimizer, lr_schedule,
                    *, remat: bool = True, attn_chunk: int = 1024,
                    loss_fn: Callable | None = None,
                    pspecs: PyTree | None = None,
                    placement: Placement | None = None,
                    transport: "T.GradientTransport | None" = None,
                    grad_accum: int = 1):
    """One builder for every placement and gradient wire.

    The gradient path is owned by ``transport``
    (:class:`repro.dist.transport.GradientTransport`); when omitted it is
    derived from ``pspecs``/``placement``: an FSDP placement selects the
    reduce-scatter transport, anything else the implicit-psum default —
    so existing callers get the historic behaviour unchanged.

    ``grad_accum=k`` splits the batch into k microbatches and scans
    forward/backward over them, accumulating gradients in f32 against
    **one** prepared working copy (one FSDP all-gather per step, not per
    microbatch), then does a single reduce + optimizer update on the
    mean. With a transport whose ``wire_replicas`` is n > 1 each
    microbatch is additionally vmapped into n per-wire-replica chunks
    (``spmd_axis_name`` pins the chunk dim to the wire axis) so the wire
    reduction is explicit — see :mod:`repro.dist.transport`.

    The reported ``loss`` is the uniform mean over microbatch/chunk
    losses — identical to the global mean whenever the per-microbatch
    label masks have equal counts (always true for the synthetic LM
    streams; a caveat only under ragged ``ignore`` masks).
    """
    qa = QArith(policy)
    if transport is None:
        transport = T.make_transport(placement=placement, pspecs=pspecs)
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    n_wire = transport.wire_replicas

    def _loss(params, batch):
        """The loss, with the expert layers' router loss at
        ``cfg.router_aux_coef``, and their load counters (empty for a
        model without them)."""
        with jax.named_scope(S.MODEL):
            logits, stats = R.forward_logits(qa, params, cfg, batch,
                                             remat=remat,
                                             attn_chunk=attn_chunk,
                                             with_stats=True)
            with jax.named_scope(S.LOSS):
                if loss_fn is not None:
                    loss = loss_fn(logits, batch)
                else:
                    loss = softmax_xent(logits, batch["labels"])
                if stats and cfg.router_aux_coef:
                    loss = loss + cfg.router_aux_coef * stats["router_loss"]
        return loss, {k: stats[k] for k in LOAD_COUNTERS if k in stats}

    def _micro_grads(wc, batch):
        """(Loss, load counters) + grads of one microbatch; grads stacked
        (n_wire, ...) on the wire axis when the transport has an explicit
        wire, and the rest averaged over it."""
        if n_wire > 1:
            chunks = _split_microbatches(batch, n_wire, "wire_replicas")
            axes = jax.tree_util.tree_map(lambda _: 0, chunks)
            aux, grads = jax.vmap(
                jax.value_and_grad(_loss, has_aux=True), in_axes=(None, axes),
                spmd_axis_name=transport.wire_axis)(wc, chunks)
            return jax.tree_util.tree_map(jnp.mean, aux), grads
        return jax.value_and_grad(_loss, has_aux=True)(wc, batch)

    def train_step(state: TrainState, batch, seed) -> tuple[TrainState, dict]:
        key = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)
        wire_key = jax.random.fold_in(key, 7)
        # local-shard cast, then the transport's pre-forward placement
        # (FSDP: the bf16-wire all-gather of the working copy)
        with jax.named_scope(S.TRANSPORT):
            wc = transport.prepare(compute_params(state.params, policy))
        if grad_accum > 1:
            # one-gather-per-step contract: the gathered working copy is
            # formed here, outside the microbatch scan, and closed over
            # by the body. Inspection of the optimized HLO (2×2×2 CPU
            # mesh, batch 32) confirms XLA keeps the FSDP working-copy
            # all-gathers in the entry computation at ga>1 — total
            # all-gather bytes are flat between ga=1 and ga=4; the only
            # loop-body gathers are the small per-microbatch embedding
            # scatter-add ones (regression: tests/test_fsdp.py)
            mbs = _split_microbatches(batch, grad_accum, "grad_accum")
            first = jax.tree_util.tree_map(lambda x: x[0], mbs)
            aux_shape, g_shape = jax.eval_shape(_micro_grads, wc, first)

            def body(carry, mb):
                acc_aux, acc = carry
                aux, grads = _micro_grads(wc, mb)
                with jax.named_scope(S.TRANSPORT):
                    acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), acc, grads)
                acc_aux = jax.tree_util.tree_map(jnp.add, acc_aux, aux)
                return (acc_aux, acc), None

            init = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, jnp.float32), (aux_shape, g_shape))
            (aux, grads), _ = jax.lax.scan(body, init, mbs)
            loss, counters = jax.tree_util.tree_map(
                lambda a: a / grad_accum, aux)
            with jax.named_scope(S.TRANSPORT):
                grads = jax.tree_util.tree_map(lambda g: g / grad_accum,
                                               grads)
        else:
            (loss, counters), grads = _micro_grads(wc, batch)
        # grads arrive in the compute dtype (bf16 FMAC outputs; f32 once
        # accumulated); the transport reduces them across replicas and
        # the quantized optimizer consumes them per Algorithms 2–5.
        with jax.named_scope(S.TRANSPORT):
            grads, new_residuals = transport.reduce(
                grads, state.wire_residuals, wire_key)
        lr = lr_schedule(state.step)
        with jax.named_scope(S.OPTIMIZER):
            new_params, new_opt = optimizer.update(
                grads, state.opt_state, state.params,
                step=state.step, key=key, lr=lr)
        with jax.named_scope(S.TRANSPORT):
            new_params = transport.finalize(new_params)
        metrics = {"loss": loss.astype(jnp.float32), "lr": lr,
                   "grad_norm": _global_norm(grads)}
        with jax.named_scope(S.STEP_METRICS):
            metrics.update({f"expert_{k}": v for k, v in counters.items()})
        return TrainState(state.step + 1, new_params, new_opt,
                          new_residuals), metrics

    return train_step


def make_fsdp_train_step(cfg, policy: PrecisionPolicy, optimizer, lr_schedule,
                         *, pspecs: PyTree, placement: Placement,
                         remat: bool = True, attn_chunk: int = 1024,
                         loss_fn: Callable | None = None,
                         transport: "T.GradientTransport | None" = None,
                         grad_accum: int = 1):
    """FSDP-aware train step (params + optimizer state sharded per ``pspecs``).

    Collective structure per step:

    1. the storage shards are cast to the compute format *locally*, then
       all-gathered into the full working copy — a bf16-wire gather for
       16-bit policies, half the bytes of gathering fp32 masters;
    2. forward/backward run on the gathered copy (batch sharded over all
       data axes, FSDP axis included);
    3. gradients are constrained onto the parameter shard layout so the
       cross-replica sum can lower to a reduce-scatter (backend-
       dependent — see :func:`repro.dist.fsdp.reduce_scatter_grads`) and
       the update consumes only local gradient shards;
    4. the quantized optimizer update (Algorithms 2–5) runs leafwise on
       local shards only: moments, Kahan compensation and SR residuals
       are co-sharded with their parameter, so Algorithm 5's ``c`` buffer
       accumulates against the local shard, never the gathered copy.

    Outside a mesh (or with no FSDP axis in the placement) every
    collective helper is a no-op and this reduces to ``make_train_step``
    — which is also literally what it delegates to, with the
    reduce-scatter transport derived from ``pspecs``/``placement``
    (or an explicit ``transport``, e.g. the compressed wire stacked on
    the FSDP inner).
    """
    return make_train_step(cfg, policy, optimizer, lr_schedule, remat=remat,
                           attn_chunk=attn_chunk, loss_fn=loss_fn,
                           pspecs=pspecs, placement=placement,
                           transport=transport, grad_accum=grad_accum)


@jax.named_scope(S.STEP_METRICS)
def _global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def make_eval_step(cfg, policy: PrecisionPolicy, *, attn_chunk: int = 1024):
    qa = QArith(policy)

    def eval_step(params, batch):
        wc = compute_params(params, policy)
        logits = R.forward_logits(qa, wc, cfg, batch, remat=False,
                                  attn_chunk=attn_chunk)
        loss = softmax_xent(logits, batch["labels"])
        acc = (logits.argmax(-1) == batch["labels"]).mean()
        return {"loss": loss, "acc": acc}

    return eval_step


def make_serve_step(cfg, policy: PrecisionPolicy, *, fused_decode=False,
                    paged: bool = False, chunk: int = 1,
                    return_logits: bool = False):
    """Slot-indexed decode step:
    ``(params, cache, token, pos[, active, reset]) → (next_token, new_cache)``.

    Greedy decode of exactly one token per slot against the KV/state
    cache. Two position layouts share the implementation:

    * ``pos`` scalar — lock-step decode, every lane at the same depth
      (``repro.serve.decode.generate`` and the encoder–decoder dry-run
      cells, whose decoder position drives a scalar sinusoidal
      embedding);
    * ``pos (N,)`` — per-slot depths, the continuous-batching layout
      (:class:`repro.serve.engine.Engine`) and what the decoder-only
      ``decode_*`` / ``long_500k`` dry-run cells lower: each lane
      writes its KV cell at its own position.

    The two ``(N,)`` bool lane masks make admission and eviction part of
    the same executable — there is exactly **one** compiled program per
    (mesh, policy), shared by prefill and decode:

    * ``reset`` — slots re-initialized *before* the step (position maps
      to −1, recurrent state to 0; stale KV values merely become
      unreachable — see :func:`repro.serve.cache.reset_slots`): how the
      engine admits a request into a recycled slot;
    * ``active`` — lanes actually decoding. Parked lanes run with
      ``pos = −1``, which routes their KV scatter out of range (write
      dropped, pool untouched); their recurrent state is carried
      through by :func:`repro.serve.cache.keep_active` and they report
      token −1.

    ``fused_decode=True`` traces the step inside the
    :func:`repro.kernels.dispatch.fused_decode` context, so attention
    against the KV pool runs as the fused Pallas decode kernel (one
    launch per lane, parked lanes skipped in-kernel) — token-for-token
    parity with the generic path (tests/test_serve.py::TestFusedDecode).

    ``paged=True`` expects full-context attention caches in the paged
    layout (see :func:`repro.models.transformer.init_cache`) and two
    extra keyword inputs: ``block_table`` ((N, n_blocks) i32, logical
    block → physical page row) and ``page_reset`` ((R,) bool, physical
    pages recycled *this* step — freed pages' position rows go to −1
    in-graph, the page analogue of the ``reset`` slot mask).

    ``chunk=C > 1`` compiles the *chunked-prefill* variant: ``token`` is
    (N, C) and an extra ``n_tok`` ((N,) i32) says how many of each lane's
    C tokens are real this step (1 for steady-state decode lanes, up to C
    for prefilling lanes; padding tokens run at position −1 → writes
    dropped, rows discarded). The returned token is the model output of
    each lane's *last real* token, so a chunk step is token-for-token
    identical to feeding the same tokens over C single-token steps.
    Chunked prefill requires an attention-only stack (recurrent state
    advances strictly one token per step).

    ``paged=True`` also accepts optional ``copy_dst``/``copy_src`` ((K,)
    i32, static K): physical page-row copies applied after ``page_reset``
    and *before* the model's KV writes — the engine's copy-on-write remap
    for prefix-shared blocks (padding rows use ``dst = n_rows`` ⇒ dropped;
    see :func:`repro.serve.cache.copy_pages`).

    ``return_logits=True`` compiles the *sampling* variant, returning
    ``(next_token, out_logits, new_cache)`` with ``out_logits`` the (N, V)
    pre-softmax logits each lane's token was argmaxed from. The token
    path is byte-identical to the default variant — greedy lanes read
    ``next_token`` exactly as before, sampling lanes re-decide host-side
    from the logits (:mod:`repro.serve.sampling`); the engine only ever
    compiles this variant when a sampling request is actually in flight.
    """
    # deferred: repro.serve.engine imports this module (serve sits above
    # train in the layering), so the helper import can't run at load time
    from repro.kernels import dispatch
    from repro.serve import cache as SC

    qa = QArith(policy)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")

    def serve_step(params, cache, token, pos, active=None, reset=None,
                   mrope_positions=None, block_table=None, page_reset=None,
                   n_tok=None, copy_dst=None, copy_src=None):
        with dispatch.fused_decode(fused_decode):
            return _body(params, cache, token, pos, active, reset,
                         mrope_positions, block_table, page_reset, n_tok,
                         copy_dst, copy_src)

    def _body(params, cache, token, pos, active, reset, mrope_positions,
              block_table, page_reset, n_tok, copy_dst, copy_src):
        wc = compute_params(params, policy)
        if reset is not None:
            cache = SC.reset_slots(cache, reset)
        if paged and page_reset is not None:
            cache = SC.reset_pages(cache, page_reset)
        if paged and copy_dst is not None:
            cache = SC.copy_pages(cache, copy_dst, copy_src)
        if chunk == 1:
            if active is not None:
                pos = jnp.where(active, pos, -1)  # parked ⇒ KV write dropped
            cache_pos = pos
            last = None
        else:
            # per-token positions for the chunk; tokens past a lane's
            # n_tok (and whole parked lanes) run at −1: KV writes
            # dropped, attention rows discarded below.
            offs = jnp.arange(chunk, dtype=jnp.int32)
            tpos = pos[:, None] + offs[None, :]
            valid = offs[None, :] < n_tok[:, None]
            if active is not None:
                valid &= active[:, None]
            cache_pos = jnp.where(valid, tpos, -1)
            last = jnp.clip(n_tok - 1, 0, chunk - 1)
        logits, new_cache = R.decode(qa, wc, cfg, token, cache, cache_pos,
                                     mrope_positions=mrope_positions,
                                     block_table=block_table)
        if last is None:
            out_logits = logits[:, -1, :]
        else:
            out_logits = jnp.take_along_axis(
                logits, last[:, None, None], axis=1)[:, 0, :]
        next_token = jnp.argmax(out_logits, axis=-1).astype(jnp.int32)
        if active is not None:
            new_cache = SC.keep_active(active, new_cache, cache)
            next_token = jnp.where(active, next_token, -1)
        if return_logits:
            return next_token[:, None], out_logits, new_cache
        return next_token[:, None], new_cache

    return serve_step
