"""Fault-tolerant training loop.

Wraps the jitted train step with the machinery a 1000-node run needs:

* resume-from-latest on startup (elastic: reshard onto the current mesh)
* periodic atomic checkpoints, serialized + committed on a background
  thread (``async_saves``) so the step never blocks on an npz write;
  every exit path — completion, preemption, crash — drains the writer
* checkpoint-on-SIGTERM preemption hook (snapshot, drain, exit)
* bounded retry around the step (transient-failure tolerance; a
  fault-injection hook exists for tests). A step that donates its state
  and fails after consuming it is not retried: the run raises and
  restarts from its last checkpoint
* a loss-spike / divergence monitor (``spike_factor``) that rolls the
  run back to the last good checkpoint and widens the checkpoint
  cadence, instead of checkpointing over it with poisoned state
* straggler telemetry: per-step wall-time EWMA; steps slower than
  ``straggler_factor ×`` EWMA are counted and surfaced — the deployment
  runbook (README) reacts by excluding the slow host and resuming from
  the latest checkpoint on a shrunk mesh (the elastic restore path).
* checkpoint cadence tightens automatically while stragglers persist
  (single-process only: cadence must stay identical across hosts, and
  straggler counts are local observations).

Under ``jax.distributed`` (one process per host — see
:mod:`repro.dist.multihost`) the loop is collective: every process runs
it in lockstep, checkpoint snapshots gather across hosts, only process
0 writes, and all processes barrier around restore. The restore step —
at startup and on spike rollback — is agreed via a process-0 broadcast
(only process 0 has queued async commits that can move LATEST), and
the SIGTERM agreement is polled every ``preempt_poll_every`` steps
rather than per step.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import signal
import time
from typing import Any, Callable, Iterator, Union

import jax
from jax.profiler import TraceAnnotation

from repro.profile import scopes
from repro.train.checkpoint import CheckpointManager, latest_step, manifest
from repro.train.train_state import TrainState

__all__ = ["TrainLoopConfig", "run_training"]

# The pre-wire_residuals TrainState layout (3 fields), for recognizing
# checkpoints written before the field existed — see _restore.
_LEGACY_STATE = collections.namedtuple(
    "TrainState", ["step", "params", "opt_state"])

# ``batches``: either a plain iterator, or a callable mapping the start
# step to an iterator — the loop calls it after restore (and again after
# a rollback) so the stream begins at the batch the run actually needs.
Batches = Union[Iterator, Callable[[int], Iterator]]


def _barrier(tag: str) -> None:
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(tag)


def _agree_preempted(local: bool, multiproc: bool) -> bool:
    """Preemption decision, agreed across hosts. SIGTERM lands at
    slightly different step boundaries on different processes; the
    checkpoint snapshot is collective, so every process must stop (and
    force-save) at the *same* step — any host's signal stops them all."""
    if not multiproc:
        return local
    import numpy as np
    from jax.experimental import multihost_utils
    flags = multihost_utils.process_allgather(np.int32(local))
    return bool(np.max(flags) > 0)


def _agreed_restore_step(mgr: CheckpointManager,
                         multiproc: bool) -> int | None:
    """The step every process will restore, agreed across hosts
    (None when no checkpoint exists).

    Process 0 is the only process that ever has queued async commits:
    its ``drain()`` can move LATEST forward while a peer's (no-op)
    drain leaves the peer still reading the pre-commit pointer — each
    host picking its own ``latest_step`` can therefore pick *different*
    steps and silently diverge after restore. So only process 0 reads
    LATEST, after draining, and broadcasts the result: the collective
    completing on any host implies process 0's commits already hit the
    (shared) filesystem, and every host restores the same step."""
    mgr.drain()              # flush queued commits (no-op off-primary)
    if not multiproc:
        return latest_step(mgr.directory)
    import numpy as np
    from jax.experimental import multihost_utils
    local = -1
    if jax.process_index() == 0:
        found = latest_step(mgr.directory)
        local = -1 if found is None else found
    step = int(multihost_utils.broadcast_one_to_all(np.int64(local)))
    return None if step < 0 else step


def _restore(mgr: CheckpointManager, state: TrainState, state_shardings, log,
             *, step: int | None = None, wire_format: str | None = None):
    """Elastic restore, tolerant of gradient-wire residual layout drift
    in every direction a restart can change the wire:

    * checkpoint without residuals → compressed-wire run: restore
      everything else, zero-init the error-feedback buffers;
    * checkpoint with residuals for a *different* wire replica count
      (pod-axis resize): drop the stale buffers unread (``skip`` — they
      can be parameter-sized), zero-init at the current shape;
    * checkpoint with residuals → stateless-transport run (wire
      downgraded to fp32): drop the stored buffers unread;
    * checkpoint with shape-compatible residuals but a *different wire
      format* (``--grad-wire=bf16`` checkpoint resumed under ``bf12``,
      or a changed keep policy): residual shapes are format-independent,
      so the mismatch is invisible to shape checks — it is detected from
      the ``wire_format`` the manager stamps into the manifest, and the
      stale buffers (quantization error on the *old* grid, wrong to
      re-inject on the new one) are dropped unread and zero-inited.
      Checkpoints predating the stamp restore as before (bf16 ↔
      ``compressed`` is the only format that ever wrote them).

    Zero-init is cheap because the buffers hold only last-step
    quantization error — one uncompensated step. Every fallback is gated
    on the stored treedef actually matching the hypothesized layout, so
    an unrelated leaf-count delta — e.g. a Kahan ↔ non-Kahan policy
    change, which also shifts the count by one param-shaped tree —
    falls through to ``checkpoint.restore``'s own clear validation
    error instead of being misdiagnosed as residual drift.

    ``step`` pins the checkpoint to restore (multi-host passes the
    cross-host agreed step — see :func:`_agreed_restore_step`); None
    restores whatever LATEST names.
    """
    residuals = getattr(state, "wire_residuals", None)
    n_state = len(jax.tree_util.tree_leaves(state))
    n_params = len(jax.tree_util.tree_leaves(state.params))
    man = manifest(mgr.directory, step=step)
    n_ckpt = man["n_leaves"]
    none_like = lambda tree: jax.tree_util.tree_map(lambda _: None, tree)  # noqa: E731
    stored_as = lambda tree: man.get("treedef") == str(  # noqa: E731
        jax.tree_util.tree_structure(tree))
    if residuals is not None:
        n_bare = n_state - n_params            # residuals mirror params
        bare = state._replace(wire_residuals=None)
        # a checkpoint from before TrainState.wire_residuals existed was
        # a 3-field namedtuple of the same name — build that treedef
        # structurally (renders identically) rather than via repr surgery
        legacy = _LEGACY_STATE(state.step, state.params, state.opt_state)
        accepted = {str(jax.tree_util.tree_structure(t))
                    for t in (bare, legacy)}
        if n_ckpt == n_bare and man.get("treedef") in accepted:
            bare_sh = (state_shardings._replace(wire_residuals=None)
                       if state_shardings is not None else None)
            restored, at = mgr.restore_latest(bare, shardings=bare_sh,
                                              step=step)
            log("[loop] checkpoint has no wire_residuals; zero-initialized "
                "error-feedback buffers")
            return restored._replace(wire_residuals=residuals), at
        stored = man["shapes"][n_bare:n_state]
        ours = [list(l.shape) for l in jax.tree_util.tree_leaves(residuals)]
        if n_ckpt == n_state and stored != ours and stored_as(state):
            sh = (state_shardings._replace(wire_residuals=none_like(residuals))
                  if state_shardings is not None else None)
            restored, at = mgr.restore_latest(
                state, shardings=sh, skip=range(n_bare, n_state), step=step)
            log("[loop] wire replica count changed since checkpoint; "
                "zero-initialized error-feedback buffers")
            return restored._replace(wire_residuals=residuals), at
        stored_fmt = (man.get("extra") or {}).get("wire_format")
        if (n_ckpt == n_state and stored == ours and stored_as(state)
                and None not in (stored_fmt, wire_format)
                and stored_fmt != wire_format):
            sh = (state_shardings._replace(wire_residuals=none_like(residuals))
                  if state_shardings is not None else None)
            restored, at = mgr.restore_latest(
                state, shardings=sh, skip=range(n_bare, n_state), step=step)
            log(f"[loop] gradient-wire format changed since checkpoint "
                f"({stored_fmt} -> {wire_format}); zero-initialized "
                f"error-feedback buffers")
            return restored._replace(wire_residuals=residuals), at
    elif n_ckpt == n_state + n_params:
        # checkpoint may carry residuals this (stateless) transport has
        # no use for: params stand in as structure-matching placeholders,
        # the stored buffers are skipped unread
        like = state._replace(wire_residuals=state.params)
        if stored_as(like):
            sh = (state_shardings._replace(
                      wire_residuals=none_like(state.params))
                  if state_shardings is not None else None)
            restored, at = mgr.restore_latest(
                like, shardings=sh, skip=range(n_state, n_ckpt), step=step)
            log("[loop] dropping checkpointed wire_residuals (stateless "
                "gradient transport)")
            return restored._replace(wire_residuals=None), at
    return mgr.restore_latest(state, shardings=state_shardings, step=step)


def _consumed(state: TrainState) -> bool:
    """Whether a donating step has already deleted ``state``'s buffers."""
    return any(isinstance(x, jax.Array) and x.is_deleted()
               for x in jax.tree_util.tree_leaves(state))


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int
    ckpt_dir: str | None = None
    ckpt_every: int = 100
    keep_n: int = 3
    max_retries_per_step: int = 2
    straggler_factor: float = 3.0
    log_every: int = 10
    seed: int = 0
    # Most-recent metrics rows kept in host memory (the returned
    # ``history``). Million-step runs would otherwise grow one dict per
    # step unboundedly; None keeps everything.
    history_cap: int | None = 10_000
    # Serialize + commit checkpoints on a background thread; the step
    # only pays the off-device snapshot. Bounded by max_pending_saves
    # (submit blocks once that many snapshots are queued).
    async_saves: bool = True
    max_pending_saves: int = 2
    # Loss-spike / divergence monitor: after ``spike_patience``
    # consecutive steps with non-finite loss or loss >
    # ``spike_factor × EWMA``, roll back to the last good checkpoint and
    # multiply the checkpoint cadence by ``rollback_widen`` (more steps
    # of evidence before the next checkpoint can trust the post-spike
    # trajectory). None disables. Requires ckpt_dir.
    spike_factor: float | None = None
    spike_patience: int = 2
    max_rollbacks: int = 2
    rollback_widen: int = 2
    # Multi-host only: the SIGTERM agreement is a cross-host allgather,
    # so it is polled every this many steps instead of every step (a
    # per-step collective would negate the batched-metrics win). A
    # host's signal is therefore acted on within preempt_poll_every
    # steps — keep it small relative to the preemption grace period.
    # Single-process runs still react on the very next step boundary.
    preempt_poll_every: int = 10
    # Identity of the gradient-wire numerics (CompressedWire.wire_format,
    # e.g. "bf16" or "bf12+keep<2048|embed,norm,bias,scale"). Stamped
    # into checkpoint manifests and compared on restore: a resume under a
    # different format zero-inits the error-feedback residuals instead of
    # re-injecting quantization error measured on the old grid. None
    # (stateless transports) disables both the stamp and the check.
    wire_format: str | None = None


def run_training(state: TrainState, train_step: Callable, batches: Batches,
                 cfg: TrainLoopConfig, *, log: Callable[[str], None] = print,
                 fault_hook: Callable[[int], None] | None = None,
                 state_shardings=None) -> tuple[TrainState, dict]:
    """Run to ``total_steps`` with checkpoint/restart + retry.

    ``batches`` may be a callable ``start_step -> iterator`` — the loop
    invokes it *after* resume (and after a rollback), so a resumed run
    continues the stream at the restored step instead of replaying the
    first ``step0`` batches. A plain iterator is also accepted; the
    caller is then responsible for advancing it past already-trained
    steps (the spike monitor additionally requires the callable form —
    a rollback must rewind the stream).

    The stream is pulled exactly once per step, *before* the retry
    loop: a retried step replays the same batch object (retries target
    transient device/runtime faults, not data poisoning — a poisoned
    batch that deterministically faults will exhaust the retries and
    checkpoint-and-raise). ``fault_hook(step)`` (tests) may raise to
    simulate failures. The returned ``history`` keeps the most recent
    ``cfg.history_cap`` metric rows; rows are materialized from device
    arrays in batches at ``log_every`` cadence (and at exit), not per
    step — per-step ``device_get`` of every metric serializes dispatch.
    """
    mgr = CheckpointManager(cfg.ckpt_dir, every_steps=cfg.ckpt_every,
                            keep_n=cfg.keep_n,
                            async_saves=cfg.async_saves,
                            max_pending=cfg.max_pending_saves,
                            extra=({"wire_format": cfg.wire_format}
                                   if cfg.wire_format else None),
                            ) if cfg.ckpt_dir else None
    batches_fn = batches if callable(batches) else None
    if cfg.spike_factor is not None:
        if mgr is None:
            raise ValueError("spike_factor requires ckpt_dir "
                             "(rollback needs a checkpoint to return to)")
        if batches_fn is None:
            raise ValueError("spike_factor requires callable batches "
                             "(a rollback must rewind the data stream)")
    multiproc = jax.process_count() > 1
    if multiproc:
        # every process must agree on whether a checkpoint exists before
        # any of them decides to restore (primary may still be
        # committing from a previous incarnation on a shared FS)
        _barrier("repro:loop:start")
    if mgr:
        at_step = _agreed_restore_step(mgr, multiproc)
        if at_step is not None:
            state, at = _restore(mgr, state, state_shardings, log,
                                 step=at_step, wire_format=cfg.wire_format)
            log(f"[loop] resumed from checkpoint at step {at}")
            if multiproc:
                _barrier("repro:loop:restored")

    stop = {"preempted": False}

    def _sigterm(sig, frame):
        stop["preempted"] = True
    old = None
    try:
        old = signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:
        pass  # not on main thread (tests)

    ewma = None
    stragglers = 0
    metrics_hist: list[dict] = []
    pending: list[dict] = []    # device-array metric rows awaiting fetch
    suspect: list[dict] = []    # rows from steps under spike suspicion

    def _flush():
        # one host sync for a whole window of rows, instead of one
        # device_get per metric per step
        if pending:
            with TraceAnnotation(scopes.SPAN_FLUSH):
                fetched = jax.device_get(pending)
            del pending[:]
            metrics_hist.extend(
                {k: float(v) for k, v in row.items()} for row in fetched)
        if cfg.history_cap is not None and len(metrics_hist) > cfg.history_cap:
            del metrics_hist[:len(metrics_hist) - cfg.history_cap]

    step0 = int(jax.device_get(state.step))
    stream = batches_fn(step0) if batches_fn else batches
    step = step0
    warm_until = step0 + 2
    loss_ewma = None
    spike_run = 0
    rollbacks = 0
    try:
        while step < cfg.total_steps:
            with TraceAnnotation(scopes.SPAN_BATCH):
                batch = next(stream)
            t0 = time.time()
            attempt = 0
            while True:
                try:
                    if fault_hook is not None:
                        fault_hook(step)
                    # commit to the new state only after the sync point: under
                    # async dispatch a device fault surfaces at block_until_ready,
                    # and retries (and the crash checkpoint) must see the last
                    # good state, not the failed step's poisoned buffers
                    with TraceAnnotation(scopes.SPAN_DISPATCH):
                        new_state, metrics = train_step(state, batch,
                                                        cfg.seed)
                    with TraceAnnotation(scopes.SPAN_SYNC):
                        jax.block_until_ready(metrics["loss"])
                    state = new_state
                    break
                except Exception as e:          # noqa: BLE001 — retry wall
                    attempt += 1
                    if _consumed(state):
                        # a step jitted with a donated state deletes its
                        # input once it runs: there is nothing left to
                        # retry from or to checkpoint
                        log(f"[loop] step {step} failed after consuming "
                            f"its donated state; raising for restart from "
                            f"the last committed checkpoint: {e}")
                        raise
                    if attempt > cfg.max_retries_per_step:
                        if mgr and not multiproc:
                            mgr.maybe_save(step, state, force=True)
                            log(f"[loop] step {step} failed {attempt}×; "
                                f"checkpointed for external restart: {e}")
                        elif multiproc:
                            # the crash save's snapshot is collective and
                            # the peers never reach this branch — saving
                            # here would wedge every host in a dead
                            # allgather until the backend times out.
                            # Just raise; the launcher restarts the run
                            # from the last committed checkpoint.
                            log(f"[loop] step {step} failed {attempt}×; "
                                f"raising for cluster restart from the "
                                f"last committed checkpoint: {e}")
                        raise
                    log(f"[loop] step {step} retry {attempt} after {type(e).__name__}")
            dt = time.time() - t0

            if cfg.spike_factor is not None:
                # the loss is already synced (block_until_ready above), so
                # this per-step scalar fetch is cheap; identical on every
                # process (the loss is a global collective mean), so the
                # rollback decision is made in lockstep across hosts
                loss_val = float(jax.device_get(metrics["loss"]))
                spiked = (not math.isfinite(loss_val)
                          or (loss_ewma is not None
                              and loss_val > cfg.spike_factor * loss_ewma))
                if spiked:
                    spike_run += 1
                else:
                    spike_run = 0
                    loss_ewma = (loss_val if loss_ewma is None
                                 else 0.9 * loss_ewma + 0.1 * loss_val)
                if spike_run >= cfg.spike_patience:
                    # all processes reach this point at the same step
                    # (the loss is a global mean); the restore step is
                    # still agreed via process 0 so a pending async
                    # commit can't land between two hosts' LATEST reads
                    at_step = _agreed_restore_step(mgr, multiproc)
                    if at_step is None:
                        raise RuntimeError(
                            f"loss diverged at step {step} "
                            f"(loss {loss_val:g}) with no checkpoint to "
                            f"roll back to")
                    if rollbacks >= cfg.max_rollbacks:
                        # deliberately NOT checkpointed: LATEST must keep
                        # naming the last good state, not the diverged one
                        raise RuntimeError(
                            f"loss diverged at step {step} after "
                            f"{rollbacks} rollbacks; giving up")
                    state, at = _restore(mgr, state, state_shardings, log,
                                         step=at_step,
                                         wire_format=cfg.wire_format)
                    if multiproc:
                        _barrier("repro:loop:rolled-back")
                    rollbacks += 1
                    mgr.every_steps = cfg.ckpt_every * (
                        cfg.rollback_widen ** rollbacks)
                    log(f"[loop] loss spike at step {step} "
                        f"(loss {loss_val:.4g}, ewma "
                        f"{loss_ewma if loss_ewma is None else round(loss_ewma, 4)}); "
                        f"rolled back to step {at}; "
                        f"ckpt_every -> {mgr.every_steps}")
                    _flush()
                    suspect.clear()   # rows from the discarded trajectory
                    step = at
                    warm_until = at + 2
                    loss_ewma, spike_run = None, 0
                    stream = batches_fn(at)
                    continue    # spiked state is never checkpointed/logged

            # the first steps carry jit-compile time — keep them out of the
            # EWMA or a 20 s compile masks every real straggler for hundreds
            # of steps
            dt_for_stats = None if step < warm_until else dt
            straggling = (ewma is not None and dt_for_stats is not None
                          and dt > cfg.straggler_factor * ewma)
            if dt_for_stats is not None and not straggling:
                ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
            if straggling:
                stragglers += 1
                log(f"[loop] straggler: step {step} took {dt:.2f}s (ewma {ewma:.2f}s)")
            if mgr and spike_run == 0:
                # a step under spike suspicion (spiked, but patience not
                # yet exhausted) is never committed — the rollback target
                # must predate the first suspicious update
                base = cfg.ckpt_every * (cfg.rollback_widen ** rollbacks)
                if not multiproc:
                    # cadence adaptation keys off *local* straggler
                    # counts — under multi-host it must stay identical
                    # across processes (snapshots are collective)
                    mgr.every_steps = max(
                        base // (2 if stragglers > 3 else 1), 1)
                with TraceAnnotation(scopes.SPAN_CHECKPOINT):
                    mgr.maybe_save(step + 1, state)
            if spike_run > 0:
                # quarantine: if the run rolls back, the trajectory this
                # row measured is discarded — it must not reach history
                suspect.append(metrics)
            else:
                if suspect:
                    # suspicion cleared without a rollback: those steps'
                    # updates were kept, so their rows are real history
                    pending.extend(suspect)
                    suspect.clear()
                pending.append(metrics)
            if step % cfg.log_every == 0:
                _flush()
                loss = metrics_hist[-1]["loss"] if metrics_hist else float("nan")
                log(f"[loop] step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)")
            # the cross-host agreement is a collective, so under
            # multi-host it runs on a fixed step schedule (every process
            # must enter it at the same steps) instead of every step;
            # single-process keeps per-step responsiveness for free
            poll = (not multiproc
                    or step % max(cfg.preempt_poll_every, 1) == 0)
            if poll and _agree_preempted(stop["preempted"], multiproc):
                if mgr:
                    with TraceAnnotation(scopes.SPAN_CHECKPOINT):
                        mgr.maybe_save(step + 1, state, force=True)
                log(f"[loop] preempted at step {step}; checkpointed and exiting")
                break
            step += 1
    except BaseException:
        if mgr:
            try:
                mgr.drain()     # the crash checkpoint must hit disk
            except Exception as e2:  # noqa: BLE001 — original error wins
                log(f"[loop] checkpoint drain failed during unwind: {e2}")
        raise
    finally:
        if old is not None:
            signal.signal(signal.SIGTERM, old)
    if suspect:
        # the run ended while still under (unresolved) spike suspicion;
        # those steps' updates are in the returned state, so their rows
        # are part of the realized trajectory
        pending.extend(suspect)
        suspect.clear()
    _flush()
    if mgr:
        with TraceAnnotation(scopes.SPAN_CHECKPOINT):
            mgr.drain()         # preemption/final saves committed before return
    return state, {"history": metrics_hist, "stragglers": stragglers,
                   "preempted": stop["preempted"], "rollbacks": rollbacks}
