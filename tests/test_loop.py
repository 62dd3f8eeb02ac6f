"""Fault-tolerant loop: retry, resume-equality, preemption, stragglers,
batch-stream resume offsets, batched metrics fetch, spike rollback."""
import itertools
import os
import signal

import jax
import jax.numpy as jnp
import pytest

from repro.core import QArith, get_policy
from repro.data.synthetic import lm_batches
from repro.models import registry as R
from repro.optim import adamw, constant
from repro.train.loop import TrainLoopConfig, run_training
from repro.train.step import make_train_step
from repro.train.train_state import make_train_state

POLICY = get_policy("bf16_sr")
CFG = R.get_config("qwen2.5-3b").reduced()


def _setup():
    params = R.init(CFG, jax.random.PRNGKey(0), POLICY.param_dtype)
    opt = adamw(POLICY, b2=0.997)
    state = make_train_state(params, opt)
    step = jax.jit(make_train_step(CFG, POLICY, opt, constant(1e-3),
                                   attn_chunk=8))
    return state, step


def test_loss_decreases():
    state, step = _setup()
    batches = lm_batches(CFG.vocab, 8, 16, seed=3)
    state, info = run_training(state, step, batches,
                               TrainLoopConfig(total_steps=30, log_every=100),
                               log=lambda *_: None)
    first = sum(m["loss"] for m in info["history"][:5]) / 5
    last = sum(m["loss"] for m in info["history"][-5:]) / 5
    assert last < first, (first, last)


def test_retry_on_transient_failure():
    state, step = _setup()
    batches = lm_batches(CFG.vocab, 4, 16)
    boom = {"count": 0}

    def fault_hook(s):
        if s == 3 and boom["count"] < 2:
            boom["count"] += 1
            raise RuntimeError("injected transient failure")

    state, info = run_training(state, step, batches,
                               TrainLoopConfig(total_steps=6, log_every=100),
                               log=lambda *_: None, fault_hook=fault_hook)
    assert boom["count"] == 2                 # retried twice then passed
    assert int(jax.device_get(state.step)) == 6


def test_persistent_failure_checkpoints_and_raises(tmp_path):
    state, step = _setup()
    batches = lm_batches(CFG.vocab, 4, 16)

    def always_fail(s):
        if s == 2:
            raise RuntimeError("permanent")

    with pytest.raises(RuntimeError):
        run_training(state, step, batches,
                     TrainLoopConfig(total_steps=6, ckpt_dir=str(tmp_path),
                                     ckpt_every=100, max_retries_per_step=1),
                     log=lambda *_: None, fault_hook=always_fail)
    from repro.train import checkpoint as C
    assert C.latest_step(tmp_path) == 2       # crash checkpoint exists


def test_failure_after_donation_raises_without_retry(tmp_path):
    """A donating step that fails after it consumed its state leaves
    nothing to retry from or to checkpoint: the loop raises at once."""
    state, _ = _setup()
    state = jax.tree_util.tree_map(jnp.copy, state)   # one buffer per leaf
    opt = adamw(POLICY, b2=0.997)
    donating = jax.jit(make_train_step(CFG, POLICY, opt, constant(1e-3),
                                       attn_chunk=8), donate_argnums=(0,))
    calls = {"n": 0}

    def step(state, batch, seed):
        out = donating(state, batch, seed)
        if int(jax.device_get(out[0].step)) == 3:
            calls["n"] += 1
            raise RuntimeError("device fault after dispatch")
        return out

    logs = []
    with pytest.raises(RuntimeError, match="after dispatch"):
        run_training(state, step, lm_batches(CFG.vocab, 4, 16),
                     TrainLoopConfig(total_steps=6, ckpt_dir=str(tmp_path),
                                     ckpt_every=100, max_retries_per_step=2),
                     log=logs.append)
    assert calls["n"] == 1                    # not retried
    assert any("consuming its donated state" in m for m in logs), logs
    from repro.train import checkpoint as C
    assert C.latest_step(tmp_path) is None    # no crash save of dead buffers


def test_resume_is_exact(tmp_path):
    """10 straight steps ≡ 5 steps + checkpoint + resume + 5 steps,
    bit-for-bit (deterministic data + per-step keys)."""
    def batches():
        return lm_batches(CFG.vocab, 4, 16, seed=9)

    state, step = _setup()
    full, _ = run_training(state, step, batches(),
                           TrainLoopConfig(total_steps=10),
                           log=lambda *_: None)

    state2, _ = _setup()
    half, _ = run_training(state2, step, batches(),
                           TrainLoopConfig(total_steps=5,
                                           ckpt_dir=str(tmp_path),
                                           ckpt_every=5),
                           log=lambda *_: None)
    # fresh state; loop restores from step 5 and replays the same stream
    state3, _ = _setup()
    b = batches()
    for _ in range(5):                        # advance stream to step 5
        next(b)
    resumed, _ = run_training(state3, step, b,
                              TrainLoopConfig(total_steps=10,
                                              ckpt_dir=str(tmp_path),
                                              ckpt_every=1000),
                              log=lambda *_: None)
    for a, c in zip(jax.tree_util.tree_leaves(full.params),
                    jax.tree_util.tree_leaves(resumed.params)):
        assert bool(jnp.all(a == c))


def test_lm_batches_start_step_is_stream_suffix():
    """The stream is step-keyed: start_step=k yields exactly the suffix
    of the start_step=0 stream from batch k on."""
    from repro.data.synthetic import lm_batches
    full = lm_batches(CFG.vocab, 4, 16, seed=9)
    for _ in range(5):
        next(full)
    tail = lm_batches(CFG.vocab, 4, 16, seed=9, start_step=5)
    for _ in range(3):
        a, b = next(full), next(tail)
        assert bool(jnp.all(a["tokens"] == b["tokens"]))
        assert bool(jnp.all(a["labels"] == b["labels"]))


def test_resume_does_not_replay_batch_stream(tmp_path):
    """Regression (launcher resume bug): rebuilding the stream from
    scratch on resume re-trained the first step0 batches. With callable
    batches the loop requests the stream *at the restored step*, and the
    pre-/post-resume batches form one non-overlapping sequence."""
    starts = []
    consumed = []

    def factory(start_step):
        starts.append(start_step)

        def gen():
            b = lm_batches(CFG.vocab, 4, 16, seed=9, start_step=start_step)
            i = start_step
            while True:
                consumed.append(i)
                yield next(b)
                i += 1
        return gen()

    state, step = _setup()
    half, _ = run_training(state, step, factory,
                           TrainLoopConfig(total_steps=5,
                                           ckpt_dir=str(tmp_path),
                                           ckpt_every=5),
                           log=lambda *_: None)
    # fresh state: the loop restores step 5 and must ask for the stream
    # at step 5, not replay batches 0..4
    state2, _ = _setup()
    resumed, _ = run_training(state2, step, factory,
                              TrainLoopConfig(total_steps=10,
                                              ckpt_dir=str(tmp_path),
                                              ckpt_every=1000),
                              log=lambda *_: None)
    assert starts == [0, 5]
    assert consumed == list(range(10))        # one non-overlapping sequence

    # and the result equals an uninterrupted 10-step run, bit-for-bit
    state3, _ = _setup()
    full, _ = run_training(state3, step,
                           lm_batches(CFG.vocab, 4, 16, seed=9),
                           TrainLoopConfig(total_steps=10),
                           log=lambda *_: None)
    for a, c in zip(jax.tree_util.tree_leaves(full.params),
                    jax.tree_util.tree_leaves(resumed.params)):
        assert bool(jnp.all(a == c))


def test_metrics_fetched_in_batches_not_per_step(monkeypatch):
    """Regression: the loop used to float(device_get(v)) every metric
    every step, serializing dispatch. Metrics now stay on device and are
    materialized at log_every cadence / loop exit."""
    from repro.train import loop as LP
    from repro.train.train_state import TrainState

    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(LP.jax, "device_get", counting)

    def step_fn(state, batch, seed):
        m = {"loss": jnp.float32(1.0), "gnorm": jnp.float32(2.0),
             "lr": jnp.float32(3e-4), "scale": jnp.float32(1.0)}
        return state._replace(step=state.step + 1), m

    state = TrainState(jnp.int32(0), {"w": jnp.zeros(4)}, {}, None)
    _, info = LP.run_training(state, step_fn, itertools.repeat({}),
                              TrainLoopConfig(total_steps=40, log_every=10),
                              log=lambda *_: None)
    assert len(info["history"]) == 40
    assert all(isinstance(v, float) for v in info["history"][-1].values())
    # 40 steps × 4 metrics = 160 per-step fetches before the fix; now one
    # device_get per flush window (+ the step-0 read)
    assert calls["n"] <= 10, calls["n"]


def test_spike_rollback_restores_and_widens_cadence(tmp_path):
    from repro.train.train_state import TrainState

    rolled = {"done": False}
    starts = []

    def step_fn(state, batch, seed):
        s = int(state.step)
        loss = 1.0 + 0.001 * s
        if s in (7, 8) and not rolled["done"]:
            loss = 1e9                        # two-step divergence
        return (state._replace(step=state.step + 1),
                {"loss": jnp.float32(loss)})

    def factory(start_step):
        starts.append(start_step)
        if starts.count(start_step) > 1 or start_step > 0:
            rolled["done"] = True             # post-rollback stream
        return itertools.repeat({})

    logs = []
    state = TrainState(jnp.int32(0), {"w": jnp.zeros(4)}, {}, None)
    out, info = run_training(
        state, step_fn, factory,
        TrainLoopConfig(total_steps=12, ckpt_dir=str(tmp_path), ckpt_every=2,
                        spike_factor=4.0, spike_patience=2, log_every=100),
        log=logs.append)
    assert info["rollbacks"] == 1
    assert int(jax.device_get(out.step)) == 12
    assert starts[0] == 0 and len(starts) == 2 and 0 < starts[1] <= 8
    assert any("rolled back to step" in l for l in logs), logs
    assert any("ckpt_every -> 4" in l for l in logs), logs
    # the spiked state was never committed: every history row is sane
    assert all(m["loss"] < 10.0 for m in info["history"][-4:])


def test_spike_suspect_rows_never_reach_history_on_rollback(tmp_path):
    """Regression: a spiked step under patience appended its metric row
    to the pending buffer, so after the rollback discarded that
    trajectory the row (and the spiked loss) still surfaced in
    ``history``. Suspicious rows are now quarantined and dropped on
    rollback — history holds exactly the realized trajectory's rows."""
    import itertools as it

    from repro.train.train_state import TrainState

    rolled = {"done": False}

    def step_fn(state, batch, seed):
        s = int(state.step)
        loss = 1.0 + 0.001 * s
        if s in (7, 8) and not rolled["done"]:
            loss = 1e9                        # two-step divergence
        return (state._replace(step=state.step + 1),
                {"loss": jnp.float32(loss)})

    def factory(start_step):
        if start_step > 0:
            rolled["done"] = True             # post-rollback stream
        return it.repeat({})

    state = TrainState(jnp.int32(0), {"w": jnp.zeros(4)}, {}, None)
    out, info = run_training(
        state, step_fn, factory,
        TrainLoopConfig(total_steps=12, ckpt_dir=str(tmp_path), ckpt_every=2,
                        spike_factor=4.0, spike_patience=2, log_every=3),
        log=lambda *_: None)
    assert info["rollbacks"] == 1
    # no row from the discarded trajectory: step 7's 1e9 loss was
    # quarantined while under suspicion and dropped at the rollback
    assert all(m["loss"] < 1e6 for m in info["history"]), info["history"]
    # steps 0..6 ran once, steps 6..11 re-ran after the rollback
    assert len(info["history"]) == 13


def test_spike_under_patience_rows_merge_back_when_cleared(tmp_path):
    """A suspicious step that recovers (patience not exhausted) keeps
    its update, so its quarantined row merges back into history in
    order — including a run that *ends* while still under suspicion."""
    from repro.train.train_state import TrainState

    def step_fn(state, batch, seed):
        s = int(state.step)
        loss = 1e9 if s in (5, 9) else 1.0    # isolated one-step spikes
        return (state._replace(step=state.step + 1),
                {"loss": jnp.float32(loss)})

    state = TrainState(jnp.int32(0), {"w": jnp.zeros(4)}, {}, None)
    out, info = run_training(
        state, step_fn, lambda s: itertools.repeat({}),
        TrainLoopConfig(total_steps=10, ckpt_dir=str(tmp_path), ckpt_every=3,
                        spike_factor=4.0, spike_patience=2, log_every=100),
        log=lambda *_: None)
    assert info["rollbacks"] == 0
    assert len(info["history"]) == 10
    # in order: row 5 merged back when step 6 cleared suspicion; row 9
    # (run ended under suspicion, update kept) merged at exit
    assert info["history"][5]["loss"] >= 1e6
    assert info["history"][9]["loss"] >= 1e6
    assert all(info["history"][i]["loss"] < 1e6
               for i in range(10) if i not in (5, 9))


class _TwoProcessJax:
    """Stand-in for the ``jax`` module inside ``repro.train.loop`` that
    reports a 2-process cluster; everything else delegates to real jax.
    Collective helpers (`_barrier`/`_agree_preempted`/
    `_agreed_restore_step`) are stubbed separately by each test — the
    unit under test is the loop's multi-host *branching*, not gloo."""

    process_count = staticmethod(lambda: 2)

    def __getattr__(self, name):
        return getattr(jax, name)


def test_multiproc_retry_exhaustion_skips_collective_crash_save(
        tmp_path, monkeypatch):
    """Regression: the retry-exhaustion crash checkpoint calls
    ``maybe_save(force=True)``, whose snapshot is collective — but only
    the failing process reaches it, so under multi-host it wedged every
    peer in a dead allgather. Multi-host now just raises (the launcher
    restarts from the last committed checkpoint)."""
    from repro.train import checkpoint as C
    from repro.train import loop as LP
    from repro.train.train_state import TrainState

    monkeypatch.setattr(LP, "jax", _TwoProcessJax())
    monkeypatch.setattr(LP, "_barrier", lambda tag: None)
    monkeypatch.setattr(LP, "_agree_preempted", lambda local, mp: local)
    monkeypatch.setattr(LP, "_agreed_restore_step", lambda mgr, mp: None)

    def step_fn(state, batch, seed):
        return state._replace(step=state.step + 1), {"loss": jnp.float32(1.0)}

    def always_fail(s):
        if s == 2:
            raise RuntimeError("permanent")

    state = TrainState(jnp.int32(0), {"w": jnp.zeros(4)}, {}, None)
    with pytest.raises(RuntimeError, match="permanent"):
        LP.run_training(state, step_fn, itertools.repeat({}),
                        TrainLoopConfig(total_steps=6, ckpt_dir=str(tmp_path),
                                        ckpt_every=100, max_retries_per_step=1),
                        log=lambda *_: None, fault_hook=always_fail)
    # no crash checkpoint: the save's snapshot would never complete
    # (its allgather has no peers), so multi-host must not attempt it
    assert C.latest_step(tmp_path) is None


def test_preemption_agreement_poll_cadence(monkeypatch):
    """Under multi-host the SIGTERM agreement is a cross-host collective;
    it is polled every ``preempt_poll_every`` steps instead of per step
    (which would reintroduce a per-step host sync). Single-process keeps
    checking its local flag every step."""
    from repro.train import loop as LP
    from repro.train.train_state import TrainState

    calls = {"n": 0}

    def counting_agree(local, mp):
        calls["n"] += 1
        return local

    monkeypatch.setattr(LP, "_agree_preempted", counting_agree)

    def step_fn(state, batch, seed):
        return state._replace(step=state.step + 1), {"loss": jnp.float32(1.0)}

    def run():
        state = TrainState(jnp.int32(0), {"w": jnp.zeros(4)}, {}, None)
        LP.run_training(state, step_fn, itertools.repeat({}),
                        TrainLoopConfig(total_steps=40, log_every=100,
                                        preempt_poll_every=10),
                        log=lambda *_: None)

    run()                                     # single-process: every step
    assert calls["n"] == 40

    calls["n"] = 0
    monkeypatch.setattr(LP, "jax", _TwoProcessJax())
    monkeypatch.setattr(LP, "_barrier", lambda tag: None)
    run()                                     # multi-host: steps 0,10,20,30
    assert calls["n"] == 4


def test_agreed_restore_step_drains_pending_commits(tmp_path):
    """Single-process semantics of the agreed-restore-step helper: the
    async writer is drained before LATEST is read, so a just-submitted
    snapshot is always visible to the rollback/startup restore."""
    from repro.train import loop as LP
    from repro.train.checkpoint import CheckpointManager

    with CheckpointManager(tmp_path, async_saves=True) as mgr:
        assert LP._agreed_restore_step(mgr, False) is None
        mgr.maybe_save(3, {"w": jnp.arange(4.0)}, force=True)
        assert LP._agreed_restore_step(mgr, False) == 3


def test_spike_monitor_requires_rollback_target():
    state, step = _setup()
    with pytest.raises(ValueError, match="ckpt_dir"):
        run_training(state, step, lambda s: iter([]),
                     TrainLoopConfig(total_steps=1, spike_factor=3.0),
                     log=lambda *_: None)


def test_sigterm_preemption_checkpoints_with_async_saves(tmp_path):
    """Preemption under async checkpointing: the forced save is queued on
    the writer thread, and the loop drains before returning — LATEST is
    committed by the time run_training hands back control."""
    state, step = _setup()

    def fault_hook(s):
        if s == 3:
            os.kill(os.getpid(), signal.SIGTERM)

    state, info = run_training(
        state, step, lm_batches(CFG.vocab, 4, 16),
        TrainLoopConfig(total_steps=50, ckpt_dir=str(tmp_path),
                        ckpt_every=1000, async_saves=True),
        log=lambda *_: None, fault_hook=fault_hook)
    assert info["preempted"]
    from repro.train import checkpoint as C
    assert C.latest_step(tmp_path) == 4       # step 3 ran, then exit


def test_straggler_detection():
    import time as _time
    state, step = _setup()
    batches = lm_batches(CFG.vocab, 4, 16)
    slow = {"done": False}

    def fault_hook(s):
        if s == 12 and not slow["done"]:
            slow["done"] = True
            _time.sleep(6.0)                  # one artificially slow step
            # (6 s ≫ 3× the EWMA even on a contended CPU)

    state, info = run_training(state, step, batches,
                               TrainLoopConfig(total_steps=15, log_every=100),
                               log=lambda *_: None, fault_hook=fault_hook)
    assert info["stragglers"] >= 1
