"""Checkpoint manager: atomicity, async commits, keep-N GC, resume,
elastic reshard, crash-mid-commit recovery."""
import json
import os
import shutil
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_local_mesh
from repro.train import checkpoint as C


def _tree(seed=0):
    key = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(key, (16, 8), jnp.bfloat16),
            "opt": {"m": jax.random.normal(key, (16, 8), jnp.float32),
                    "step": jnp.int32(7)}}


class TestSaveRestore:
    def test_roundtrip_bitexact(self, tmp_path):
        t = _tree()
        C.save(tmp_path, 5, t)
        like = jax.tree_util.tree_map(jnp.zeros_like, t)
        got, step = C.restore(tmp_path, like)
        assert step == 5
        for a, b in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(got)):
            assert a.dtype == b.dtype
            assert bool(jnp.all(a == b))

    def test_latest_pointer(self, tmp_path):
        C.save(tmp_path, 1, _tree(1))
        C.save(tmp_path, 2, _tree(2))
        assert C.latest_step(tmp_path) == 2
        got, step = C.restore(tmp_path, _tree())
        assert step == 2

    def test_keep_n_gc(self, tmp_path):
        for s in range(6):
            C.save(tmp_path, s, _tree(s), keep_n=2)
        steps = sorted(p.name for p in tmp_path.glob("step_*"))
        assert len(steps) == 2
        assert steps[-1] == "step_000000005"

    def test_mixed_dtype_roundtrip_casts_to_like(self, tmp_path):
        """Restoring into a tree of different dtypes casts leaf-for-leaf to
        the dtype of ``like`` — the policy-elastic path (fp32 master ckpt
        resumed under a bf16 policy and vice versa), mixed trees included."""
        t = {"w": jnp.linspace(-2, 2, 32, dtype=jnp.float32).reshape(8, 4),
             "m": jnp.linspace(0, 1, 8, dtype=jnp.bfloat16),
             "step": jnp.int32(3)}
        C.save(tmp_path, 1, t)
        like = {"w": jnp.zeros((8, 4), jnp.bfloat16),     # f32 → bf16
                "m": jnp.zeros((8,), jnp.float32),        # bf16 → f32
                "step": jnp.int32(0)}                     # unchanged
        got, _ = C.restore(tmp_path, like)
        assert got["w"].dtype == jnp.bfloat16
        assert got["m"].dtype == jnp.float32
        assert got["step"].dtype == jnp.int32 and int(got["step"]) == 3
        assert bool(jnp.all(got["w"] == t["w"].astype(jnp.bfloat16)))
        # bf16 values are exactly representable in f32: lossless widen
        assert bool(jnp.all(got["m"] == t["m"].astype(jnp.float32)))

    def test_same_dtype_roundtrip_stays_bitexact(self, tmp_path):
        t = _tree()
        C.save(tmp_path, 2, t)
        got, _ = C.restore(tmp_path, jax.tree_util.tree_map(jnp.zeros_like, t))
        for a, b in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(got)):
            assert a.dtype == b.dtype and bool(jnp.all(a == b))

    def test_structure_mismatch_rejected(self, tmp_path):
        C.save(tmp_path, 1, _tree())
        with pytest.raises(ValueError):
            C.restore(tmp_path, {"w": jnp.zeros((16, 8), jnp.bfloat16)})

    def test_shape_mismatch_rejected(self, tmp_path):
        C.save(tmp_path, 1, _tree())
        bad = _tree()
        bad["w"] = jnp.zeros((4, 4), jnp.bfloat16)
        with pytest.raises(ValueError):
            C.restore(tmp_path, bad)


def _age(path, secs=2 * C.TMP_STALE_SECS):
    t = time.time() - secs
    os.utime(path, (t, t))


class TestAtomicity:
    def test_tmp_dirs_never_visible_as_checkpoints(self, tmp_path):
        C.save(tmp_path, 1, _tree())
        # simulate a writer that crashed long ago
        junk = tmp_path / "tmp.2.deadbeef"
        junk.mkdir()
        (junk / "arrays.npz").write_bytes(b"garbage")
        _age(junk)
        assert C.latest_step(tmp_path) == 1
        got, step = C.restore(tmp_path, _tree())
        assert step == 1
        # next save GCs the stale junk
        C.save(tmp_path, 3, _tree())
        assert not junk.exists()

    def test_gc_spares_recent_tmp_dirs(self, tmp_path):
        """Regression: _gc used to rm-tree every tmp.* unconditionally,
        racing any concurrent (async) writer. A *recent* tmp dir may be
        another writer's in-flight commit — only stale ones are reaped."""
        C.save(tmp_path, 1, _tree())
        fresh = tmp_path / "tmp.9.aaaa0000"
        fresh.mkdir()
        stale = tmp_path / "tmp.9.bbbb0000"
        stale.mkdir()
        _age(stale)
        C.save(tmp_path, 2, _tree())
        assert fresh.exists()          # could be an in-flight writer
        assert not stale.exists()      # provably a crashed one

    def test_gc_never_deletes_this_processes_inflight_tmp(self, tmp_path):
        """Even a stale-looking tmp dir is spared while a live writer in
        this process owns it (a commit can legitimately be slow)."""
        C.save(tmp_path, 1, _tree())
        mine = tmp_path / "tmp.7.cccc0000"
        mine.mkdir()
        _age(mine)
        C._IN_FLIGHT.add(str(mine))
        try:
            C.save(tmp_path, 2, _tree())
            assert mine.exists()
        finally:
            C._IN_FLIGHT.discard(str(mine))

    def test_corrupt_latest_pointer_falls_back_and_repairs(self, tmp_path):
        """Regression: a dangling LATEST (crash between the step-dir
        rename and the LATEST rename) used to make latest_step return
        None — has_checkpoint() reported no checkpoint despite valid
        step dirs on disk. Now: fall back to the newest valid step dir
        and repair the pointer."""
        C.save(tmp_path, 1, _tree())
        C.save(tmp_path, 2, _tree(2))
        (tmp_path / "LATEST").write_text("step_000009999")
        assert C.latest_step(tmp_path) == 2
        # pointer was repaired in passing
        assert (tmp_path / "LATEST").read_text().strip() == "step_000000002"
        got, step = C.restore(tmp_path, _tree())
        assert step == 2

    def test_missing_latest_pointer_falls_back(self, tmp_path):
        C.save(tmp_path, 3, _tree())
        (tmp_path / "LATEST").unlink()
        assert C.latest_step(tmp_path) == 3
        assert (tmp_path / "LATEST").exists()

    def test_crash_between_rmtree_and_rename_recovers(self, tmp_path):
        """Crash on an overwriting save after `rmtree(final)` but before
        `os.replace(tmp, final)`: LATEST names a dir that no longer has
        a manifest. Recovery falls back to the previous committed step."""
        C.save(tmp_path, 1, _tree())
        C.save(tmp_path, 2, _tree(2))
        assert C.latest_step(tmp_path) == 2
        shutil.rmtree(tmp_path / "step_000000002")   # LATEST now dangles
        assert C.latest_step(tmp_path) == 1
        got, step = C.restore(tmp_path, _tree())
        assert step == 1

    def test_no_valid_checkpoint_is_still_none(self, tmp_path):
        tmp_path.mkdir(exist_ok=True)
        (tmp_path / "LATEST").write_text("step_000000042")
        bad = tmp_path / "step_000000042"
        bad.mkdir()                                   # dir without manifest
        assert C.latest_step(tmp_path) is None


class TestElastic:
    def test_restore_with_different_sharding_target(self, tmp_path):
        """Arrays are stored unsharded → restoring onto any device layout
        (here: explicit single-device shardings) works — the re-shard-on-
        resume path used when the mesh changes between runs."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        t = _tree()
        C.save(tmp_path, 1, t)
        mesh = make_local_mesh(1)
        shardings = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), t)
        got, _ = C.restore(tmp_path, t, shardings=shardings)
        for a, b in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(got)):
            assert bool(jnp.all(a == b))


class TestManager:
    def test_cadence(self, tmp_path):
        mgr = C.CheckpointManager(tmp_path, every_steps=10, keep_n=2)
        saved = [s for s in range(35) if mgr.maybe_save(s, _tree(s))]
        assert saved == [10, 20, 30]

    def test_force(self, tmp_path):
        mgr = C.CheckpointManager(tmp_path, every_steps=1000)
        assert mgr.maybe_save(3, _tree(), force=True) is not None
        assert mgr.has_checkpoint()

    def test_restore_latest_explicit_step(self, tmp_path):
        """`step=` pins the checkpoint instead of whatever LATEST names —
        the multi-host restore path passes a cross-host agreed step."""
        mgr = C.CheckpointManager(tmp_path, every_steps=1000, keep_n=5)
        mgr.maybe_save(3, _tree(3), force=True)
        mgr.maybe_save(7, _tree(7), force=True)
        like = jax.tree_util.tree_map(jnp.zeros_like, _tree())
        got, step = mgr.restore_latest(like, step=3)
        assert step == 3
        ref = _tree(3)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            assert bool(jnp.all(a == b))
        _, newest = mgr.restore_latest(like)
        assert newest == 7


class TestAsync:
    def test_async_save_commits_off_thread_and_roundtrips(self, tmp_path):
        t = _tree()
        with C.CheckpointManager(tmp_path, every_steps=1,
                                 async_saves=True) as mgr:
            assert mgr.maybe_save(5, t) is not None
            mgr.drain()
            assert C.latest_step(tmp_path) == 5
            got, step = mgr.restore_latest(
                jax.tree_util.tree_map(jnp.zeros_like, t))
        for a, b in zip(jax.tree_util.tree_leaves(t),
                        jax.tree_util.tree_leaves(got)):
            assert a.dtype == b.dtype and bool(jnp.all(a == b))

    def test_commits_happen_in_submission_order(self, tmp_path, monkeypatch):
        """A step-N snapshot must never commit after a step-N+k one —
        LATEST would travel backwards. Slow the writer down per-commit
        and record the order commits actually land in."""
        committed = []
        real = C._commit

        def slow_commit(directory, snap, keep_n):
            time.sleep(0.05 if snap.step % 2 == 0 else 0.0)
            committed.append(snap.step)
            return real(directory, snap, keep_n)

        monkeypatch.setattr(C, "_commit", slow_commit)
        with C.CheckpointManager(tmp_path, every_steps=1, keep_n=10,
                                 async_saves=True, max_pending=2) as mgr:
            for s in range(1, 7):
                mgr.maybe_save(s, _tree(s))
            mgr.drain()
        assert committed == [1, 2, 3, 4, 5, 6]
        assert C.latest_step(tmp_path) == 6

    def test_snapshot_is_taken_at_submit_time(self, tmp_path):
        """The committed bytes are the state at maybe_save() time, even
        if the caller mutates its arrays before the background write."""
        arr = np.zeros(8, np.float32)
        with C.CheckpointManager(tmp_path, every_steps=1,
                                 async_saves=True) as mgr:
            mgr.maybe_save(1, {"w": jnp.asarray(arr)})
            arr += 1.0          # too late: snapshot already off-device
            mgr.drain()
        got, _ = C.restore(tmp_path, {"w": jnp.ones(8, np.float32)})
        assert bool(jnp.all(got["w"] == 0.0))

    def test_background_failure_surfaces_at_drain(self, tmp_path, monkeypatch):
        def boom(directory, snap, keep_n):
            raise OSError("disk full")

        monkeypatch.setattr(C, "_commit", boom)
        mgr = C.CheckpointManager(tmp_path, every_steps=1, async_saves=True)
        mgr.maybe_save(1, _tree())
        with pytest.raises(RuntimeError, match="async checkpoint"):
            mgr.drain()

    def test_has_checkpoint_waits_for_pending_commits(self, tmp_path,
                                                      monkeypatch):
        real = C._commit

        def slow(directory, snap, keep_n):
            time.sleep(0.1)
            return real(directory, snap, keep_n)

        monkeypatch.setattr(C, "_commit", slow)
        with C.CheckpointManager(tmp_path, every_steps=1,
                                 async_saves=True) as mgr:
            mgr.maybe_save(1, _tree())
            assert mgr.has_checkpoint()   # drains first — no race


# ---------------------------------------------------------------------------
# gradient-wire format drift (manifest `extra` stamp → residual zero-init)
# ---------------------------------------------------------------------------

class TestWireFormatDrift:
    """Residual buffers are shape-identical across wire formats, so a
    ``--grad-wire`` change between save and resume is invisible to the
    shape checks — it must be caught from the ``wire_format`` stamp the
    manager writes into the manifest ``extra`` dict, and the stale
    buffers (quantization error on the *old* grid) dropped unread."""

    def _state(self, res_fill=0.125):
        from repro.train.train_state import TrainState
        params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)}
        opt = {"m": jnp.full((3, 4), 2.0, jnp.float32)}
        res = {"w": jnp.full((1, 3, 4), res_fill, jnp.float32)}
        return TrainState(jnp.int32(5), params, opt, res)

    def _save(self, tmp_path, stamp):
        mgr = C.CheckpointManager(
            tmp_path, every_steps=1,
            extra=({"wire_format": stamp} if stamp else None))
        assert mgr.maybe_save(5, self._state(res_fill=0.125),
                              force=True) is not None

    def _restore(self, tmp_path, wire_format):
        from repro.train.loop import _restore
        msgs = []
        template = self._state(res_fill=0.0)     # fresh zero buffers
        restored, at = _restore(
            C.CheckpointManager(tmp_path, every_steps=1), template, None,
            msgs.append, wire_format=wire_format)
        assert at == 5
        return restored, msgs

    def test_manager_stamps_manifest_extra(self, tmp_path):
        self._save(tmp_path, "bf16")
        assert C.manifest(tmp_path)["extra"] == {"wire_format": "bf16"}

    def test_format_change_zero_inits_residuals(self, tmp_path):
        self._save(tmp_path, "bf16")
        restored, msgs = self._restore(tmp_path, "bf12")
        # params/opt restore bit-exact; the stale bf16-grid residuals
        # are dropped and the fresh zero buffers kept
        assert bool(jnp.all(restored.params["w"]
                            == self._state().params["w"]))
        assert bool(jnp.all(restored.opt_state["m"] == 2.0))
        assert not np.asarray(restored.wire_residuals["w"]).any()
        assert any("format changed" in m and "bf16 -> bf12" in m
                   for m in msgs), msgs

    def test_policy_change_is_format_drift_too(self, tmp_path):
        # the stamp includes the keep policy (CompressedWire.wire_format),
        # so a policy-only change also refuses the stale buffers
        self._save(tmp_path, "bf12+keep<2048|embed")
        restored, msgs = self._restore(tmp_path, "bf12")
        assert not np.asarray(restored.wire_residuals["w"]).any()
        assert any("format changed" in m for m in msgs), msgs

    def test_same_format_restores_residuals(self, tmp_path):
        self._save(tmp_path, "bf12")
        restored, msgs = self._restore(tmp_path, "bf12")
        assert bool(jnp.all(restored.wire_residuals["w"] == 0.125))
        assert msgs == []

    def test_unstamped_checkpoint_restores_residuals(self, tmp_path):
        # pre-stamp checkpoints: bf16 (== "compressed") was the only
        # format that ever wrote residuals — restore them as before
        self._save(tmp_path, None)
        restored, msgs = self._restore(tmp_path, "bf16")
        assert bool(jnp.all(restored.wire_residuals["w"] == 0.125))
        assert msgs == []

    def test_no_current_format_restores_residuals(self, tmp_path):
        # a stamped checkpoint resumed by a caller that does not declare
        # a wire format: nothing to compare against, keep the buffers
        self._save(tmp_path, "bf16")
        restored, msgs = self._restore(tmp_path, None)
        assert bool(jnp.all(restored.wire_residuals["w"] == 0.125))
        assert msgs == []
