"""Real multi-host fault tolerance: N ``jax.distributed`` processes
(CPU + gloo) spawned by ``tools/dist_launch.py``.

Covers the production failure modes end-to-end:

* 2-process training with cross-host gradient collectives, process-0
  checkpoint commits, and a mesh spanning both hosts' devices;
* one simulated host death (SIGKILL) mid-run, then elastic resume of
  the surviving topology on a *shrunk* mesh, with bf16/Kahan state
  bit-preserved and stale compressed-wire residuals dropped;
* SIGTERM preemption: both processes agree on the stop step, force a
  collective snapshot, drain the async writer, and exit 0.

Gated like the ``-m dist`` tier: run with ``-m multihost`` (CI has a
dedicated job); skipped otherwise — each case spawns real processes
that compile the model, too heavy for tier-1.
"""
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))
import dist_launch as DL  # noqa: E402

pytestmark = [
    pytest.mark.multihost,
    pytest.mark.skipif(
        not os.environ.get("REPRO_MULTIHOST_TESTS"),
        reason="multi-process jax.distributed tests — run with -m multihost"),
]

TRAIN = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2.5-3b",
         "--reduced", "--policy", "bf16_sr_kahan", "--batch", "4",
         "--seq", "16", "--lr", "1e-3"]


def _single_proc_env():
    env = dict(os.environ)
    for k in ("REPRO_COORDINATOR", "REPRO_NUM_PROCESSES",
              "REPRO_PROCESS_ID", "XLA_FLAGS"):
        env.pop(k, None)
    env["JAX_NUM_CPU_DEVICES"] = "1"
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def _logs(log_dir, n=2):
    out = []
    for i in range(n):
        p = Path(log_dir) / f"rank{i}.log"
        out.append(p.read_text() if p.exists() else "<missing>")
    return out


def test_two_process_gloo_collectives(tmp_path):
    """Smallest possible cluster: 2 processes, 1 CPU device each, one
    jitted cross-host reduction over a 2-device mesh."""
    script = (
        "import repro.dist.multihost as MH\n"
        "assert MH.initialize(), 'REPRO_* env missing'\n"
        "import jax, jax.numpy as jnp\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "from repro.launch.mesh import make_local_mesh\n"
        "assert jax.device_count() == 2 and jax.local_device_count() == 1\n"
        "mesh = make_local_mesh(2)\n"
        "x = jax.device_put(jnp.arange(4.0), NamedSharding(mesh, P('data')))\n"
        "print('sum', float(jax.jit(lambda a: a.sum())(x)))\n"
        "MH.barrier('done')\n"
        "print('rank', MH.process_index(), 'of', MH.process_count())\n")
    procs = DL.launch([sys.executable, "-c", script], 2, log_dir=tmp_path)
    codes = DL.wait(procs, timeout=240)
    logs = _logs(tmp_path)
    assert codes == [0, 0], logs
    for i, text in enumerate(logs):
        assert "sum 6.0" in text, text
        assert f"rank {i} of 2" in text, text


def test_two_process_training_commits_from_process_zero(tmp_path):
    """2-host data-parallel training run: both ranks step in lockstep,
    only process 0 writes checkpoints and logs, LATEST lands at the
    final step."""
    ck = tmp_path / "ck"
    cmd = TRAIN + ["--steps", "6", "--ckpt-dir", str(ck), "--ckpt-every", "3"]
    procs = DL.launch(cmd, 2, log_dir=tmp_path / "logs")
    codes = DL.wait(procs, timeout=900)
    r0, r1 = _logs(tmp_path / "logs")
    assert codes == [0, 0], (r0[-2000:], r1[-2000:])

    from repro.train import checkpoint as C
    assert C.latest_step(ck) == 6
    man = C.manifest(ck)
    assert man["step"] == 6
    assert "bfloat16" in man["dtypes"]        # pure-bf16 state on disk
    assert "[train] done at step 6" in r0
    # process-0 semantics: the non-primary rank is silent
    assert "[train] done" not in r1 and "[loop]" not in r1

    # resume under the same 2-process topology: the restore step is
    # agreed via a process-0 broadcast (only process 0 drains async
    # commits), so both ranks must restore the same step
    cmd2 = TRAIN + ["--steps", "9", "--ckpt-dir", str(ck),
                    "--ckpt-every", "3"]
    procs = DL.launch(cmd2, 2, log_dir=tmp_path / "logs2")
    codes = DL.wait(procs, timeout=900)
    r0, r1 = _logs(tmp_path / "logs2")
    assert codes == [0, 0], (r0[-2000:], r1[-2000:])
    assert "resumed from checkpoint at step 6" in r0
    assert "[train] done at step 9" in r0
    assert C.latest_step(ck) == 9


def test_host_death_then_elastic_resume_on_shrunk_mesh(tmp_path):
    """Kill one of two hosts mid-run (SIGKILL — no goodbye), then resume
    single-process on the shrunk mesh from the survivors' checkpoint.
    The bf16 params + Kahan compensation buffers restore bit-exact; the
    compressed-wire error-feedback residuals (shaped for 2 wire
    replicas) are detected as stale and re-zeroed for the 1-replica
    wire."""
    ck = tmp_path / "ck"
    cmd = TRAIN + ["--steps", "500", "--ckpt-dir", str(ck),
                   "--ckpt-every", "2", "--grad-wire", "compressed"]
    procs = DL.launch(cmd, 2, log_dir=tmp_path / "logs")

    from repro.train import checkpoint as C
    deadline = time.time() + 600
    latest = None
    while time.time() < deadline:
        latest = C.latest_step(ck, repair=False)
        if latest is not None and latest >= 4:
            break
        dead = [p.returncode for p in procs if p.poll() is not None]
        assert not dead, ("rank died before first checkpoint",
                          _logs(tmp_path / "logs"))
        time.sleep(0.5)
    assert latest is not None and latest >= 4, _logs(tmp_path / "logs")

    procs[1].kill()                  # host death: no drain, no barrier
    time.sleep(1.0)
    procs[0].kill()                  # survivor is wedged in a dead collective
    DL.wait(procs, timeout=30)

    latest = C.latest_step(ck)       # repairs LATEST if the kill dangled it
    assert latest is not None and latest >= 4

    # --- bit-preservation: rebuild the shrunk-mesh (1-device) state the
    # launcher would build, and restore through the elastic path
    import jax
    import jax.numpy as jnp
    from repro.core.policy import get_policy
    from repro.dist import transport as TR
    from repro.models import registry as R
    from repro.optim import adamw
    from repro.train.loop import _restore
    from repro.train.train_state import make_train_state

    policy = get_policy("bf16_sr_kahan")
    cfg = R.get_config("qwen2.5-3b").reduced()
    params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
    opt = adamw(policy, b2=0.997, weight_decay=0.01)
    transport = TR.make_transport(wire="compressed")     # 1-replica wire
    like = make_train_state(params, opt, transport=transport)

    msgs = []
    restored, at = _restore(C.CheckpointManager(ck), like, None, msgs.append)
    assert at == latest
    assert any("wire replica count changed" in m for m in msgs), msgs

    # every stored leaf (minus the skipped stale residuals) is bit-equal
    # to the npz bytes — Kahan/SR auxiliary state survives the crash
    raw = np.load(ck / f"step_{latest:09d}" / "arrays.npz")
    man = C.manifest(ck, step=latest)
    bare = restored._replace(wire_residuals=None)
    leaves = jax.tree_util.tree_leaves(bare)
    assert len(leaves) == man["n_leaves"] - len(
        jax.tree_util.tree_leaves(restored.wire_residuals))
    for i, leaf in enumerate(leaves):
        a = np.asarray(jax.device_get(leaf))
        if a.dtype == jnp.bfloat16:
            a = a.view(np.uint16)
        assert np.array_equal(a, raw[f"a{i}"]), f"leaf {i} not bit-equal"
    # the Kahan compensation buffers are live state, not zeros
    kahan = jax.tree_util.tree_leaves(restored.opt_state.kahan_c)
    assert kahan and any(
        bool(jnp.any(k != 0)) for k in kahan), "Kahan buffers all zero"

    # --- elastic re-join: single process, shrunk mesh, same entry point
    cmd2 = TRAIN + ["--steps", str(latest + 3), "--ckpt-dir", str(ck),
                    "--ckpt-every", "100", "--grad-wire", "compressed"]
    r = subprocess.run(cmd2, env=_single_proc_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert f"resumed from checkpoint at step {latest}" in r.stdout
    assert "wire replica count changed" in r.stdout
    assert f"[train] done at step {latest + 3}" in r.stdout


def test_sigterm_preempts_both_ranks_and_drains_async_saves(tmp_path):
    """Preemption: SIGTERM both ranks mid-run. The ranks agree on a stop
    step (the signal lands at different step boundaries), force one
    collective snapshot, drain the background writer, and exit 0 with a
    committed LATEST."""
    ck = tmp_path / "ck"
    cmd = TRAIN + ["--steps", "2000", "--ckpt-dir", str(ck),
                   "--ckpt-every", "1000"]
    procs = DL.launch(cmd, 2, log_dir=tmp_path / "logs")

    rank0 = tmp_path / "logs" / "rank0.log"
    deadline = time.time() + 600
    while time.time() < deadline:
        if rank0.exists() and "[loop] step " in rank0.read_text():
            break
        dead = [p.returncode for p in procs if p.poll() is not None]
        assert not dead, ("rank died before first step",
                          _logs(tmp_path / "logs"))
        time.sleep(0.5)
    time.sleep(1.0)                       # let a few more steps through
    DL.terminate(procs)                   # SIGTERM, the preemption signal
    codes = DL.wait(procs, timeout=300)
    r0, r1 = _logs(tmp_path / "logs")
    assert codes == [0, 0], (r0[-2000:], r1[-2000:])
    assert "preempted at step" in r0
    assert "checkpointed and exiting" in r0

    from repro.train import checkpoint as C
    latest = C.latest_step(ck)
    assert latest is not None and latest >= 1
    # the commit came from the forced preemption save, not the cadence
    # (every_steps=1000 and we stopped far earlier)
    assert latest < 1000
