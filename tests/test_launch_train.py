"""The launcher's training setup (``repro.launch.train.build_training``)."""
import jax
import numpy as np
import pytest

from repro.core import get_policy
from repro.data.synthetic import lm_batches
from repro.launch.train import build_training
from repro.models import registry as R
from repro.optim import adamw
from repro.train.loop import TrainLoopConfig, run_training
from repro.train.train_state import make_train_state

CFG = R.get_config("qwen2.5-3b").reduced()


@pytest.mark.parametrize("fused", [False, True])
def test_state_matches_eager_build(fused):
    """The jitted build makes the state the eager one would: same tree
    and dtypes, and initial weights within one bf16 ulp (the compiled
    init may skip an intermediate rounding the eager one makes)."""
    policy = get_policy("bf16_sr_kahan")
    run = build_training(CFG, policy, seed=3, lr=1e-3, steps=4, seq=16,
                         fused_update=fused)
    params = R.init(CFG, jax.random.PRNGKey(3), policy.param_dtype)
    eager = make_train_state(params, adamw(policy, b2=0.997),
                             transport=run.transport)
    got, want = (jax.tree_util.tree_flatten(s) for s in (run.state, eager))
    assert got[1] == want[1]
    for a, b in zip(got[0], want[0]):
        assert a.dtype == b.dtype and a.shape == b.shape
    for a, b in zip(*(jax.tree_util.tree_leaves(s.params)
                      for s in (run.state, eager))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.abs(b))
    assert run.shardings is None


def test_step_donates_state_and_trains():
    policy = get_policy("bf16_sr_kahan")
    run = build_training(CFG, policy, seed=0, lr=3e-3, steps=8, seq=16)
    batch = next(lm_batches(CFG.vocab, 4, 16, seed=1))
    with run.scope():
        state, _ = run.step(run.state, batch, 0)
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(run.state))
    assert int(jax.device_get(state.step)) == 1

    run = build_training(CFG, policy, seed=0, lr=3e-3, steps=8, seq=16)
    with run.scope():
        state, info = run_training(
            run.state, run.step,
            lambda start: lm_batches(CFG.vocab, 4, 16, seed=1,
                                     start_step=start),
            TrainLoopConfig(total_steps=8, log_every=100),
            log=lambda *_: None, state_shardings=run.shardings)
    losses = [row["loss"] for row in info["history"]]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0], losses
