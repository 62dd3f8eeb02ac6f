"""The check catches a broken timed path: each test drives a whole run
of a toy cell on the CPU (past the harness's look for a chip), with one
fault planted in the program underneath, and sees ``correct`` false.
The same runs unbroken are correct."""
import jax
import pytest

import toy


@pytest.fixture(scope="module")
def sound_train():
    return toy.measure(toy.train_cell())


def test_sound_train_is_correct(sound_train):
    assert sound_train["correct"], sound_train["checks"]


def test_train_step_returns_state_unchanged(monkeypatch):
    import repro.launch.train as LT
    build = LT.build_training

    def broken(*a, **kw):
        run = build(*a, **kw)
        step = jax.jit(lambda s, b, k: (s._replace(step=s.step + 1),
                                        run.step(s, b, k)[1]))
        return run._replace(step=step)

    monkeypatch.setattr(LT, "build_training", broken)
    out = toy.measure(toy.train_cell())
    assert not out["correct"]
    assert out["checks"]["grad_norm_gap"]["value"] > 0.9


def test_train_mean_over_half_the_batch(monkeypatch):
    import repro.train.step as TS
    xent = TS.softmax_xent

    def half(logits, labels, **kw):
        keep = labels.shape[-1] // 2
        return xent(logits[..., :keep, :], labels[..., :keep], **kw)

    monkeypatch.setattr(TS, "softmax_xent", half)
    out = toy.measure(toy.train_cell())
    assert not out["correct"]
