"""The reduction from trace events to busy time, kernel time, exposed
collective time and idle gaps: on hand-made events, and on a small trace
recorded on a TPU v5e (two training steps of a reduced yi-9b cell and a
few serving steps), against numbers read off it by hand."""
from pathlib import Path

import pytest

from harness import trace as T
from harness.trace import Ev

DATA = Path(__file__).resolve().parent / "data"


def test_hand_made_events():
    dev = [Ev("fusion.1", 0, 10), Ev("all-gather.2", 5, 20),
           Ev("fused_adamw_kernel", 25, 30)]
    host = [Ev(T.WINDOW_SPAN, 0, 40), Ev("bench.train_step", 0, 35),
            Ev("bench.batches", 35, 40)]
    r = T.reduce([dev], host)
    assert r.window_s == pytest.approx(40e-9)
    assert r.busy_s == pytest.approx(25e-9)            # [0,20] + [25,30]
    assert r.collective_s == pytest.approx(15e-9)
    assert r.exposed_collective_s == pytest.approx(10e-9)  # [10,20]
    assert r.kernel_s("fused_adamw") == pytest.approx(5e-9)
    assert r.kernel_s(r"fusion\.\d") == pytest.approx(10e-9)
    assert r.kernel_s("no_such_kernel") is None
    assert r.idle_by_host == pytest.approx(
        {"bench.train_step": 5e-9, "bench.batches": 10e-9})
    b = r.breakdown()
    assert b["device_ops"][0] == ["all-gather.2", pytest.approx(15e-9)]


def test_window_clips_and_averages_devices():
    host = [Ev(T.WINDOW_SPAN, 10, 30)]
    a = [Ev("op", 0, 20)]             # half of it before the window
    b = [Ev("op", 10, 30)]
    r = T.reduce([a, b], host)
    assert r.busy_s == pytest.approx(15e-9)            # (10 + 20) / 2
    assert r.kernel_s("op") == pytest.approx(15e-9)


def test_nested_ops_count_self_time():
    # a loop op holding one body op; a third op overlaps the loop's end
    dev = [Ev("while.1", 0, 10), Ev("fusion.2", 2, 4), Ev("fusion.3", 6, 12)]
    host = [Ev(T.WINDOW_SPAN, 0, 20)]
    r = T.reduce([dev], host)
    assert r.op_s == pytest.approx(
        {"while.1": 8e-9, "fusion.2": 2e-9, "fusion.3": 6e-9})
    assert r.busy_s == pytest.approx(12e-9)


def test_union_and_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_recorded_training_trace():
    """Ten seconds of ``yi-9b.train.4k`` on a v5e (25 steps). Read off the
    trace by hand: the ``XLA Modules`` line holds 10.198249 s of
    ``jit_train_step`` and 0.000277 s of the batch program in a
    10.273737 s window; the 300 ``tpu_custom_call`` ops (12 leaves x 25
    steps, the fused AdamW) last 0.908855 s in all."""
    import importlib.util
    from harness.spec import BENCH_DIR
    r = T.reduce(*T.events_of(str(DATA / "yi-9b.train.4k.xplane.pb.gz")))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(10.273737, abs=1e-6)
    assert r.busy_s == pytest.approx(10.198249 + 0.000277, rel=2e-4)
    spec = importlib.util.spec_from_file_location(
        "roofline", BENCH_DIR / "metrics" / "fused_adamw_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert r.kernel_s(mod.KERNEL) == pytest.approx(0.908855, abs=1e-6)
    assert r.collective_s == 0.0
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
