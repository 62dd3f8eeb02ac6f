"""Each metric's reader, on a record made from the recorded training
trace: the numbers follow from the counts, the peaks and the trace, and
a reader with nothing to read stays silent (None), never 0."""
from pathlib import Path

import pytest

from harness import counts, spec
from harness import trace as T
from harness.train import Record

DATA = Path(__file__).resolve().parent / "data"
BENCH = spec.load_benchmark()


@pytest.fixture(scope="module")
def train_record():
    cell = spec.load_cell("yi-9b.train.4k", BENCH)
    r = T.reduce(*T.events_of(str(DATA / "yi-9b.train.4k.xplane.pb.gz")))
    rec = Record("train", cell, window_s=r.window_s, steps=25,
                 tokens=25 * 4096, trace=r, device_kind="TPU v5 lite",
                 extra={"setup_s": 20.0, "param_leaves": 12})
    return rec


def test_fused_adamw_roofline(train_record):
    read = spec.metric_reader("fused_adamw_roofline")
    least = 25 * counts.adamw_bytes(train_record.cell.config) / 819e9
    assert read(train_record) == pytest.approx(100 * least / 0.908855,
                                               rel=1e-5)
    assert 50 < read(train_record) < 100


def test_mfu_train(train_record):
    read = spec.metric_reader("mfu.train")
    flops = 25 * counts.train_flops_per_step(train_record.cell.config, 1,
                                             4096)
    assert read(train_record) == pytest.approx(
        100 * flops / (train_record.window_s * 197e12))


def test_train_tokens_per_s(train_record):
    read = spec.metric_reader("train_tokens_per_s")
    assert read(train_record) == pytest.approx(25 * 4096
                                               / train_record.window_s)


def test_roofline_refuses_a_miscounted_kernel(train_record):
    import dataclasses
    rec = dataclasses.replace(train_record, steps=24)
    with pytest.raises(ValueError, match="kernel events"):
        spec.metric_reader("fused_adamw_roofline")(rec)


def test_roofline_silent_without_the_kernel(train_record):
    empty = T.Reduction(window_s=1.0, busy_s=1.0, n_devices=1, op_s={},
                        exposed_collective_s=0.0, collective_s=0.0,
                        idle_by_host={})
    rec = Record("train", train_record.cell, 1.0, 1, 4096, trace=empty,
                 device_kind="TPU v5 lite")
    assert spec.metric_reader("fused_adamw_roofline")(rec) is None


def test_unknown_device_kind_is_an_error(train_record):
    rec = Record("train", train_record.cell, 1.0, 1, 4096,
                 device_kind="cpu")
    with pytest.raises(KeyError):
        spec.metric_reader("mfu.train")(rec)
