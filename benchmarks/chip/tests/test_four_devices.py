"""The check over a cell's four chips, on four virtual CPU devices in a
child process (``four_devices.py``; the device count is fixed when JAX
starts): the reference split over four devices follows the one-device
reference to float32 round-off, each device holds a quarter of a leaf
that divides, and a toy FSDP cell on four devices is ``correct``."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip())
    done = subprocess.run([sys.executable, str(HERE / "four_devices.py")],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert done.returncode == 0, done.stderr[-6000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("reading", ["losses", "grad_norms",
                                     "change_norms"])
def test_reference_on_four_devices_follows_one(four, reading):
    one, split = (np.asarray(four[k][reading]) for k in ("one", "four"))
    assert one.shape == split.shape and one.size
    assert np.all(one > 0)
    np.testing.assert_allclose(split, one, rtol=1e-5)


def test_each_device_holds_a_quarter(four):
    for shape, held in four["shards"]:
        assert len(held) == 4
        # every leaf of the toy model has an axis that four divides: the
        # largest such is split, the others are whole
        axis = max((a for a, n in enumerate(shape) if n % 4 == 0),
                   key=lambda a: shape[a])
        want = [n // 4 if a == axis else n for a, n in enumerate(shape)]
        assert held == [want] * 4, (shape, held)


def test_layout_of_shapes_that_do_not_divide(four):
    assert four["layouts"] == {
        "(3, 5)": "PartitionSpec(None, None)",    # no axis: whole on each
        "(6, 8)": "PartitionSpec(None, 'd')",
        "(8, 8)": "PartitionSpec('d', None)",     # a tie: the first
        "(4,)": "PartitionSpec('d',)",
        "()": "PartitionSpec()"}


def test_fsdp4_toy_cell_is_correct(four):
    run = four["fsdp4"]
    assert run["device"]["count"] == 4
    assert run["correct"], run["checks"]
    assert run["attempted"] > 0
