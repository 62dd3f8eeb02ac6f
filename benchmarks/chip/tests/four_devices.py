"""The check over four devices, for ``test_four_devices.py``: run in a
process of its own with four virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``), it prints one
JSON line:

* ``one``, ``four``: the float32 reference's losses, first gradient
  norms and change norms over the toy model's first three steps, on one
  device and split over four;
* ``shards``: for each weight leaf of the four-device layout, its shape
  and the shape each device holds;
* ``layouts``: the split of a few shapes that the toy model lacks;
* ``fsdp4``: a whole run of a toy cell with ``"fsdp": 4`` on the four
  devices.
"""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH / "tests"), str(BENCH), str(BENCH.parents[1] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import toy  # noqa: E402
from harness import seeds, spec, train, weights  # noqa: E402
from reference import train as ref_train  # noqa: E402

SEED = 2 ** 31 + 12345


def reference(devices, shapes, batches, opt):
    w0 = weights.make(shapes, seeds.key(SEED, "weights"),
                      out_shardings=ref_train.shardings(shapes, devices))
    out = ref_train.run(toy.TINY, w0, batches, opt, steps=len(batches),
                        devices=devices)
    return {k: np.asarray(out[k]).tolist()
            for k in ("losses", "grad_norms", "change_norms")}


def main():
    from repro.models import registry as R
    devices = jax.devices()
    assert len(devices) == 4, devices
    cell = toy.train_cell(chips=4, fsdp=4, batch=4)
    shapes = jax.eval_shape(
        lambda k: R.init(spec.model_config(toy.TINY), k, jax.numpy.bfloat16),
        jax.random.PRNGKey(0))
    make = train.batch_fn(toy.TINY, cell.traffic, SEED)
    batches = [make(i) for i in range(cell.traffic["check_steps"])]
    opt = cell.traffic["optimizer"]
    four = ref_train.shardings(shapes, devices)
    w0 = weights.make(shapes, seeds.key(SEED, "weights"), out_shardings=four)
    shards = [[list(x.shape), [list(s.data.shape)
                               for s in x.addressable_shards]]
              for x in jax.tree_util.tree_leaves(w0)]
    layouts = {str(shape): str(s.spec) for shape, s in (
        (shape, ref_train.shardings(jax.ShapeDtypeStruct(shape, "float32"),
                                    devices))
        for shape in ((3, 5), (6, 8), (8, 8), (4,), ()))}
    out = {"one": reference(devices[:1], shapes, batches, opt),
           "four": reference(devices, shapes, batches, opt),
           "shards": shards, "layouts": layouts,
           "fsdp4": toy.measure(cell, seed=SEED)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
