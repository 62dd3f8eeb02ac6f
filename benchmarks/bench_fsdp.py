"""DP vs FSDP: per-device memory for params + optimizer state, step time.

The memory claim the subsystem exists for: Algorithm 5 (Kahan) doubles
per-weight optimizer state, and FSDP shards all of it over the data axes
— so per-device bytes shrink by ~the FSDP factor while the step stays
numerically equivalent. The comparison runs on 8 virtual host devices
(2 data × 2 fsdp × 2 model) in a subprocess, because the parent's XLA
backend is already locked to 1 device.

Rows: per-device bytes (params + optimizer state) and µs/step for DP
replication vs FSDP sharding, plus the realized memory ratio.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

from benchmarks.common import row

_SRC = str(Path(__file__).resolve().parent.parent / "src")

_SCRIPT = """
    import time
    import jax, jax.numpy as jnp
    from repro.core import get_policy
    from repro.dist import partition as PT
    from repro.dist import fsdp as F
    from repro.dist.axes import activation_sharding
    from repro.launch.mesh import make_local_mesh
    from repro.models import registry as R
    from repro.optim import adamw, constant
    from repro.train.step import make_train_step, make_fsdp_train_step
    from repro.train.train_state import make_train_state

    policy = get_policy("bf16_sr_kahan")
    cfg = R.get_config("qwen2.5-3b").reduced()
    params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
    opt = adamw(policy, b2=0.997)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    mesh = make_local_mesh(2, 2, fsdp=2)

    def bench(placement, step_fn, tag):
        state = jax.device_put(
            make_train_state(params, opt),
            F.train_state_shardings(make_train_state(params, opt), cfg,
                                    mesh, placement))
        bytes_dev = F.per_device_bytes((state.params, state.opt_state))
        fn = jax.jit(step_fn)
        with jax.set_mesh(mesh), activation_sharding(PT.dp_axes(mesh), PT.dp_size(mesh),
                                       "model", 2):
            state, m = fn(state, batch, 0)           # compile + warm
            jax.block_until_ready(m["loss"])
            t0 = time.perf_counter()
            for _ in range(5):
                state, m = fn(state, batch, 0)
            jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / 5 * 1e6
        print(tag, bytes_dev, us)
        return bytes_dev

    dp_pl = PT.Placement()
    b_dp = bench(dp_pl, make_train_step(cfg, policy, opt, constant(1e-3),
                                        attn_chunk=32), "dp")
    fs_pl = PT.default_placement(mesh, fsdp=True)
    pspecs = PT.param_specs(params, cfg, mesh, fs_pl)
    b_fs = bench(fs_pl, make_fsdp_train_step(cfg, policy, opt, constant(1e-3),
                                             pspecs=pspecs, placement=fs_pl,
                                             attn_chunk=32), "fsdp")
    print("ratio", b_dp / b_fs, 0.0)
"""


def run() -> None:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"        # virtual CPU devices; never the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_SCRIPT)],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"fsdp bench subprocess failed: {r.stderr[-2000:]}")
    for line in r.stdout.strip().splitlines():
        parts = line.split()
        if len(parts) != 3:
            continue
        tag, a, b = parts
        if tag == "ratio":
            row("fsdp_vs_dp_state_bytes_ratio", 0.0, f"{float(a):.3f}x")
        else:
            row(f"fsdp_compare_{tag}_step", float(b),
                f"state_bytes_per_device={a}")
