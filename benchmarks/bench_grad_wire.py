"""Gradient-wire transports: bytes/step on the wire and step time.

The claim the compressed wire exists for: SR-to-bf16 with error feedback
halves gradient bytes on the DCN pod axis versus an fp32 reduction,
without giving up the unbiased mean (``tests/test_transport.py`` holds
the accuracy side). Measured, not asserted: wire bytes come from the
lowered module's explicit ``all_reduce`` collectives — shard_map emits
the wire reduce with its true operand dtype (bf16 for the compressed
wire, f32 for the fp32 wire) — summed per dtype. Post-optimization HLO
would *not* work here: the CPU test backend promotes bf16 all-reduce to
f32 (a backend quirk; TPU/GPU keep bf16 on the wire), which is exactly
why the accounting reads the pre-partitioning module.

Rows (8 virtual host devices, subprocess — the parent backend is locked
to 1 device):

* ``grad_wire_<wire>_<pods>pod_step`` — µs/step + wire bytes/step for
  fp32 vs compressed on a 1-pod (4 data × 2 model; the compressed wire
  rides the ``data`` axis) and a 2-pod (2 pod × 2 data × 2 model) mesh.
* ``grad_wire_pod_bytes_ratio`` — fp32 ÷ compressed wire bytes on the
  2-pod mesh; the acceptance bar is ≥ ~2×.

Each step row is additionally labeled with the number of
reduce-scatter→all-reduce+slice fallback sites found in the *optimized*
module (``rs_fallbacks=N(ar+slice,…B)``, via
:func:`repro.launch.hlo_analysis.analyze_hlo`): those are the sites
where post-opt byte accounting would over-count by the shard factor,
i.e. the reason this bench reads the pre-partitioning module for wire
bytes in the first place.

Byte-accounting convention: the StableHLO numbers are **carrier** bytes
— the dtype the all-reduce operand is emitted with (f32, or bf16 for
the compressed wire). Each compressed row additionally reports
``payload_bytes`` = Σ n_elem · ``fmt.bits``/8, the *format* payload
(``CompressedWire.payload_bytes``): for the default bf16 wire the two
coincide, but for sub-bf16/fp8 formats (see ``--sweep``) the carrier
over-counts — bf12 rides a bf16 carrier on CPU yet moves 12 bits of
information per element, and the payload column is the honest number.

``python benchmarks/bench_grad_wire.py --smoke`` runs the 2-pod pair
only (the CI smoke). ``--sweep`` (optionally with ``--smoke``) runs the
format × policy × model sweep instead — see
:mod:`benchmarks.bench_grad_wire_sweep`.
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
    import re, time
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import get_policy
    from repro.dist import partition as PT
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.dist import fsdp as F
    from repro.dist import transport as T
    from repro.dist.axes import activation_sharding
    from repro.launch.mesh import make_local_mesh
    from repro.models import registry as R
    from repro.optim import adamw, constant
    from repro.train.step import make_train_step
    from repro.train.train_state import make_train_state

    SMOKE = {smoke}
    policy = get_policy("bf16_sr")
    cfg = R.get_config("qwen2.5-3b").reduced()
    params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
    opt = adamw(policy, b2=0.997)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    raw_batch = {{"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}}

    # wire-format accounting: explicit all_reduce collectives in the
    # lowered module, bytes summed per operand dtype (see module docs)
    DT_BYTES = {{"bf16": 2, "f16": 2, "f32": 4, "f64": 8}}
    AR = re.compile(r'"stablehlo\\.all_reduce".*?\\}}\\)\\s*:\\s*'
                    r'\\(tensor<([0-9x]*?)x?(bf16|f16|f32|f64)>\\)', re.S)

    def wire_bytes(lowered_text):
        total = {{}}
        for m in AR.finditer(lowered_text):
            dims, dt = m.groups()
            n = 1
            for d in dims.split("x"):
                if d:
                    n *= int(d)
            total[dt] = total.get(dt, 0) + n * DT_BYTES[dt]
        return total

    def bench(pods, wire):
        mesh = make_local_mesh(4 // pods, 2, pods=pods)
        pl = PT.Placement()
        pspecs = PT.param_specs(params, cfg, mesh, pl)
        tr = T.make_transport(mesh=mesh, placement=pl, pspecs=pspecs,
                              wire=wire)
        state = make_train_state(params, opt, transport=tr)
        state = jax.device_put(state, F.train_state_shardings(
            state, cfg, mesh, pl, transport=tr))
        batch = jax.device_put(raw_batch, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), PT.batch_specs(raw_batch, mesh),
            is_leaf=lambda x: isinstance(x, P)))
        step = make_train_step(cfg, policy, opt, constant(1e-3),
                               attn_chunk=32, transport=tr)
        hints, hsize = tr.hint_axes(mesh)
        fn = jax.jit(step)
        with jax.set_mesh(mesh), activation_sharding(hints, hsize, "model", 2):
            lowered = fn.lower(state, batch, 0)
            wb = wire_bytes(lowered.as_text())
            cost = analyze_hlo(lowered.compile().as_text())
            state, m = fn(state, batch, 0)           # compile + warm
            jax.block_until_ready(m["loss"])
            iters = 2 if SMOKE else 5
            t0 = time.perf_counter()
            for _ in range(iters):
                state, m = fn(state, batch, 0)
            jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / iters * 1e6
        total = sum(wb.values())
        by = "+".join(f"{{dt}}:{{b}}" for dt, b in sorted(wb.items()))
        # carrier bytes (the emitted all-reduce operand dtype) vs format
        # payload bytes (fmt.bits-based; identical for the bf16 wire,
        # narrower for sub-bf16 formats — see the sweep)
        payload = (tr.payload_bytes(params)
                   if hasattr(tr, "payload_bytes") else total)
        # label reduce-scatter→all-reduce+slice fallback sites: on this
        # backend those collectives move the whole buffer per shard, so
        # the post-opt module over-counts wire bytes at exactly these
        # sites (the StableHLO accounting above is unaffected)
        fb = (f"rs_fallbacks={{cost.rs_fallbacks}}"
              f"(ar+slice,{{int(cost.rs_fallback_bytes)}}B)"
              if cost.rs_fallbacks else "rs_fallbacks=0")
        print(f"row grad_wire_{{wire}}_{{pods}}pod_step {{us:.1f}} "
              f"wire_bytes={{total}} carrier={{by or 'implicit-gspmd'}} "
              f"payload_bytes={{payload}} {{fb}}")
        return total

    cases = [(2, "fp32"), (2, "compressed")]
    if not SMOKE:
        cases = [(1, "fp32"), (1, "compressed")] + cases
    bytes_2pod = {{}}
    for pods, wire in cases:
        b = bench(pods, wire)
        if pods == 2:
            bytes_2pod[wire] = b
    ratio = bytes_2pod["fp32"] / max(bytes_2pod["compressed"], 1)
    print(f"row grad_wire_pod_bytes_ratio {{ratio:.3f}} "
          f"fp32={{bytes_2pod['fp32']}} compressed={{bytes_2pod['compressed']}}")
    assert ratio >= 1.9, f"compressed pod wire saves only {{ratio:.2f}}x"
"""


def _run_sub(smoke: bool) -> list[str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"        # virtual CPU devices; never the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    script = textwrap.dedent(_SCRIPT).format(smoke=smoke)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(
            f"grad-wire bench subprocess failed: {r.stderr[-2000:]}")
    return [l for l in r.stdout.splitlines() if l.startswith("row ")]


def run(*, smoke: bool = False) -> None:
    for line in _run_sub(smoke):
        parts = line.split()
        name, val, derived = parts[1], float(parts[2]), " ".join(parts[3:])
        if name.endswith("_ratio"):
            row(name, 0.0, f"{val:.3f}x {derived}")
        else:
            row(name, val, derived)


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    print("name,us_per_call,derived")
    if "--sweep" in sys.argv:
        from benchmarks.bench_grad_wire_sweep import run as run_sweep
        run_sweep(smoke=smoke)
    else:
        run(smoke=smoke)
