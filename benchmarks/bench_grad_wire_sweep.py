"""Gradient-wire format × policy sweep: payload bytes/step vs converged loss.

The ROADMAP question behind the format-generic wire: how far below bf16
can the gradient wire go before error feedback stops holding parity?
This bench trains the two paper workloads — the reduced LM and the DLRM
click model — once per wire format (fp32 baseline, bf16, bf14, bf12,
e4m3) plus one per-leaf keep-policy cell (``bf12+keep``: embeddings /
norms / biases / sub-2048 leaves ride fp32, bulk matmul leaves ride
bf12), and emits one row per cell:

* ``payload_bytes_per_step`` — the **format** payload, Σ n_elem ·
  ``fmt.bits``/8 per wire reduce (``CompressedWire.payload_bytes``).
  This is deliberately *not* the carrier-dtype byte count: sub-bf16
  formats are simulated on a bf16/f16 carrier on CPU, and counting
  carrier bytes would credit bf12 with bf16's 2 bytes/element. The
  carrier is labeled per row instead.
* ``ratio_vs_fp32`` — fp32 payload ÷ this format's payload (pure bf12
  is 32/12 ≈ 2.67×; the acceptance bar asserts ≥ 2.6).
* ``final_loss`` + ``tol`` — mean loss over the last 10 steps, and the
  tolerance within which the keep-policy cell must recover the fp32
  row's loss (asserted; the pure low-format rows are reported
  unasserted — drifting is exactly what the sweep exists to chart).

A final ``grad_wire_sweep_hlo_<fmt>`` row per format (full mode, 8
virtual devices in a subprocess) lowers a 2-pod train step and reports
per-dtype collective bytes twice: from the pre-partitioning StableHLO
(the carrier the wire reduce is *emitted* with) and from
``hlo_analysis.analyze_hlo`` on the optimized module (post-opt — where
the CPU backend's bf16→f32 all-reduce promotion is visible; the label
makes the promotion explicit rather than letting it masquerade as an
f32 wire).

``--smoke`` runs one low-step LM cell (bf12 + keep) and skips the HLO
subprocess — the CI hook.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.common import row

_SRC = str(Path(__file__).resolve().parent.parent / "src")

# (label, wire format name, keep policy spec or None)
CELLS = [
    ("fp32", "fp32", None),
    ("bf16", "bf16", None),
    ("bf14", "bf14", None),
    ("bf12", "bf12", None),
    ("e4m3", "e4m3", None),
    ("bf12_keep", "bf12", "default"),
]

# |final_loss - fp32 final_loss| bound for the keep-policy cell
TOL = {"lm": 0.15, "dlrm": 0.03}


def _make_transport(wire: str, policy_spec: str | None):
    from repro.dist import transport as TR
    wp = TR.WirePolicy.parse(policy_spec) if policy_spec is not None else None
    return TR.make_transport(wire=wire, wire_policy=wp)


def _payload(tr, params) -> tuple[int, str]:
    """(payload bytes per wire reduce, carrier label) for a transport."""
    n_f32 = sum(l.size for l in jax.tree_util.tree_leaves(params)) * 4
    if not hasattr(tr, "payload_bytes"):
        return n_f32, "f32"
    from repro.core.formats import wire_carrier_dtype
    carriers = sorted({jnp.dtype(wire_carrier_dtype(f)).name
                       for f in tr.leaf_formats(params)})
    return tr.payload_bytes(params), "+".join(carriers)


def _train_lm(tr, steps: int, seed: int = 0) -> tuple[float, float]:
    """Reduced-LM cell through the transport; (final_loss, us/step)."""
    from repro.core import get_policy
    from repro.data.synthetic import lm_batches
    from repro.models import registry as R
    from repro.optim import adamw, constant
    from repro.optim.base import init_params_for_policy
    from repro.train.step import make_train_step
    from repro.train.train_state import make_train_state
    policy = get_policy("bf16_sr")
    cfg = R.get_config("qwen2.5-3b").reduced()
    params = R.init(cfg, jax.random.PRNGKey(seed), jnp.float32)
    params = init_params_for_policy(params, policy)
    opt = adamw(policy, b2=0.997)
    state = make_train_state(params, opt, transport=tr)
    step = jax.jit(make_train_step(cfg, policy, opt, constant(3e-3),
                                   attn_chunk=8, transport=tr))
    losses = []
    t0 = time.perf_counter()
    for i, b in enumerate(lm_batches(cfg.vocab, 8, 32, seed=seed)):
        if i >= steps:
            break
        state, m = step(state, b, seed)
        losses.append(float(m["loss"]))
    us = (time.perf_counter() - t0) / max(len(losses), 1) * 1e6
    return sum(losses[-10:]) / min(len(losses), 10), us


def _train_dlrm(tr, steps: int, seed: int = 0) -> tuple[float, float]:
    """DLRM cell: the bench's own SGD step with the wire reduce spliced
    between backward and update (``common.train_dlrm`` is not
    transport-aware); (final_logloss, us/step)."""
    from repro.core import QArith, get_policy
    from repro.data.synthetic import dlrm_batches
    from repro.models.dlrm import DLRM_KAGGLE_SMALL, dlrm_apply, dlrm_init
    from repro.optim import sgd
    from repro.optim.base import init_params_for_policy
    policy = get_policy("bf16_sr")
    qa = QArith(policy)
    params = init_params_for_policy(
        dlrm_init(jax.random.PRNGKey(seed), DLRM_KAGGLE_SMALL), policy)
    opt = sgd(policy, momentum=0.0)
    opt_state = opt.init(params)
    residuals = tr.init_residuals(params)

    @jax.jit
    def step(params, opt_state, residuals, batch, i):
        def loss_fn(p):
            logits = dlrm_apply(qa, p, batch["dense"], batch["sparse"])
            y = batch["labels"]
            return jnp.mean(jnp.maximum(logits, 0) - logits * y
                            + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        loss, g = jax.value_and_grad(loss_fn)(params)
        g, residuals = tr.reduce(g, residuals,
                                 jax.random.fold_in(jax.random.PRNGKey(7), i))
        p2, s2 = opt.update(g, opt_state, params, step=i,
                            key=jax.random.PRNGKey(i), lr=0.1)
        return p2, s2, residuals, loss

    losses = []
    t0 = time.perf_counter()
    for i, batch in enumerate(dlrm_batches(DLRM_KAGGLE_SMALL, 128,
                                           seed=seed + 1)):
        if i >= steps:
            break
        params, opt_state, residuals, loss = step(
            params, opt_state, residuals, batch, jnp.int32(i))
        losses.append(float(loss))
    us = (time.perf_counter() - t0) / max(len(losses), 1) * 1e6
    return sum(losses[-10:]) / min(len(losses), 10), us


_HLO_SCRIPT = """
    import re
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import get_policy
    from repro.dist import partition as PT
    from repro.dist import fsdp as F
    from repro.dist import transport as T
    from repro.dist.axes import activation_sharding
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_local_mesh
    from repro.models import registry as R
    from repro.optim import adamw, constant
    from repro.train.step import make_train_step
    from repro.train.train_state import make_train_state

    policy = get_policy("bf16_sr")
    cfg = R.get_config("qwen2.5-3b").reduced()
    params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
    opt = adamw(policy, b2=0.997)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    raw_batch = {{"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}}

    DT_BYTES = {{"bf16": 2, "f16": 2, "f32": 4, "f64": 8}}
    AR = re.compile(r'"stablehlo\\.all_reduce".*?\\}}\\)\\s*:\\s*'
                    r'\\(tensor<([0-9x]*?)x?(bf16|f16|f32|f64)>\\)', re.S)

    def stablehlo_bytes(text):
        total = {{}}
        for m in AR.finditer(text):
            dims, dt = m.groups()
            n = 1
            for d in dims.split("x"):
                if d:
                    n *= int(d)
            total[dt] = total.get(dt, 0) + n * DT_BYTES[dt]
        return total

    mesh = make_local_mesh(2, 2, pods=2)
    pl = PT.Placement()
    pspecs = PT.param_specs(params, cfg, mesh, pl)
    for wire in {wires!r}:
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
        with jax.set_mesh(mesh), activation_sharding(hints, hsize, "model", 2):
            lowered = jax.jit(step).lower(state, batch, 0)
            pre = stablehlo_bytes(lowered.as_text())
            cost = analyze_hlo(lowered.compile().as_text())
        ar_post = cost.collective_bytes_by_dtype.get("all-reduce", {{}})
        fmt_pre = "+".join(f"{{d}}:{{b}}" for d, b in sorted(pre.items()))
        fmt_post = "+".join(f"{{d}}:{{int(b)}}"
                            for d, b in sorted(ar_post.items()))
        print(f"row grad_wire_sweep_hlo_{{wire}} 0.0 "
              f"stablehlo_carrier_bytes={{fmt_pre or 'implicit-gspmd'}} "
              f"postopt_allreduce_bytes={{fmt_post or 'none'}} "
              f"note=post-opt-promotes-16bit-carriers-to-f32-on-cpu")
"""


def _hlo_rows(wires: list[str]) -> list[str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"        # virtual CPU devices; never the chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    script = textwrap.dedent(_HLO_SCRIPT).format(wires=wires)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(
            f"grad-wire sweep HLO subprocess failed: {r.stderr[-2000:]}")
    return [l for l in r.stdout.splitlines() if l.startswith("row ")]


def run(*, smoke: bool = False) -> None:
    models = {"lm": (_train_lm, 8 if smoke else 120),
              "dlrm": (_train_dlrm, 20 if smoke else 200)}
    cells = [c for c in CELLS if c[0] in ("fp32", "bf12_keep", "bf12")] \
        if smoke else CELLS
    if smoke:
        models.pop("dlrm")
    for model, (train, steps) in models.items():
        base_payload = None
        fp32_loss = None
        for label, wire, pol in cells:
            tr = _make_transport(wire, pol)
            # params for payload accounting only (training re-inits its own)
            if model == "lm":
                from repro.models import registry as R
                probe = R.init(R.get_config("qwen2.5-3b").reduced(),
                               jax.random.PRNGKey(0), jnp.float32)
            else:
                from repro.models.dlrm import DLRM_KAGGLE_SMALL, dlrm_init
                probe = dlrm_init(jax.random.PRNGKey(0), DLRM_KAGGLE_SMALL)
            payload, carrier = _payload(tr, probe)
            if label == "fp32":
                base_payload = payload
            ratio = (base_payload or payload) / payload
            loss, us = train(tr, steps)
            if label == "fp32":
                fp32_loss = loss
            tol = TOL[model]
            row(f"grad_wire_sweep_{model}_{label}", us,
                f"payload_bytes_per_step={payload} carrier={carrier} "
                f"ratio_vs_fp32={ratio:.3f} final_loss={loss:.4f} tol={tol}")
            if label == "bf12" and base_payload is not None:
                assert ratio >= 2.6, \
                    f"bf12 payload saves only {ratio:.2f}x vs fp32 on {model}"
            if label == "bf12_keep" and fp32_loss is not None and not smoke:
                assert abs(loss - fp32_loss) <= tol, \
                    (f"{model} keep-policy loss {loss:.4f} outside ±{tol} "
                     f"of fp32 {fp32_loss:.4f}")
    if not smoke:
        for line in _hlo_rows(["fp32", "bf16", "bf12", "e4m3"]):
            parts = line.split()
            row(parts[1], float(parts[2]), " ".join(parts[3:]))


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run(smoke="--smoke" in sys.argv)
