#!/usr/bin/env python3
"""Bring-up smoke: the bf16 trainer and the serving engine on a TPU v5e.

    python chip_smoke.py                # one chip: train and serve phases
    python chip_smoke.py --four-chips   # four chips: the FSDP phases only

Both paths run qwen2.5-3b at its published widths (d_model 2048, d_ff
11008, 16 query / 2 KV heads of 128, vocab 151,936). Weights are random,
made by ``R.init`` from ``--seed``: the repository holds no trained
weights. Everything runs in this one process, which holds the chip(s).

One chip:

* *train* -- depth cut to 4 layers, so that the pure-bf16 weights,
  moments and Kahan buffers fit. 6 steps of ``bf16_sr_kahan`` with the
  fused SR-AdamW kernel, built by ``repro.launch.train.build_training``
  (the launcher's own setup) and driven by ``run_training``; the loss
  must be finite and fall. Then 3 ``bf16_kahan`` AdamW steps through the fused
  kernel and through the jnp ``adamw`` on N(0, 1) weights of the model's
  leaf shapes, each from the same weights and (from the second on,
  nonzero) moments and Kahan buffers: weights and moments must agree
  within the one-bf16-ulp bound of ``tests/test_optim_fused.py`` when
  the jnp update rounds as written (see ``fused_vs_jnp``).
* *serve* -- all 36 layers. ``Engine`` drains 8 seeded greedy requests
  (prompts of 128-512 tokens, 32 new tokens each) on the contiguous pool
  and on the paged pool with chunked prefill. Every request must finish
  with 32 tokens inside the vocab, and the teacher-forced logits of each
  finished sequence of both pools must be finite. Whether the two pools
  gave the same tokens, and how often each pool's tokens are the
  teacher-forced argmax, is printed, not asserted.

Four chips (``--four-chips``):

* *fsdp-full* -- the full 36-layer model, ``bf16_sr_kahan``, FSDP over a
  4-chip mesh (``make_local_mesh(4)`` with the FSDP placement); the loss
  must be finite and fall.
* *fsdp-vs-one* -- the 4-layer model under ``bf16_kahan``, 3 steps FSDP
  over the 4 chips and 3 steps on one of them with the same batch and
  seed. Losses and parameters must agree within ``LOSS_TOL`` and
  ``PARAM_REL_TOL``.

The script needs a TPU and exits non-zero anywhere else. Every failed
check raises; the last line of stdout is the verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.policy import get_policy  # noqa: E402
from repro.core.qarith import QArith  # noqa: E402
from repro.data.synthetic import lm_batches  # noqa: E402
from repro.dist import fsdp as F  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.train import build_training  # noqa: E402
from repro.models import registry as R  # noqa: E402
from repro.optim import adamw, fused_adamw_optimizer  # noqa: E402
from repro.serve.engine import Engine  # noqa: E402
from repro.train.loop import TrainLoopConfig, run_training  # noqa: E402

ARCH = "qwen2.5-3b"
TRAIN_LAYERS = 4             # 0.62 B parameters: 16-bit state ~5 GB
# the full-depth FSDP step needs 14.83 GiB of a chip's 15.75 at batch 4
# and 14.98 at batch 8 (memory_analysis() of the step compiled for a
# described v5e:2x2). The 16-bit state and the gathered bf16 working copy
# dominate; batch 4 keeps the most headroom.
TRAIN_BATCH, TRAIN_SEQ = 4, 512
TRAIN_STEPS = 6
# the per-step loss is over a fresh batch each step: at 1e-4 it falls
# through the noise within 6 steps, at 3e-4 and above it first jumps
LR = 1e-4
PARITY_STEPS, PARITY_VOCAB = 3, 32768
SERVE_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (128, 512), 32
PREFILL_CHUNK = 128
# fsdp-vs-one: after 3 bf16_kahan steps the two layouts differ only in
# the order of the gradient sums. The loss bound is the CPU FSDP test's
# (tests/test_fsdp.py); the parameter bound is on ||w_fsdp - w_one|| /
# ||w_one|| over all leaves.
LOSS_TOL = 0.05
PARAM_REL_TOL = 1e-2
G = 2 ** 30


def require_tpu(count: int) -> jax.Device:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (jax.devices()[0] is "
                         f"{devices[0].platform!r}); this smoke runs only "
                         f"on the chip")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, found "
                         f"{len(devices)}")
    return devices[0]


def memory(device) -> str:
    stats = device.memory_stats()
    return (f"peak {stats['peak_bytes_in_use']} bytes "
            f"({stats['peak_bytes_in_use'] / G:.2f} GiB of "
            f"{stats['bytes_limit'] / G:.2f} GiB)")


def train(cfg, policy_name: str, *, steps: int, seed: int, mesh=None):
    """``steps`` steps of ``repro.launch.train``'s ``build_training`` with
    the fused update, driven by ``run_training``.

    ``mesh=None`` trains on the default device; a mesh trains FSDP over
    its data axis with the fused update running shard-local. Returns
    (state, per-step losses).
    """
    run = build_training(cfg, get_policy(policy_name), seed=seed, lr=LR,
                         steps=steps, seq=TRAIN_SEQ, mesh=mesh,
                         fsdp=mesh is not None, fused_update=True)
    with run.scope():
        t0 = time.perf_counter()
        state, info = run_training(
            run.state, run.step,
            lambda start: lm_batches(cfg.vocab, TRAIN_BATCH, TRAIN_SEQ,
                                     seed=seed, start_step=start),
            TrainLoopConfig(total_steps=steps, seed=seed, log_every=1),
            state_shardings=run.shardings)
        wall = time.perf_counter() - t0
    losses = [row["loss"] for row in info["history"]]
    print(f"[train] {policy_name} {cfg.n_layers} layers, "
          f"{sum(x.size for x in jax.tree_util.tree_leaves(state.params)) / 1e9:.3f} B params, "
          f"batch {TRAIN_BATCH}x{TRAIN_SEQ}, "
          f"{'FSDP x%d' % mesh.size if mesh is not None else 'one chip'}: "
          f"{steps} steps in {wall:.2f} s incl. compile; "
          f"losses {[round(x, 4) for x in losses]}")
    return state, losses


def check_falling(losses, what: str) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{what}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall {losses}")


def fused_vs_jnp(cfg, key) -> dict:
    """``PARITY_STEPS`` ``bf16_kahan`` AdamW steps through the fused kernel
    and through the jnp ``adamw``, on every leaf shape of ``cfg``, in one
    program. Weights and gradients are drawn N(0, 1) and b2 = 0.997, as
    ``tests/test_optim_fused.py::test_fused_adamw_matches_reference``
    draws them. Each step starts both updates from the same weights and
    state, the jnp update's of the step before, so from the second step
    on the kernel reads nonzero moments and Kahan buffers.

    Checked: the new weights ``w`` (scale: the old weights), the first
    moments ``m`` (scale: the gradients) and the second moments ``v``
    within one bf16 ulp, |ref - kernel| <= 2^-7 * max(|kernel|, |scale|,
    2^-126) + 1e-12; and the compensated weights ``w-c``, which hold what
    the bf16 weights round away, within 2^-6 of the step's size
    |d(w - c)| + |c| + |c_new|.

    The program is compiled twice. ``as written`` turns off XLA's
    ``xla_allow_excess_precision``, so the jnp update rounds to bf16
    wherever its code does, as the kernel does. ``default`` is how a
    jitted train step compiles: XLA may then keep f32 where the code
    rounds. Returns {compile: {quantity: (n beyond, worst |diff|/bound)}}.
    """
    policy = get_policy("bf16_kahan")
    fused = fused_adamw_optimizer(policy, b2=0.997)
    ref = adamw(policy, b2=0.997)
    shapes = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda k: R.init(cfg, k, policy.param_dtype), jax.random.PRNGKey(0)))
    f32 = lambda x: x.astype(jnp.float32)

    def beyond(diff, bound):
        return jnp.sum(diff > bound), jnp.max(diff / bound)

    def ulp_beyond(a, b, scale):
        a, b, scale = f32(a), f32(b), f32(scale)
        return beyond(jnp.abs(a - b), 2.0 ** -7 * jnp.maximum(
            jnp.maximum(jnp.abs(b), 2.0 ** -126), jnp.abs(scale)) + 1e-12)

    def draw(key):
        return [jax.random.normal(k, s.shape, s.dtype)
                for k, s in zip(jax.random.split(key, len(shapes)), shapes)]

    def both(key):
        key, kp = jax.random.split(key)
        params = draw(kp)
        state = ref.init(params)
        checks = {"w": [], "m": [], "v": [], "w-c": []}
        for t in range(PARITY_STEPS):
            key, kg, ku = jax.random.split(key, 3)
            grads = draw(kg)
            p_r, s_r = ref.update(grads, state, params, step=t, key=ku,
                                  lr=1e-3)
            p_k, s_k = fused.update(grads, state, params, step=t, key=ku,
                                    lr=1e-3)
            checks["w"] += [ulp_beyond(*x) for x in zip(p_r, p_k, params)]
            checks["m"] += [ulp_beyond(*x) for x in zip(s_r.m, s_k.m, grads)]
            checks["v"] += [ulp_beyond(a, b, a) for a, b in zip(s_r.v, s_k.v)]
            for w, c, wr, cr, wk, ck in zip(params, state.kahan_c, p_r,
                                            s_r.kahan_c, p_k, s_k.kahan_c):
                big_r, big_k = f32(wr) - f32(cr), f32(wk) - f32(ck)
                size = (jnp.abs(big_r - (f32(w) - f32(c))) + jnp.abs(f32(c))
                        + jnp.abs(f32(cr)))
                checks["w-c"].append(beyond(jnp.abs(big_r - big_k),
                                            2.0 ** -6 * size + 1e-12))
            params, state = p_r, s_r
        return {name: (sum(n for n, _ in cs),
                       jnp.max(jnp.stack([w for _, w in cs])))
                for name, cs in checks.items()}

    lowered = jax.jit(both).lower(key)
    out = {}
    for name, opts in (("as written", {"xla_allow_excess_precision": False}),
                       ("default", {})):
        res = jax.device_get(lowered.compile(compiler_options=opts)(key))
        out[name] = {q: (int(n), float(w)) for q, (n, w) in res.items()}
    return out


def phase_train(device, seed: int) -> None:
    cfg = dataclasses.replace(R.get_config(ARCH), n_layers=TRAIN_LAYERS)
    state, losses = train(cfg, "bf16_sr_kahan", steps=TRAIN_STEPS, seed=seed)
    check_falling(losses, "train bf16_sr_kahan")
    del state
    # the parity program holds weights, gradients and state of both
    # updates for 3 steps: with the 151,936-row embedding it does not fit
    # a v5e (the compile for a described v5e asks for 16.82 GiB of
    # 15.75), so its vocab is cut. The kernel flattens every leaf, so a
    # leaf's element count, not its shape, is what it sees.
    cut = dataclasses.replace(cfg, vocab=PARITY_VOCAB)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(jax.eval_shape(
        lambda k: R.init(cut, k), jax.random.PRNGKey(0))))
    parity = fused_vs_jnp(cut, jax.random.PRNGKey(seed))
    for name, res in parity.items():
        print(f"[train] bf16_kahan fused vs jnp AdamW ({name}), "
              f"{PARITY_STEPS} steps on {n_params} N(0,1) weights of the "
              f"model's leaf shapes (vocab {PARITY_VOCAB}), values beyond their bound (worst "
              f"|diff|/bound): " + ", ".join(
                  f"{q} {n} ({w:.4f})" for q, (n, w) in res.items()))
    bad = {q: n for q, (n, _) in parity["as written"].items() if n}
    if bad:
        raise AssertionError(f"fused and jnp AdamW disagree: {bad}")
    print(f"[train] device memory: {memory(device)}")


def phase_serve(device, seed: int) -> None:
    cfg = R.get_config(ARCH)
    policy = get_policy("bf16_sr")            # repro.launch.serve's default
    t0 = time.perf_counter()
    params = jax.jit(lambda k: R.init(cfg, k, policy.param_dtype))(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[serve] {ARCH}: {cfg.n_layers} layers, {n_params / 1e9:.3f} B "
          f"params ({n_params * 2 / 1e9:.2f} GB bf16) made in "
          f"{time.perf_counter() - t0:.2f} s")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(PROMPT_LENS[0],
                                                           PROMPT_LENS[1] + 1)),
                            dtype=np.int32) for _ in range(SERVE_REQUESTS)]
    # one short request first: it compiles every step width the drain uses
    warm = rng.integers(0, cfg.vocab, PREFILL_CHUNK + 2, dtype=np.int32)
    max_len = PROMPT_LENS[1] + NEW_TOKENS
    tokens = {}
    for name, kw in (("contiguous", {}),
                     ("paged", {"paged": True, "prefill_chunk": PREFILL_CHUNK})):
        engine = Engine(params, cfg, policy, n_slots=SERVE_REQUESTS,
                        max_len=max_len, **kw)
        t0 = time.perf_counter()
        engine.submit(warm, 2)
        engine.run()
        compile_s = time.perf_counter() - t0
        steps0 = engine.stats.steps
        rids = [engine.submit(p, NEW_TOKENS) for p in prompts]
        t0 = time.perf_counter()
        done = {c.rid: c for c in engine.run()}
        drain_s = time.perf_counter() - t0
        if sorted(done) != rids:
            raise AssertionError(f"{name}: finished {sorted(done)}, "
                                 f"submitted {rids}")
        out = np.stack([done[r].tokens for r in rids])
        if out.shape != (SERVE_REQUESTS, NEW_TOKENS) or out.min() < 0 \
                or out.max() >= cfg.vocab:
            raise AssertionError(f"{name}: tokens {out.shape} outside "
                                 f"[0, {cfg.vocab})")
        print(f"[serve] {name}: warm-up (compile) {compile_s:.2f} s; drained "
              f"{SERVE_REQUESTS} requests ({sum(p.size for p in prompts)} "
              f"prompt + {out.size} new tokens) in {drain_s:.2f} s over "
              f"{engine.stats.steps - steps0} engine steps")
        tokens[name] = out
        del engine
    same = tokens["contiguous"] == tokens["paged"]
    first = [int(np.argmin(row)) if not row.all() else NEW_TOKENS
             for row in same]
    print(f"[serve] contiguous and paged pools gave the same tokens: "
          f"{bool(same.all())} ({int(same.sum())}/{same.size} tokens, "
          f"{int(same.all(axis=1).sum())}/{SERVE_REQUESTS} requests; first "
          f"differing new token per request {first})")

    # teacher-forced logits of every finished sequence of both pools,
    # padded at the end to one length so that one program serves all
    qa = QArith(policy)

    @jax.jit
    def logits_check(params, seq):
        logits = R.forward_logits(qa, params, cfg, {"tokens": seq}, remat=False)
        top2 = jax.lax.top_k(logits[0].astype(jnp.float32), 2)[0]
        return (jnp.all(jnp.isfinite(logits)), jnp.argmax(logits[0], axis=-1),
                top2[:, 0] - top2[:, 1])

    for name, out in tokens.items():
        hit, margins = [], []
        for p, gen in zip(prompts, out):
            seq = np.zeros((1, max_len - 1), np.int32)
            seq[0, :p.size + NEW_TOKENS - 1] = np.concatenate([p, gen[:-1]])
            finite, argmax, margin = jax.device_get(logits_check(params, seq))
            if not finite:
                raise AssertionError(f"{name}: non-finite teacher-forced "
                                     f"logits")
            at = slice(p.size - 1, p.size - 1 + NEW_TOKENS)
            hit.append(argmax[at] == gen)
            margins.append(margin[at])
        hit, margins = np.concatenate(hit), np.concatenate(margins)
        print(f"[serve] {name}: teacher-forced logits finite for all "
              f"{SERVE_REQUESTS} sequences; their argmax matches "
              f"{int(hit.sum())}/{hit.size} engine tokens; top-2 logit margin "
              f"median {float(np.median(margins))}, largest where they differ "
              f"{float(margins[~hit].max()) if (~hit).any() else None}")
    print(f"[serve] device memory since start: {memory(device)}")


def phase_four_chips(device, seed: int) -> None:
    mesh = make_local_mesh(4)
    full = R.get_config(ARCH)
    state, losses = train(full, "bf16_sr_kahan", steps=TRAIN_STEPS, seed=seed,
                          mesh=mesh)
    check_falling(losses, "fsdp-full bf16_sr_kahan")
    state_bytes = F.per_device_bytes(state)
    print(f"[fsdp-full] train state per chip {state_bytes} bytes "
          f"({state_bytes / G:.2f} GiB)")
    for d in mesh.devices.flat:
        print(f"[fsdp-full] chip {d.id}: {memory(d)}")
    del state

    cut = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    state, loss4 = train(cut, "bf16_kahan", steps=3, seed=seed, mesh=mesh)
    w4 = jax.device_get(state.params)
    del state
    state, loss1 = train(cut, "bf16_kahan", steps=3, seed=seed)
    w1 = jax.device_get(state.params)
    del state
    loss_gap = max(abs(a - b) for a, b in zip(loss4, loss1))
    num = den = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(w4), jax.tree_util.tree_leaves(w1)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        num += float(np.sum((a - b) ** 2))
        den += float(np.sum(b ** 2))
    rel = (num / den) ** 0.5
    print(f"[fsdp-vs-one] {TRAIN_LAYERS}-layer bf16_kahan, 3 steps: max loss "
          f"gap {loss_gap:.5f} (tol {LOSS_TOL}); ||w_fsdp - w_one|| / ||w_one|| "
          f"{rel:.3e} (tol {PARAM_REL_TOL})")
    if not (loss_gap <= LOSS_TOL and rel <= PARAM_REL_TOL):
        raise AssertionError("FSDP over 4 chips and one chip disagree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and batches")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the FSDP phases, over four chips")
    args = ap.parse_args()
    enable_compile_cache()
    device = require_tpu(4 if args.four_chips else 1)
    print(f"[smoke] {device.platform} {device.device_kind} x{jax.device_count()}, "
          f"jax {jax.__version__}")
    if args.four_chips:
        phase_four_chips(device, args.seed)
    else:
        phase_train(device, args.seed)
        phase_serve(device, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
