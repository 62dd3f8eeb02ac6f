"""Training cells: the launcher's own state and step
(``repro.launch.train.build_training``), driven with the traffic file's
``in_flight`` steps dispatched ahead of the one waited for, so that a
stall of the host does not starve the chip. (The program's own loop,
``repro.train.loop.run_training``, waits for every step's loss, and
the host's stalls spread its rate by percents.)

Set-up builds one training object, puts the benchmark's weights in it,
and drives it through ``check_steps`` steps with the same call and feed
as the window; the first of those compiles. The same object then trains
for the window. When its time is up nothing more is sent; the window
ends in ``block_until_ready`` of the last state, and every step sent
counts. The check compares those first steps with the float32 reference.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import sys
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, seeds, weights
from harness.device import peak_bytes
from harness.spec import Cell, model_config


@dataclasses.dataclass
class Record:
    """What one run measured, for the end-to-end metrics and readers."""
    kind: str
    cell: Cell
    window_s: float
    steps: int = 0
    tokens: int = 0
    chips: int = 1
    trace: Any = None              # harness.trace.Reduction, or None
    device_kind: str = ""
    extra: dict = dataclasses.field(default_factory=dict)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _rows(key, i, batch: int, seq: int, vocab: int):
    toks = jax.random.randint(jax.random.fold_in(key, i), (batch, seq + 1),
                              0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def batch_fn(cfg: dict, traffic: dict, seed: int):
    """Step i's batch: token rows drawn from (seed, i), all different."""
    base = seeds.key(seed, "data")
    shape = (traffic["batch"], traffic["seq"], cfg["vocab_size"])
    return lambda i: _rows(base, i, *shape)


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _norms(leaves):
    return jnp.stack([_norm(x) for x in leaves])


@jax.jit
def _change_norms(ws, cs, w0s):
    f32 = jnp.float32
    return jnp.stack([_norm((w.astype(f32) - w0.astype(f32)) - c.astype(f32))
                      for w, c, w0 in zip(ws, cs, w0s)])


class _Steps:
    """The program's step under a host span, counting calls and keeping
    the harness clock at each. ``hook(calls, state)`` runs after a step,
    for the check's readings."""

    def __init__(self, step, hook=None):
        self.step, self.hook, self.calls = step, hook, 0
        self.t = []           # harness clock at each call

    def __call__(self, state, batch, seed):
        self.t.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, metrics = self.step(state, batch, seed)
        self.calls += 1
        if self.hook is not None:
            self.hook(self.calls, state)
        return state, metrics


def _ahead(step, state, make, start, seed, in_flight, *, n=None,
           until=None):
    """Drive ``step`` from batch ``start`` with ``in_flight`` steps
    dispatched ahead of the one waited for: ``n`` steps, or until the
    harness clock passes ``until``; then wait for all that was sent.
    Returns the state and the metrics of every step."""
    sent, rows = collections.deque(), []
    i = start
    while (n is None or i < start + n) and (until is None
                                            or time.perf_counter() < until):
        with jax.profiler.TraceAnnotation("bench.batches"):
            b = make(i)
        state, metrics = step(state, b, seed)
        sent.append(metrics)
        rows.append(metrics)
        if len(sent) > in_flight:
            jax.block_until_ready(sent.popleft()["loss"])
        i += 1
    jax.block_until_ready(state)
    return state, rows


def intervals(t) -> dict:
    """The spread of the window's step-to-step intervals (harness clock),
    for the run's standard error: a stall shows as a long one."""
    d = np.diff(np.asarray(t)) * 1e3
    if d.size == 0:
        return {}
    med = float(np.median(d))
    return {"steps": int(d.size + 1), "median_ms": med,
            "p90_ms": float(np.percentile(d, 90)), "max_ms": float(d.max()),
            "over_1.05_median_ms": float(np.sum(d[d > 1.05 * med] - med))}


def run(cell: Cell, seed: int, seconds: float, *, devices, t_start: float,
        tracer=None, control: str | None = None) -> Record:
    """Set-up, window and check of one training cell. ``control`` (fp8
    or half_batch) runs no program: that control or fault, put in the
    program's place, is read through the check instead."""
    from repro.core.policy import get_policy
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import build_training
    from repro.models import registry as R

    cfg_d, tr = cell.config, cell.traffic
    cfg = model_config(cfg_d)
    opt = tr["optimizer"]
    policy = get_policy(tr["policy"])
    mesh = (make_local_mesh(1, 1, fsdp=tr["fsdp"]) if tr.get("fsdp", 1) > 1
            else None)
    pseed = seeds.program_seed(seed)
    shapes = jax.eval_shape(lambda k: R.init(cfg, k, policy.param_dtype),
                            jax.random.PRNGKey(0))
    w_key = seeds.key(seed, "weights")
    make = batch_fn(cfg_d, tr, seed)
    _log("start")
    if control:
        # the control needs no program: the reference takes its place
        rec = Record("train", cell, 0.0, chips=len(devices), extra={
            "setup_s": time.perf_counter() - t_start,
            "memory_peak_bytes": peak_bytes(devices)})
        rec.extra["readings"] = _check(cell, seed, None, control, make,
                                       shapes, w_key, policy, devices)
        return rec
    run_ = build_training(cfg, policy, seed=pseed, lr=opt["lr"],
                          steps=opt["schedule_steps"], seq=tr["seq"],
                          mesh=mesh, fsdp=mesh is not None,
                          fused_update=tr["fused_update"],
                          grad_accum=tr.get("grad_accum", 1))
    _log("program built")
    placed = run_.shardings.params if run_.shardings else None
    params = weights.make(shapes, w_key, policy.param_dtype,
                          out_shardings=placed)
    # the launcher's own random weights are dropped for the benchmark's
    state = run_.state._replace(params=params)
    run_ = run_._replace(state=None)
    del params
    n_check = tr["check_steps"]
    readings_prog: dict[str, Any] = {}
    check_s = [0.0]

    def read_state(calls, state):
        """The program's side of the check, read from its state: the
        first gradient as the optimizer got it (m = (1 - b1) g after one
        step) and the compensated weights' change after ``n_check``."""
        t = time.perf_counter()
        if calls == 1:
            ms = jax.tree_util.tree_leaves(state.opt_state.m)
            readings_prog["grad_norms"] = np.asarray(_norms(ms)) / (
                1.0 - opt["b1"])
            readings_prog["grad"] = ([np.asarray(x) for x in ms],
                                     1.0 / (1.0 - opt["b1"]))
        if calls == n_check:
            w0 = jax.tree_util.tree_leaves(
                weights.make(shapes, w_key, policy.param_dtype,
                             out_shardings=placed))
            ws = jax.tree_util.tree_leaves(state.params)
            cs = (jax.tree_util.tree_leaves(state.opt_state.kahan_c)
                  if policy.kahan else [jnp.zeros((), w.dtype) for w in ws])
            # (w - w0) is exact in float32; the Kahan buffer c holds what
            # w still owes, so w - c is the compensated weight
            readings_prog["change_norms"] = np.asarray(
                _change_norms(ws, cs, w0))
            del w0
        check_s[0] += time.perf_counter() - t

    step = _Steps(run_.step, read_state)
    with run_.scope():
        state, rows = _ahead(step, state, make, 0, pseed, tr["in_flight"],
                             n=n_check)
        readings_prog["losses"] = [float(r["loss"]) for r in rows]
        setup_s = time.perf_counter() - t_start - check_s[0]
        _log("set-up done")
        step.hook = None
        calls0 = step.calls
        ctx = tracer.window() if tracer else contextlib.nullcontext()
        with ctx:
            t0 = time.perf_counter()
            state, _ = _ahead(step, state, make, n_check, pseed,
                              tr["in_flight"], until=t0 + seconds)
            window = time.perf_counter() - t0
    n_steps = step.calls - calls0
    tok = n_steps * tr["batch"] * tr["seq"]
    rec = Record("train", cell, window, n_steps, tok,
                 chips=len(devices), extra={
                     "setup_s": setup_s,
                     "param_leaves": len(jax.tree_util.tree_leaves(shapes))})
    rec.extra["memory_peak_bytes"] = peak_bytes(devices)
    rec.extra["intervals"] = intervals(step.t[calls0:])
    del state, step, run_
    _log("window done")
    rec.extra["readings"] = _check(cell, seed, readings_prog, None, make,
                                   shapes, w_key, policy, devices)
    return rec


def _release_host_memory() -> None:
    """Collect garbage and hand the C heap's free pages back to the
    system, so the reference finds the host memory that set-up and the
    window no longer use."""
    import ctypes
    import gc
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass        # not glibc: nothing to hand back


def _rss_gb() -> float:
    """The process's resident host memory now, in GB."""
    with open("/proc/self/status") as f:
        kb = next(int(line.split()[1]) for line in f
                  if line.startswith("VmRSS:"))
    return kb * 1024 / 1e9


def _log(msg: str) -> None:
    print(f"{msg} (host rss {_rss_gb():.2f} GB)", file=sys.stderr,
          flush=True)


def _check(cell, seed, prog, control, make, shapes, w_key, policy,
           devices) -> dict:
    """Readings of the program (``prog``; None where ``control`` takes its
    place) or of ``control`` (the fp8 control or the half-batch fault)
    against the float32 reference over the same first steps, on the
    cell's ``devices``. The control runs first and keeps its first
    gradient, so the reference compares it with its own."""
    from reference import train as ref_train
    _release_host_memory()
    _log("check starts")
    tr = cell.traffic
    n = tr["check_steps"]
    batches = [make(i) for i in range(n)]
    w0 = weights.make(shapes, w_key, policy.param_dtype,
                      out_shardings=ref_train.shardings(shapes, devices))
    runs = {} if prog is None else {"program": prog}
    if control == "fp8":
        runs["control"] = ref_train.run(cell.config, w0, batches,
                                        tr["optimizer"], steps=n,
                                        devices=devices, mm="fp8",
                                        keep_grad=True, log=_log)
    elif control == "half_batch":
        # a fault planted in the reference put in the program's place:
        # the mean taken over the first half of each row's tokens only
        runs["fault_half_batch"] = ref_train.run(
            cell.config, w0, batches, tr["optimizer"], steps=n,
            devices=devices, half_batch=True, keep_grad=True, log=_log)
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")
    _release_host_memory()
    grads = {k: r.pop("grad") for k, r in runs.items()}
    grads = {k: g if k == "program" else (g, 1.0) for k, g in grads.items()}
    ref = ref_train.run(cell.config, w0, batches, tr["optimizer"], steps=n,
                        devices=devices, compare=grads, log=_log)
    del grads
    return {k: check.train_readings(r, ref, ref["grad_diff"][k])
            for k, r in runs.items()}
