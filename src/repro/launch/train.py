"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2.5-3b --reduced --policy bf16_sr \
        --steps 300 --batch 8 --seq 64 --ckpt-dir /tmp/run1

On a real cluster this same entry point runs under ``jax.distributed``
— one process per host, joined via ``--coordinator/--num-processes/
--process-id`` or the ``REPRO_*`` environment variables that
``tools/dist_launch.py`` sets (see docs/multihost.md). The mesh axes
and activation-sharding context are installed exactly as in the
dry-run; the mesh spans the *global* device set, checkpoints are
committed by process 0 only, and every process barriers around
restore. With no explicit mesh flags a multi-process run defaults to
data-parallelism over all global devices.

``--fsdp`` shards parameters *and* all optimizer state (moments, Kahan
compensation, SR residuals) over the data axes — a dedicated ``fsdp``
axis when ``--fsdp-parallel > 1`` gives one, otherwise the ``data`` axis
itself. ``--pods`` prepends a ``pod`` mesh axis (DCN data parallelism
across ICI domains), and ``--grad-wire`` selects the gradient transport
for it: ``fp32`` (explicit f32 mean over the pod axis), ``compressed``
(the historic SR-to-bf16 wire with persistent error-feedback residuals
— half the DCN bytes), or any named wire format — ``bf16``/``bf14``/
``bf12``/``bf10``/``fp16``/``e5m2``/``e4m3`` — for the sub-bf16/fp8
regimes (without a pod axis the compressed wire rides the ``data``
axis). ``--wire-keep-fp32`` adds the per-leaf keep policy: embeddings,
norms, biases and tiny leaves ride fp32 while bulk matmul leaves take
the low format. ``--grad-accum=k`` scans k microbatches over one gathered
working copy before the single reduce + update. The TrainState sharding
tree — error-feedback residuals included — is handed to
``run_training`` so an elastic checkpoint resume re-shards restored
state onto the *current* mesh instead of restoring it unsharded.
"""
from __future__ import annotations

import argparse
import contextlib
from typing import Any, Callable, NamedTuple

import jax

from repro.core.policy import get_policy
from repro.data.synthetic import lm_batches
from repro.dist import fsdp as F
from repro.dist import multihost as MH
from repro.dist import partition as PT
from repro.dist import transport as TR
from repro.dist.axes import activation_sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import registry as R
from repro.optim import adamw, fused_adamw_optimizer, linear_warmup_cosine
from repro.train.loop import TrainLoopConfig, run_training
from repro.train.step import make_train_step
from repro.train.train_state import TrainState, make_train_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-friendly)")
    ap.add_argument("--policy", default="bf16_sr")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--fsdp-parallel", type=int, default=1,
                    help="size of a dedicated fsdp mesh axis (implies --fsdp)")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params + optimizer state (incl. Kahan "
                         "buffers) over the data axes")
    ap.add_argument("--pods", type=int, default=1,
                    help="pod mesh axis size: DP across ICI domains, "
                         "gradient reduce over (virtual) DCN")
    ap.add_argument("--grad-wire", default="fp32",
                    choices=["fp32", "compressed", "bf16", "bf14", "bf12",
                             "bf10", "fp16", "e5m2", "e4m3"],
                    help="gradient transport on the wire axis: fp32 mean, "
                         "or an SR-compressed wire with error feedback at "
                         "the named format ('compressed' = bf16, the "
                         "historic wire; e5m2/e4m3 are fp8, clamped at "
                         "max_finite)")
    ap.add_argument("--wire-keep-fp32", default=None,
                    help="per-leaf fp32 keep on a compressed wire: "
                         "'default' (embeddings/norms/biases/scales and "
                         "leaves <2048 elems ride fp32), 'none', or a "
                         "comma list of name patterns with an optional "
                         "size threshold, e.g. '4096,embed,norm'")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatches scanned per step over one gathered "
                         "working copy (single reduce + update)")
    ap.add_argument("--fused-update", action="store_true",
                    help="run the optimizer update through the fused Pallas "
                         "kernels (one HBM pass over w/m/v/g/c); on a mesh "
                         "the update runs shard-local inside shard_map — "
                         "bf16 policies only")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of the jax.distributed coordinator "
                         "(process 0); defaults to $REPRO_COORDINATOR")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="total jax.distributed process count "
                         "(default $REPRO_NUM_PROCESSES)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's rank (default $REPRO_PROCESS_ID)")
    ap.add_argument("--sync-ckpt", action="store_true",
                    help="commit checkpoints inline instead of on the "
                         "background writer thread")
    ap.add_argument("--spike-factor", type=float, default=None,
                    help="loss-spike monitor: roll back to the last good "
                         "checkpoint after --spike-patience consecutive "
                         "steps with loss > factor × EWMA (or non-finite)")
    ap.add_argument("--spike-patience", type=int, default=2)
    ap.add_argument("--max-rollbacks", type=int, default=2)
    ap.add_argument("--preempt-poll", type=int, default=10,
                    help="multi-host: poll the (collective) SIGTERM "
                         "agreement every this many steps")
    args = ap.parse_args()

    enable_compile_cache()
    # must precede any backend/device use in the process
    MH.initialize(coordinator=args.coordinator,
                  num_processes=args.num_processes,
                  process_id=args.process_id)

    policy = get_policy(args.policy)
    cfg = R.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    wire_policy = (TR.WirePolicy.parse(args.wire_keep_fp32)
                   if args.wire_keep_fp32 is not None else None)
    dp, mp, fp, pods = (args.data_parallel, args.model_parallel,
                        args.fsdp_parallel, args.pods)
    if MH.active() and dp * mp * fp * pods == 1:
        # multi-process with no explicit topology: data-parallel over
        # every global device (a single-device mesh would leave the
        # other hosts' devices idle and the collectives unformed)
        dp = jax.device_count()
    mesh = (make_local_mesh(dp, mp, fsdp=fp, pods=pods)
            if dp * mp * fp * pods > 1 else None)
    run = build_training(cfg, policy, seed=args.seed, lr=args.lr,
                         steps=args.steps, seq=args.seq, mesh=mesh,
                         fsdp=args.fsdp or fp > 1,
                         fused_update=args.fused_update,
                         grad_wire=args.grad_wire, wire_policy=wire_policy,
                         grad_accum=args.grad_accum)
    with run.scope():
        _run(run, cfg, args)


class Training(NamedTuple):
    state: TrainState
    step: Callable               # jitted; donates the state it is given
    shardings: Any               # TrainState sharding tree, None off-mesh
    transport: TR.GradientTransport
    scope: Callable[[], contextlib.AbstractContextManager]


def build_training(cfg, policy, *, seed: int, lr: float, steps: int,
                   seq: int, mesh=None, fsdp: bool = False,
                   fused_update: bool = False, grad_wire: str = "fp32",
                   wire_policy=None, grad_accum: int = 1) -> Training:
    """The launcher's training state and step, on ``mesh`` or one device.

    The state is made by one jitted ``R.init`` + ``make_train_state``
    straight into its shardings, so no device ever holds more than its
    share of it, and the step is jitted donating the state: the update
    writes over the old state's buffers instead of beside them. A step
    that fails after it has consumed its state is not retried
    (``run_training`` raises; the run restarts from its last
    checkpoint). ``scope()`` is the context the step runs in: the mesh
    and the activation-sharding hints.
    """
    key = jax.random.PRNGKey(seed)
    params_shape = jax.eval_shape(
        lambda k: R.init(cfg, k, policy.param_dtype), key)
    placement = pspecs = shardings = None
    if mesh is not None:
        placement = PT.default_placement(mesh, fsdp=fsdp)
        pspecs = PT.param_specs(params_shape, cfg, mesh, placement)
    # the fused kernels run shard-local (inside shard_map) on a mesh, so
    # the optimizer is built only after the placement is known
    opt = (fused_adamw_optimizer(policy, b2=0.997, weight_decay=0.01,
                                 mesh=mesh, pspecs=pspecs)
           if fused_update else adamw(policy, b2=0.997, weight_decay=0.01))
    transport = TR.make_transport(mesh=mesh, placement=placement,
                                  pspecs=pspecs, wire=grad_wire,
                                  wire_policy=wire_policy)

    def init(k):
        return make_train_state(R.init(cfg, k, policy.param_dtype), opt,
                                transport=transport)

    if mesh is not None:
        shardings = F.train_state_shardings(jax.eval_shape(init, key), cfg,
                                            mesh, placement,
                                            transport=transport)
    state = jax.jit(init, out_shardings=shardings)(key)
    step_fn = make_train_step(cfg, policy, opt,
                              linear_warmup_cosine(lr, max(steps // 20, 1),
                                                   steps),
                              transport=transport, grad_accum=grad_accum,
                              attn_chunk=min(1024, seq))

    @contextlib.contextmanager
    def scope():
        if mesh is None:
            yield
            return
        hint_axes, hint_size = transport.hint_axes(mesh)
        with jax.set_mesh(mesh), activation_sharding(
                hint_axes, hint_size, PT.MODEL_AXIS,
                mesh.shape[PT.MODEL_AXIS]):
            yield

    return Training(state, jax.jit(step_fn, donate_argnums=(0,)), shardings,
                    transport, scope)


def _run(run: Training, cfg, args):
    def batches(start_step):
        # step-keyed stream: a resume (or spike rollback) at step k
        # continues with batch k — never replays batches 0..k-1
        return lm_batches(cfg.vocab, args.batch, args.seq, seed=args.seed,
                          start_step=start_step)
    log = print if MH.is_primary() else (lambda *_a, **_k: None)
    state, info = run_training(
        run.state, run.step, batches,
        TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every, seed=args.seed,
                        async_saves=not args.sync_ckpt,
                        spike_factor=args.spike_factor,
                        spike_patience=args.spike_patience,
                        max_rollbacks=args.max_rollbacks,
                        preempt_poll_every=args.preempt_poll,
                        wire_format=getattr(run.transport, "wire_format",
                                            None)),
        log=log, state_shardings=run.shardings)
    last = info["history"][-1] if info["history"] else {}
    log(f"[train] done at step {int(jax.device_get(state.step))}; "
        f"final loss {last.get('loss'):.4f}; "
        f"stragglers={info['stragglers']} preempted={info['preempted']} "
        f"rollbacks={info['rollbacks']}")


if __name__ == "__main__":
    main()
