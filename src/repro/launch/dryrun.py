import os
os.environ["XLA_FLAGS"] = os.environ.get("REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any jax import: XLA locks the host
device count at first backend init. 512 placeholder CPU devices stand in
for the 2×16×16 production mesh (256/pod).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape train_4k --mesh single --out artifacts/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import LM_SHAPES, shape_by_name
from repro.core.policy import get_policy
from repro.core.qarith import QArith
from repro.dist import partition as PT
from repro.dist import transport as TR
from repro.dist.axes import activation_sharding
from repro.launch import analysis as A
from repro.launch import hlo_analysis as HA
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import batch_struct, input_specs
from repro.models import registry as R
from repro.optim import adamw, constant, sgd
from repro.train.step import (make_fsdp_train_step, make_serve_step,
                              make_train_step)
from repro.train.train_state import TrainState


def _sds(tree, spec_tree, mesh):
    """Attach NamedShardings onto a ShapeDtypeStruct tree."""
    from jax.sharding import NamedSharding

    def one(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(one, tree, spec_tree)


def runnable(arch: str, shape_name: str) -> tuple[bool, str]:
    cfg = R.get_config(arch)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: 512k decode skipped (DESIGN.md §5)"
    return True, ""


def lower_cell(arch: str, shape_name: str, mesh, *, policy_name: str = "bf16_sr",
               save_hlo: Path | None = None, moe_strategy: str | None = None,
               attn_chunk: int = 1024,
               placement: PT.Placement | None = None,
               grad_wire: str | None = None, grad_accum: int = 1,
               wire_policy: "TR.WirePolicy | None" = None) -> dict:
    """Lower + compile one (arch × shape × mesh) cell.

    ``grad_wire`` (None keeps the historic implicit-psum lowering)
    selects an explicit gradient transport for train cells — on a
    multi-pod mesh ``"compressed"`` (or any wire-format name, e.g.
    ``"bf12"``/``"e4m3"``) lowers the SR pod wire with its
    error-feedback residuals in the TrainState; ``wire_policy`` adds
    the per-leaf fp32 keep; ``grad_accum`` lowers the k-microbatch
    accumulation scan.
    """
    import dataclasses as _dc
    cfg = R.get_config(arch)
    if moe_strategy:
        cfg = _dc.replace(cfg, moe_strategy=moe_strategy)
    shape = shape_by_name(shape_name)
    policy = get_policy(policy_name)
    qa = QArith(policy)
    chips = mesh.devices.size
    pdtype = policy.param_dtype

    params_shape = jax.eval_shape(lambda: R.init(cfg, jax.random.PRNGKey(0), pdtype))
    pspecs = PT.param_specs(params_shape, cfg, mesh, placement)
    params_in = _sds(params_shape, pspecs, mesh)
    dp = PT.dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    t0 = time.time()
    if shape.kind == "train":
        opt = adamw(policy, b2=0.997, weight_decay=0.01)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        ospecs = PT.state_shardings(pspecs, opt_shape, mesh)
        transport = None
        res_in = None
        hint_dp, hint_dp_size = dp, dp_size
        if grad_wire is not None:
            transport = TR.make_transport(mesh=mesh, placement=placement,
                                          pspecs=pspecs, wire=grad_wire,
                                          wire_policy=wire_policy)
            res_shape = jax.eval_shape(transport.init_residuals, params_shape)
            if res_shape is not None:
                res_in = _sds(res_shape, transport.residual_specs(pspecs),
                              mesh)
            hint_dp, hint_dp_size = transport.hint_axes(mesh)
        state_in = TrainState(
            jax.ShapeDtypeStruct((), jnp.int32),
            params_in, _sds(opt_shape, ospecs, mesh), res_in)
        batch_shape = input_specs(cfg, shape, compute_dtype=policy.compute_dtype)
        bspecs = PT.batch_specs(batch_shape, mesh)
        batch_in = _sds(batch_shape, bspecs, mesh)
        if transport is not None:
            step_fn = make_train_step(cfg, policy, opt, constant(1e-4),
                                      transport=transport,
                                      grad_accum=grad_accum)
        elif placement is not None and placement.fsdp_axis is not None:
            step_fn = make_fsdp_train_step(cfg, policy, opt, constant(1e-4),
                                           pspecs=pspecs, placement=placement,
                                           grad_accum=grad_accum)
        else:
            step_fn = make_train_step(cfg, policy, opt, constant(1e-4),
                                      grad_accum=grad_accum)
        with jax.set_mesh(mesh), activation_sharding(hint_dp, hint_dp_size, "model",
                                       mesh.shape["model"]):
            lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(
                state_in, batch_in, jax.ShapeDtypeStruct((), jnp.int32))
    elif shape.kind == "prefill":
        batch_shape = input_specs(cfg, shape, compute_dtype=policy.compute_dtype)
        bspecs = PT.batch_specs(batch_shape, mesh)
        batch_in = _sds(batch_shape, bspecs, mesh)

        def prefill_step(params, batch):
            logits = R.forward_logits(qa, params, cfg, batch, remat=False)
            return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

        with jax.set_mesh(mesh), activation_sharding(dp, dp_size, "model", mesh.shape["model"]):
            lowered = jax.jit(prefill_step).lower(params_in, batch_in)
    else:  # decode
        B, S = shape.global_batch, shape.seq_len
        batch_shape = batch_struct(cfg, shape, with_labels=False,
                                   compute_dtype=policy.compute_dtype)
        cache_shape = jax.eval_shape(
            lambda p, b: R.make_cache(qa, p, cfg, b, batch_size=B, max_len=S),
            params_shape, batch_shape)
        cspecs = PT.cache_specs(cache_shape, cfg, mesh)
        cache_in = _sds(cache_shape, cspecs, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sspecs = PT.serve_input_specs(B, mesh)
        tok_spec = sspecs["token"]
        token_in = jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                        sharding=NamedSharding(mesh, tok_spec))
        serve = make_serve_step(cfg, policy)
        if cfg.encdec:
            # lock-step layout: scalar position (sinusoidal decoder pos-emb)
            pos_in = jax.ShapeDtypeStruct((), jnp.int32)
            args = [params_in, cache_in, token_in, pos_in]
        else:
            # slot-indexed serving layout: per-slot positions + lane masks,
            # the executable the continuous-batching engine runs
            pos_in = jax.ShapeDtypeStruct(
                (B,), jnp.int32, sharding=NamedSharding(mesh, sspecs["pos"]))
            lane = lambda k: jax.ShapeDtypeStruct(
                (B,), jnp.bool_, sharding=NamedSharding(mesh, sspecs[k]))
            args = [params_in, cache_in, token_in, pos_in,
                    lane("active"), lane("reset")]
        if cfg.family == "vlm":   # vlm is decoder-only → args has 6 entries
            args.append(jax.ShapeDtypeStruct(
                (3, B, 1), jnp.int32,
                sharding=NamedSharding(mesh, P(None, tok_spec[0], None))))
        with jax.set_mesh(mesh), activation_sharding(dp, dp_size, "model", mesh.shape["model"]):
            lowered = jax.jit(serve, donate_argnums=(1,)).lower(*args)

    compiled = lowered.compile()
    compile_s = time.time() - t0

    # --- roofline inputs -------------------------------------------------
    # XLA's cost_analysis counts while bodies ONCE (scan-over-layers would
    # be undercounted ×L) → use the loop-aware HLO walker; keep XLA's
    # numbers for reference.
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes") if hasattr(ma, k)}
    except Exception:
        mem = None
    hlo = compiled.as_text()
    hc = HA.analyze_hlo(hlo)
    flops, bytes_accessed = hc.flops, hc.bytes
    colls = hc.collectives
    coll_bytes = hc.collective_bytes
    if save_hlo:
        save_hlo.write_text(hlo)
    terms = A.roofline_terms(flops, bytes_accessed, coll_bytes, chips)
    mf = A.model_flops(cfg, shape)
    n_devices_arg_bytes = sum(
        int(jnp.dtype(l.dtype).itemsize * __import__("math").prod(l.shape))
        for l in jax.tree_util.tree_leaves(params_shape))
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips, "policy": policy_name,
        "compile_s": round(compile_s, 1),
        "flops_per_device": flops, "bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_bytes,
        "xla_cost_analysis": {"flops": xla_flops, "bytes": xla_bytes},
        "n_whiles": hc.n_whiles, "unknown_trip_whiles": hc.unknown_trip_whiles,
        "collectives": colls,
        "collective_bytes_by_dtype": hc.collective_bytes_by_dtype,
        "memory_analysis": mem,
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_device": mf / chips,
        "useful_flops_ratio": (mf / chips) / flops if flops else None,
        "param_bytes_global": n_devices_arg_bytes,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--policy", default="bf16_sr")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--moe", default=None, choices=[None, "onehot", "grouped", "gather"])
    ap.add_argument("--fsdp", action="store_true",
                    help="FSDP placement: shard params + optimizer state "
                         "over the mesh's data axis")
    ap.add_argument("--grad-wire", default=None,
                    choices=[None, "fp32", "compressed", "bf16", "bf14",
                             "bf12", "bf10", "fp16", "e5m2", "e4m3"],
                    help="explicit gradient transport for train cells "
                         "(compressed = SR-bf16 pod wire with error-"
                         "feedback residuals; a format name picks the "
                         "wire grid, e.g. bf12 or e4m3); default keeps "
                         "the implicit-psum lowering")
    ap.add_argument("--wire-keep-fp32", default=None,
                    help="per-leaf fp32 keep policy spec for a "
                         "compressed wire (see launch.train)")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatch accumulation factor for train cells")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for arch in R.ARCH_IDS:
            for sh in LM_SHAPES:
                for mesh_kind in ("single", "multi"):
                    cells.append((arch, sh.name, mesh_kind))
    else:
        cells.append((args.arch, args.shape, args.mesh))

    meshes = {}
    for arch, shape_name, mesh_kind in cells:
        tag = f"{arch}_{shape_name}_{mesh_kind}{args.tag}".replace("/", "-")
        path = out / f"{tag}.json"
        if args.skip_existing and path.exists():
            print(f"[skip] {tag}")
            continue
        ok, why = runnable(arch, shape_name)
        if not ok:
            path.write_text(json.dumps({"arch": arch, "shape": shape_name,
                                        "mesh": mesh_kind, "skipped": why}))
            print(f"[SKIP] {tag}: {why}")
            continue
        if mesh_kind not in meshes:
            meshes[mesh_kind] = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        try:
            placement = PT.default_placement(meshes[mesh_kind],
                                             fsdp=args.fsdp)
            wp = (TR.WirePolicy.parse(args.wire_keep_fp32)
                  if args.wire_keep_fp32 is not None else None)
            rec = lower_cell(arch, shape_name, meshes[mesh_kind],
                             policy_name=args.policy, moe_strategy=args.moe,
                             placement=placement, grad_wire=args.grad_wire,
                             grad_accum=args.grad_accum, wire_policy=wp,
                             save_hlo=(out / f"{tag}.hlo") if args.save_hlo else None)
            path.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(f"[ok] {tag}: compile={rec['compile_s']}s "
                  f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                  f"collective={r['collective_s']:.3e}s dom={r['dominant']}")
        except Exception as e:
            path.with_suffix(".err").write_text(traceback.format_exc())
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
