"""FSDP placement: spec inference, buffer co-sharding, 8-device parity.

Fast cases run against the lightweight axis-name/size mesh stand-in; the
end-to-end cases (8 virtual devices: 2 data × 2 fsdp × 2 model) run in a
subprocess with their own XLA flags, like tests/test_dist.py.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import get_policy
from repro.dist import fsdp as F
from repro.dist import partition as PT
from repro.models import registry as R
from repro.optim import adamw, sgd

SRC = str(Path(__file__).resolve().parent.parent / "src")


class _SpecMesh:
    """Axis-name/size stand-in: enough mesh surface for spec inference."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


MESH222 = _SpecMesh(data=2, fsdp=2, model=2)
FSDP2 = PT.Placement(fsdp_axis="fsdp")


def _params(arch="qwen2.5-3b", dtype=jnp.bfloat16):
    cfg = R.get_config(arch).reduced()
    return cfg, jax.eval_shape(
        lambda: R.init(cfg, jax.random.PRNGKey(0), dtype))


# ---------------------------------------------------------------------------
# Placement / spec inference
# ---------------------------------------------------------------------------

class TestPlacement:
    def test_default_placement(self):
        assert PT.default_placement(MESH222) == PT.Placement()
        assert PT.default_placement(MESH222, fsdp=True).fsdp_axis == "fsdp"
        # no dedicated fsdp axis → classic ZeRO layout over `data`
        assert PT.default_placement(_SpecMesh(data=4, model=2),
                                    fsdp=True).fsdp_axis == "data"

    def test_sizes_treat_absent_axes_as_one(self):
        pl = PT.Placement(fsdp_axis="fsdp")
        assert pl.fsdp_size(_SpecMesh(data=4, model=2)) == 1
        assert pl.fsdp_size(MESH222) == 2
        assert pl.tp_size(_SpecMesh(data=8)) == 1

    def test_no_placement_matches_legacy_specs(self):
        cfg, params = _params()
        legacy = PT.param_specs(params, cfg, MESH222)
        assert legacy == PT.param_specs(params, cfg, MESH222, PT.Placement())


class TestFsdpSpecs:
    def test_largest_divisible_dim_shards(self):
        tree = {"big": jax.ShapeDtypeStruct((4, 8), jnp.float32),
                "vec": jax.ShapeDtypeStruct((6,), jnp.float32),
                "odd": jax.ShapeDtypeStruct((3, 5), jnp.float32),
                "scalar": jax.ShapeDtypeStruct((), jnp.float32)}
        specs = PT.param_specs(tree, None, _SpecMesh(fsdp=2, model=1), FSDP2)
        assert specs["big"] == P(None, "fsdp")     # 8 > 4
        assert specs["vec"] == P("fsdp")
        assert specs["odd"] == P(None, None)       # indivisible → replicate
        assert specs["scalar"] == P()

    def test_tp_dim_never_doubles_as_fsdp_dim(self):
        cfg, params = _params()
        specs = PT.param_specs(params, cfg, MESH222, FSDP2)
        for path, spec in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, P)):
            axes = [a for a in spec if a is not None]
            assert len(axes) == len(set(axes)), (path, spec)

    def test_every_arch_fsdp_dims_divide(self):
        for arch in R.ARCH_IDS:
            cfg, params = _params(arch)
            specs = PT.param_specs(params, cfg, MESH222, FSDP2)
            for leaf, spec in zip(jax.tree_util.tree_leaves(params),
                                  jax.tree_util.tree_leaves(
                                      specs, is_leaf=lambda x: isinstance(x, P))):
                assert len(spec) == len(leaf.shape), (arch, leaf.shape, spec)
                for dim, axis in enumerate(spec):
                    if axis == "fsdp":
                        assert leaf.shape[dim] % 2 == 0, (arch, leaf.shape)

    def test_gather_specs_drop_only_the_fsdp_axis(self):
        cfg, params = _params()
        specs = PT.param_specs(params, cfg, MESH222, FSDP2)
        gathered = F.gather_specs(specs, FSDP2)
        for s, g in zip(jax.tree_util.tree_leaves(
                            specs, is_leaf=lambda x: isinstance(x, P)),
                        jax.tree_util.tree_leaves(
                            gathered, is_leaf=lambda x: isinstance(x, P))):
            assert len(s) == len(g)
            for se, ge in zip(s, g):
                assert ge == (None if se == "fsdp" else se)

    def test_unshard_spec_handles_tuple_entries(self):
        pl = PT.Placement(fsdp_axis="fsdp")
        assert F.unshard_spec(P(("data", "fsdp"), "model"), pl) == \
            P("data", "model")
        assert F.unshard_spec(P(("fsdp",), None), pl) == P(None, None)


# ---------------------------------------------------------------------------
# Kahan / SR buffer co-sharding (property-style over archs × optimizers)
# ---------------------------------------------------------------------------

class TestBufferCoSharding:
    """Every param-shaped sub-tree of the optimizer state must carry specs
    identical leaf-for-leaf to the parameter specs under FSDP placement —
    the invariant that keeps Algorithm 5's compensation local."""

    ARCHS = ("qwen2.5-3b", "recurrentgemma-2b", "falcon-mamba-7b")

    def _check(self, params, opt, pspecs):
        opt_shape = jax.eval_shape(opt.init, params)
        ospecs = PT.state_shardings(pspecs, opt_shape, MESH222)
        pdef = jax.tree_util.tree_structure(params)
        flat_p = jax.tree_util.tree_leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P))
        n_aligned = 0
        for field in opt_shape._fields:
            sub = getattr(opt_shape, field)
            if sub is not None and jax.tree_util.tree_structure(sub) == pdef:
                got = jax.tree_util.tree_leaves(
                    getattr(ospecs, field), is_leaf=lambda x: isinstance(x, P))
                assert got == flat_p, field
                n_aligned += 1
            elif sub is not None:
                assert getattr(ospecs, field) == P(), field  # scalars replicate
        return n_aligned

    @pytest.mark.parametrize("arch", ARCHS)
    def test_adamw_kahan_buffers_co_shard(self, arch):
        policy = get_policy("bf16_sr_kahan")
        cfg, params = _params(arch, policy.param_dtype)
        pspecs = PT.param_specs(params, cfg, MESH222, FSDP2)
        n = self._check(params, adamw(policy, b2=0.997), pspecs)
        assert n == 3  # m, v, kahan_c all param-shaped

    @pytest.mark.parametrize("arch", ARCHS)
    def test_sgd_kahan_buffers_co_shard(self, arch):
        policy = get_policy("bf16_sr_kahan")
        cfg, params = _params(arch, policy.param_dtype)
        pspecs = PT.param_specs(params, cfg, MESH222, FSDP2)
        n = self._check(params, sgd(policy), pspecs)
        assert n == 2  # momentum, kahan_c


# ---------------------------------------------------------------------------
# mesh builders
# ---------------------------------------------------------------------------

class TestMeshValidation:
    def test_unknown_axis_rejected(self):
        from repro.launch import mesh as LM
        with pytest.raises(ValueError, match="unknown mesh axis"):
            LM.make_mesh((1,), ("bogus",))
        with pytest.raises(ValueError, match="duplicate"):
            LM.make_mesh((1, 1), ("data", "data"))

    def test_production_fsdp_must_divide(self):
        from repro.launch.mesh import make_production_mesh
        with pytest.raises(ValueError, match="divide"):
            make_production_mesh(fsdp=3)

    def test_local_mesh_single_device(self):
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh(1, 1)
        assert mesh.axis_names == ("data", "model")


# ---------------------------------------------------------------------------
# end-to-end: 8 virtual devices (2 data × 2 fsdp × 2 model), subprocess
# ---------------------------------------------------------------------------

def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


@pytest.mark.dist
def test_fsdp_step_matches_single_device_and_halves_memory():
    """Acceptance: per-device params + optimizer state (incl. Kahan) shrink
    by ~the FSDP factor vs DP replication, and the 2×2×2 FSDP train step
    matches the single-device step to bf16 tolerance."""
    out = _run("""
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
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

        s1 = make_train_state(params, opt)
        step1 = make_train_step(cfg, policy, opt, constant(1e-3), attn_chunk=8)
        s1b, m1 = jax.jit(step1)(s1, batch, 0)

        mesh = make_local_mesh(2, 2, fsdp=2)
        pl = PT.default_placement(mesh, fsdp=True)
        pspecs = PT.param_specs(params, cfg, mesh, pl)
        s8 = jax.device_put(make_train_state(params, opt),
                            F.train_state_shardings(
                                make_train_state(params, opt), cfg, mesh, pl))
        sdp = jax.device_put(make_train_state(params, opt),
                             F.train_state_shardings(
                                 make_train_state(params, opt), cfg, mesh,
                                 PT.Placement()))
        print("bytes_ratio", F.per_device_bytes((sdp.params, sdp.opt_state))
              / F.per_device_bytes((s8.params, s8.opt_state)))

        step8 = make_fsdp_train_step(cfg, policy, opt, constant(1e-3),
                                     pspecs=pspecs, placement=pl, attn_chunk=8)
        with jax.set_mesh(mesh), activation_sharding(PT.dp_axes(mesh), PT.dp_size(mesh),
                                       "model", 2):
            s8b, m8 = jax.jit(step8)(s8, batch, 0)
        print("loss1", float(m1["loss"]), "loss8", float(m8["loss"]))
        for name, t1, t8 in (("params", s1b.params, s8b.params),
                             ("kahan", s1b.opt_state.kahan_c,
                              s8b.opt_state.kahan_c)):
            d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                    for a, b in zip(jax.tree_util.tree_leaves(t1),
                                    jax.tree_util.tree_leaves(t8)))
            print("maxdiff_" + name, d)
        # the updated Kahan buffer stays co-sharded with its parameter
        co = all(p.sharding == k.sharding
                 for p, k in zip(jax.tree_util.tree_leaves(s8b.params),
                                 jax.tree_util.tree_leaves(
                                     s8b.opt_state.kahan_c)))
        print("co_sharded", int(co))
    """)
    toks = out.split()
    vals = {toks[i]: float(toks[i + 1]) for i in range(0, len(toks) - 1, 2)
            if toks[i].replace("_", "").isalnum() and not toks[i][0].isdigit()}
    # params + optimizer state shrink by ~the FSDP factor (2); the tail
    # of non-divisible leaves keeps it from being exactly 2.0
    assert vals["bytes_ratio"] > 1.7, out
    assert abs(vals["loss1"] - vals["loss8"]) < 0.05, out
    # weights AND Kahan compensation agree to bf16 tolerance (collectives
    # reorder f32 sums; SR noise is keyed identically per leaf)
    assert vals["maxdiff_params"] < 0.05, out
    assert vals["maxdiff_kahan"] < 0.05, out
    assert vals["co_sharded"] == 1, out


@pytest.mark.dist
def test_fsdp_elastic_resume_reshards_onto_current_mesh():
    """Checkpoint written by an FSDP run restores through run_training's
    state_shardings= path onto a *different* placement (DP) — the elastic
    resume contract, Kahan buffers included."""
    out = _run("""
        import tempfile
        import jax, jax.numpy as jnp
        from repro.core import get_policy
        from repro.dist import partition as PT
        from repro.dist import fsdp as F
        from repro.launch.mesh import make_local_mesh
        from repro.models import registry as R
        from repro.optim import adamw
        from repro.train.checkpoint import CheckpointManager
        from repro.train.train_state import make_train_state

        policy = get_policy("bf16_sr_kahan")
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
        opt = adamw(policy, b2=0.997)
        mesh = make_local_mesh(2, 2, fsdp=2)
        pl = PT.default_placement(mesh, fsdp=True)
        state = jax.device_put(make_train_state(params, opt),
                               F.train_state_shardings(
                                   make_train_state(params, opt), cfg, mesh, pl))

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, every_steps=1)
            mgr.maybe_save(1, state, force=True)
            # resume onto a shrunk mesh with a different placement
            mesh2 = make_local_mesh(2, 2)
            shard2 = F.train_state_shardings(
                make_train_state(params, opt), cfg, mesh2, PT.Placement())
            got, at = mgr.restore_latest(make_train_state(params, opt),
                                         shardings=shard2)
            import numpy as np
            ok = all(np.array_equal(jax.device_get(a), jax.device_get(b))
                     for a, b in zip(jax.tree_util.tree_leaves(state),
                                     jax.tree_util.tree_leaves(got)))
            kc = jax.tree_util.tree_leaves(got.opt_state.kahan_c)[0]
            print("restored_step", at)
            print("values_ok", int(ok))
            print("resharded", int(kc.sharding.mesh.shape == mesh2.shape))
    """)
    vals = {l.split()[0]: float(l.split()[1])
            for l in out.strip().splitlines()}
    assert vals["restored_step"] == 1, out
    assert vals["values_ok"] == 1, out
    assert vals["resharded"] == 1, out


@pytest.mark.dist
def test_grad_accum_keeps_working_copy_gather_out_of_the_scan():
    """Lowered-HLO regression for the one-gather-per-step contract:
    ``grad_accum=k`` must not multiply the FSDP working-copy all-gather
    bytes (a gather sunk into the microbatch scan would show up ~k×).
    Also pins the reduce-scatter→all-reduce+slice fallback detector to
    the CPU partitioner output it was calibrated against."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.core import get_policy
        from repro.dist import partition as PT
        from repro.dist import fsdp as F
        from repro.dist.axes import activation_sharding
        from repro.launch.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_local_mesh
        from repro.models import registry as R
        from repro.optim import adamw, constant
        from repro.train.step import make_fsdp_train_step
        from repro.train.train_state import make_train_state

        policy = get_policy("bf16_sr_kahan")
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
        opt = adamw(policy, b2=0.997)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 8), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

        mesh = make_local_mesh(2, 2, fsdp=2)
        pl = PT.default_placement(mesh, fsdp=True)
        pspecs = PT.param_specs(params, cfg, mesh, pl)
        state = jax.device_put(make_train_state(params, opt),
                               F.train_state_shardings(
                                   make_train_state(params, opt), cfg,
                                   mesh, pl))
        bspecs = PT.batch_specs(batch, mesh)
        batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
                 for k, v in batch.items()}

        for ga in (1, 4):
            step = make_fsdp_train_step(cfg, policy, opt, constant(1e-3),
                                        pspecs=pspecs, placement=pl,
                                        attn_chunk=8, grad_accum=ga)
            with jax.set_mesh(mesh), activation_sharding(PT.dp_axes(mesh),
                                           PT.dp_size(mesh), "model", 2):
                text = jax.jit(step).lower(state, batch, 0).compile().as_text()
            c = analyze_hlo(text)
            ag = c.collectives.get("all-gather", {"count": 0, "bytes": 0})
            print(f"ga{ga}_ag_bytes", int(ag["bytes"]))
            print(f"ga{ga}_rs_fallbacks", c.rs_fallbacks)
    """)
    vals = {l.split()[0]: float(l.split()[1])
            for l in out.strip().splitlines()}
    assert vals["ga1_ag_bytes"] > 0, out
    # trip-count-weighted gather bytes stay flat as grad_accum scales
    assert vals["ga4_ag_bytes"] < 1.5 * vals["ga1_ag_bytes"], out
    # the CPU partitioner lowers the gradient reduce-scatter as
    # all-reduce + partition-id slice; the detector must label it
    assert vals["ga1_rs_fallbacks"] >= 1, out
