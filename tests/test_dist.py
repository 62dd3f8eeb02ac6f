"""Distribution tests (8 virtual host devices via subprocess)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.dist

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """Same seed/data: 8-device (4 data × 2 model) step == 1-device step."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_local_mesh
        from repro.core import get_policy
        from repro.dist import partition as PT
        from repro.dist.axes import activation_sharding
        from repro.models import registry as R
        from repro.optim import adamw, constant
        from repro.train.step import make_train_step
        from repro.train.train_state import make_train_state
        from jax.sharding import NamedSharding

        policy = get_policy("bf16_sr")
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
        opt = adamw(policy, b2=0.997)
        step_fn = make_train_step(cfg, policy, opt, constant(1e-3), attn_chunk=8)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

        # single device
        s1 = make_train_state(params, opt)
        s1b, m1 = jax.jit(step_fn)(s1, batch, 0)

        # 8 devices
        mesh = make_local_mesh(4, 2)
        pspecs = PT.param_specs(params, cfg, mesh)
        pshard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                        is_leaf=lambda x: hasattr(x, "_normalized_spec_for_aval"))
        params8 = jax.device_put(params, pshard)
        s8 = make_train_state(params8, opt)
        with jax.set_mesh(mesh), activation_sharding(("data",), 4, "model", 2):
            s8b, m8 = jax.jit(step_fn)(s8, batch, 0)
        print("loss1", float(m1["loss"]), "loss8", float(m8["loss"]))
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(s1b.params),
                                jax.tree_util.tree_leaves(s8b.params)))
        print("maxdiff", d)
    """)
    toks = out.split()
    vals = {toks[i]: float(toks[i + 1]) for i in range(0, len(toks) - 1, 2)
            if toks[i].replace("_", "").isalnum() and not toks[i][0].isdigit()}
    assert abs(vals["loss1"] - vals["loss8"]) < 0.05, out
    # weights agree to bf16 tolerance (collectives reorder f32 sums; SR
    # noise is keyed identically per leaf)
    assert vals["maxdiff"] < 0.05, out


def test_compressed_psum_unbiased_and_bf16_wire():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_local_mesh
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_compress import compressed_psum, init_residual
        mesh = make_local_mesh(8)
        g = {"w": jnp.linspace(-1, 1, 4096, dtype=jnp.float32)}
        res = init_residual(g)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def run(gl, rl, seed):
            out, new_res = compressed_psum(gl, rl, jax.random.PRNGKey(0), "data")
            return out, new_res

        out, new_res = run(g, res, jnp.int32(0))
        err = float(jnp.max(jnp.abs(out["w"] - g["w"])))
        print("err", err)
        # residual carries the quantization error exactly
        print("res_mag", float(jnp.max(jnp.abs(new_res["w"]))))
    """)
    vals = {l.split()[0]: float(l.split()[1]) for l in out.strip().splitlines()}
    # mean of 8 SR-quantized replicas: error ≪ one bf16 ulp
    assert vals["err"] < 8e-3, out
    assert vals["res_mag"] <= 2 ** -8, out


def test_dryrun_small_mesh_compiles_train_and_decode():
    """End-to-end lower+compile on a 4×2 mesh with tiny shapes: proves the
    dry-run machinery beyond the big background sweep."""
    out = _run("""
        import jax
        from repro.launch.mesh import make_local_mesh
        from repro.configs import base as CB
        small_train = CB.ShapeConfig("train_4k", 128, 8, "train")
        small_dec  = CB.ShapeConfig("decode_32k", 128, 8, "decode")
        orig = CB.shape_by_name
        CB.shape_by_name = lambda n: {"train_4k": small_train,
                                      "decode_32k": small_dec}.get(n) or orig(n)
        import repro.launch.dryrun as DR
        DR.shape_by_name = CB.shape_by_name
        mesh = make_local_mesh(4, 2)
        for arch, shape in [("yi-9b", "train_4k"),
                            ("falcon-mamba-7b", "decode_32k"),
                            ("recurrentgemma-2b", "train_4k"),
                            ("whisper-base", "decode_32k")]:
            rec = DR.lower_cell(arch, shape, mesh)
            assert rec["flops_per_device"] >= 0
            print("ok", arch, shape, rec["roofline"]["dominant"])
    """)
    assert out.count("ok ") == 4, out
