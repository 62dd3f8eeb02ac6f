"""Fast in-process unit tests for `repro.dist` (1 CPU device, seconds).

The spec-inference rules only read mesh axis *names* and sizes, so most
cases run against a lightweight stand-in mesh — no multi-device backend
needed. The final class exercises real 8-virtual-device placement and is
marked `dist` (runs under `-m dist`, skips otherwise).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import get_policy
from repro.dist import partition as PT
from repro.dist.axes import (activation_sharding, current_sharding,
                             padded_head_count, shard_batch, shard_heads)
from repro.launch.mesh import make_local_mesh
from repro.models import registry as R
from repro.optim import adamw, sgd


class _SpecMesh:
    """Axis-name/size stand-in: enough mesh surface for spec inference."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


MESH42 = _SpecMesh(data=4, model=2)


def _leaf_specs(pspecs):
    return {jax.tree_util.keystr(path): spec for path, spec in
            jax.tree_util.tree_leaves_with_path(pspecs)}


# ---------------------------------------------------------------------------
# axes helpers
# ---------------------------------------------------------------------------

class TestAxes:
    def test_padded_head_count_no_context(self):
        assert padded_head_count(10) == 10

    @pytest.mark.parametrize("heads,mp,expect",
                             [(10, 2, 10), (10, 4, 12), (10, 3, 12),
                              (16, 16, 16), (1, 8, 8)])
    def test_padded_head_count_rounds_up(self, heads, mp, expect):
        with activation_sharding(("data",), 1, "model", mp):
            assert padded_head_count(heads) == expect

    def test_shard_helpers_noop_outside_context(self):
        x = jnp.ones((4, 6, 8))
        assert shard_heads(x, 2) is x
        assert shard_batch(x) is x

    def test_shard_helpers_noop_outside_mesh(self):
        # context active but no mesh installed → still an exact no-op
        x = jnp.ones((4, 6, 8))
        with activation_sharding(("data",), 2, "model", 2):
            assert shard_heads(x, 2) is x
            assert shard_batch(x) is x

    def test_context_nests_and_restores(self):
        assert current_sharding() is None
        with activation_sharding(("data",), 4, "model", 2) as outer:
            assert current_sharding() is outer
            with activation_sharding(("pod", "data"), 8, "model", 16) as inner:
                assert current_sharding() is inner
                assert current_sharding().dp_axes == ("pod", "data")
            assert current_sharding() is outer
        assert current_sharding() is None


# ---------------------------------------------------------------------------
# partition: dp axes + param specs
# ---------------------------------------------------------------------------

class TestPartition:
    def test_dp_axes_excludes_model(self):
        assert PT.dp_axes(MESH42) == ("data",)
        assert PT.dp_size(MESH42) == 4
        multi = _SpecMesh(pod=2, data=16, model=16)
        assert PT.dp_axes(multi) == ("pod", "data")
        assert PT.dp_size(multi) == 32

    def test_param_specs_transformer(self):
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = jax.eval_shape(
            lambda: R.init(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
        specs = _leaf_specs(PT.param_specs(params, cfg, MESH42))
        # column-parallel: output features on model (stacked leading L dim)
        assert specs["['layers']['b0']['mixer']['wq']['kernel']"] == \
            P(None, None, "model")
        assert specs["['layers']['b0']['ffn']['w_gate']"] == \
            P(None, None, "model")
        # row-parallel: input features on model
        assert specs["['layers']['b0']['mixer']['wo']['kernel']"] == \
            P(None, "model", None)
        assert specs["['layers']['b0']['ffn']['w_down']"] == \
            P(None, "model", None)
        # embeddings shard vocab rows; norms and biases replicate
        assert specs["['embed']['embedding']"] == P("model", None)
        assert specs["['final_norm']['scale']"] == P(None)
        assert specs["['layers']['b0']['mixer']['wq']['bias']"] == P(None, None)

    def test_param_specs_every_arch_matches_leaf_ranks(self):
        for arch in R.ARCH_IDS:
            cfg = R.get_config(arch).reduced()
            params = jax.eval_shape(
                lambda c=cfg: R.init(c, jax.random.PRNGKey(0), jnp.bfloat16))
            pspecs = PT.param_specs(params, cfg, MESH42)
            leaves = jax.tree_util.tree_leaves(params)
            specs = jax.tree_util.tree_leaves(pspecs)
            assert len(leaves) == len(specs)
            for leaf, spec in zip(leaves, specs):
                assert len(spec) == len(leaf.shape), (arch, leaf.shape, spec)
                for dim, axis in enumerate(spec):
                    if axis is not None:
                        assert leaf.shape[dim] % 2 == 0, (arch, leaf.shape)

    def test_param_specs_nondivisible_replicates(self):
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = jax.eval_shape(
            lambda: R.init(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
        # model axis of 7 divides none of the reduced dims → all replicated
        pspecs = PT.param_specs(params, cfg, _SpecMesh(data=1, model=7))
        assert all(all(a is None for a in s)
                   for s in jax.tree_util.tree_leaves(pspecs))


# ---------------------------------------------------------------------------
# partition: optimizer state / batch / cache specs
# ---------------------------------------------------------------------------

class TestStateShardings:
    @pytest.mark.parametrize("policy_name", ["bf16_sr", "bf16_sr_kahan"])
    def test_adamw_state_aligns_with_params(self, policy_name):
        policy = get_policy(policy_name)
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = jax.eval_shape(
            lambda: R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype))
        opt = adamw(policy, b2=0.997)
        opt_shape = jax.eval_shape(opt.init, params)
        pspecs = PT.param_specs(params, cfg, MESH42)
        ospecs = PT.state_shardings(pspecs, opt_shape, MESH42)
        flat_p = jax.tree_util.tree_leaves(pspecs)
        # moments (and the Kahan compensation buffer, when the policy has
        # one) shard exactly like their parameters
        assert jax.tree_util.tree_leaves(ospecs.m) == flat_p
        assert jax.tree_util.tree_leaves(ospecs.v) == flat_p
        if policy.kahan:
            assert jax.tree_util.tree_leaves(ospecs.kahan_c) == flat_p
        else:
            assert ospecs.kahan_c is None
        # bias-correction scalars replicate
        assert ospecs.c1 == P() and ospecs.c2 == P()

    def test_sgd_state_aligns_with_params(self):
        policy = get_policy("bf16_sr_kahan")
        cfg = R.get_config("recurrentgemma-2b").reduced()
        params = jax.eval_shape(
            lambda: R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype))
        opt = sgd(policy)
        opt_shape = jax.eval_shape(opt.init, params)
        pspecs = PT.param_specs(params, cfg, MESH42)
        ospecs = PT.state_shardings(pspecs, opt_shape, MESH42)
        assert jax.tree_util.tree_leaves(ospecs.momentum) == \
            jax.tree_util.tree_leaves(pspecs)
        assert jax.tree_util.tree_leaves(ospecs.kahan_c) == \
            jax.tree_util.tree_leaves(pspecs)


class TestBatchCacheSpecs:
    def test_batch_specs_lm_and_vlm(self):
        sds = jax.ShapeDtypeStruct
        batch = {"tokens": sds((8, 16), jnp.int32),
                 "labels": sds((8, 16), jnp.int32),
                 "mrope_positions": sds((3, 8, 16), jnp.int32)}
        specs = PT.batch_specs(batch, MESH42)
        assert specs["tokens"] == P(("data",), None)
        assert specs["labels"] == P(("data",), None)
        # (3, B, S) layout: batch lives in dim 1
        assert specs["mrope_positions"] == P(None, ("data",), None)

    def test_batch_specs_nondivisible_batch_replicates(self):
        sds = jax.ShapeDtypeStruct
        specs = PT.batch_specs({"tokens": sds((6, 16), jnp.int32)}, MESH42)
        assert specs["tokens"] == P(None, None)

    def test_cache_specs_kv_and_ssm(self):
        from repro.core.qarith import QArith
        policy = get_policy("bf16_sr")
        qa = QArith(policy)
        for arch in ("qwen2.5-3b", "falcon-mamba-7b", "recurrentgemma-2b"):
            cfg = R.get_config(arch).reduced()
            params = jax.eval_shape(
                lambda c=cfg: R.init(c, jax.random.PRNGKey(0), jnp.bfloat16))
            batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
            cache = jax.eval_shape(
                lambda p, c=cfg: R.make_cache(qa, p, c, batch, batch_size=8,
                                              max_len=16), params)
            cspecs = PT.cache_specs(cache, cfg, MESH42)
            for (path, leaf), spec in zip(
                    jax.tree_util.tree_leaves_with_path(cache),
                    jax.tree_util.tree_leaves(cspecs)):
                assert len(spec) == len(leaf.shape), (arch, path, spec)
                # stacked-layer caches carry batch in dim 1
                assert spec[1] == "data", (arch, path, spec)
                for dim, axis in enumerate(spec):
                    if axis == "model":
                        assert leaf.shape[dim] % 2 == 0, (arch, path, spec)


# ---------------------------------------------------------------------------
# multihost initialize: configuration validation (no cluster needed)
# ---------------------------------------------------------------------------

class TestMultihostInit:
    def _clear_env(self, monkeypatch):
        from repro.dist import multihost as MH
        for k in (MH.ENV_COORDINATOR, MH.ENV_NUM_PROCESSES,
                  MH.ENV_PROCESS_ID):
            monkeypatch.delenv(k, raising=False)
        return MH

    def test_noop_when_unconfigured(self, monkeypatch):
        MH = self._clear_env(monkeypatch)
        assert MH.initialize() is False

    def test_noop_single_process(self, monkeypatch):
        MH = self._clear_env(monkeypatch)
        assert MH.initialize(coordinator="127.0.0.1:9",
                             num_processes=1) is False

    def test_missing_process_id_raises_clearly(self, monkeypatch):
        """Regression: coordinator + num_processes without a rank fell
        through to jax.distributed.initialize(process_id=None), which
        dies with an opaque backend error outside auto-detecting cluster
        environments. Now a ValueError names the missing flag/env var."""
        MH = self._clear_env(monkeypatch)
        with pytest.raises(ValueError, match="REPRO_PROCESS_ID"):
            MH.initialize(coordinator="127.0.0.1:9", num_processes=2)


# ---------------------------------------------------------------------------
# real placement on 8 virtual devices (in-process; runs under `-m dist`)
# ---------------------------------------------------------------------------

@pytest.mark.dist
class TestInProcessPlacement:
    def test_param_put_and_activation_constraints(self, eight_virtual_devices):
        from jax.sharding import NamedSharding
        mesh = make_local_mesh(4, 2, devices=eight_virtual_devices)
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = R.init(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
        pspecs = PT.param_specs(params, cfg, mesh)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs)
        params8 = jax.device_put(params, shardings)
        wq = params8["layers"]["b0"]["mixer"]["wq"]["kernel"]
        assert wq.sharding.spec == P(None, None, "model")

        @jax.jit
        def f(x):
            return shard_batch(shard_heads(x, 2))

        x = jnp.ones((8, 16, 4, 32))
        with jax.set_mesh(mesh), activation_sharding(("data",), 4, "model", 2):
            y = f(x)
        assert y.sharding.spec[0] in ("data", ("data",))
        assert y.sharding.spec[2] == "model"
