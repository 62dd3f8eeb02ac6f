"""Kernel-backed optimizers ≡ reference optimizers (Appendix B path)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import get_policy
from repro.launch.mesh import make_mesh
from repro.optim import adamw, sgd
from repro.optim.fused import fused_adamw_optimizer, fused_sgd_optimizer


def _params(key, shapes=((64,), (32, 16), (7, 3, 5))):
    ks = jax.random.split(key, len(shapes))
    return {f"p{i}": jax.random.normal(k, s, jnp.bfloat16)
            for i, (k, s) in enumerate(zip(ks, shapes))}


def _close(a, b, scale=None):
    af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
    mag = jnp.maximum(jnp.abs(bf), 2.0 ** -126)
    if scale is not None:
        mag = jnp.maximum(mag, jnp.abs(scale.astype(jnp.float32)))
    assert bool(jnp.all(jnp.abs(af - bf) <= 2.0 ** -7 * mag + 1e-12))


@pytest.mark.parametrize("pol", ["bf16_sr", "bf16_kahan", "bf16_standard"])
def test_fused_sgd_matches_reference(pol):
    policy = get_policy(pol)
    ref_opt = sgd(policy, momentum=0.9, weight_decay=1e-4)
    kern_opt = fused_sgd_optimizer(policy, momentum=0.9, weight_decay=1e-4)
    params = _params(jax.random.PRNGKey(0))
    grads = _params(jax.random.PRNGKey(1))
    s_ref = ref_opt.init(params)
    s_k = kern_opt.init(params)
    key = jax.random.PRNGKey(2)
    p_ref, s_ref = ref_opt.update(grads, s_ref, params, step=0, key=key, lr=0.01)
    p_k, s_k = kern_opt.update(grads, s_k, params, step=0, key=key, lr=0.01)
    if policy.update_rounding == "stochastic":
        # independent RNG partitioning → compare statistically: same grid,
        # within one ulp of each other
        for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                        jax.tree_util.tree_leaves(p_k)):
            _close(a, b)
    else:
        for (n, a), b in zip(sorted(p_ref.items()),
                             [p_k[k] for k in sorted(p_k)]):
            _close(a, b, scale=params[n])


@pytest.mark.parametrize("pol", ["bf16_kahan", "bf16_standard"])
def test_fused_adamw_matches_reference(pol):
    policy = get_policy(pol)
    ref_opt = adamw(policy, b2=0.997)
    kern_opt = fused_adamw_optimizer(policy, b2=0.997)
    params = _params(jax.random.PRNGKey(3))
    grads = _params(jax.random.PRNGKey(4))
    s_ref = ref_opt.init(params)
    s_k = kern_opt.init(params)
    key = jax.random.PRNGKey(5)
    for step in range(3):
        params_r, s_ref = ref_opt.update(grads, s_ref, params, step=step,
                                         key=key, lr=1e-3)
        params_k, s_k = kern_opt.update(grads, s_k, params, step=step,
                                        key=key, lr=1e-3)
    for n in params:
        _close(params_r[n], params_k[n], scale=params[n])
        _close(s_ref.m[n], s_k.m[n],
               scale=grads[n])            # FMA ties on cancellation


def test_fused_rejects_non_bf16_policy():
    with pytest.raises(ValueError):
        fused_sgd_optimizer(get_policy("fp32"))
    with pytest.raises(ValueError):
        fused_adamw_optimizer(get_policy("bf14_sr"))


def test_fused_sgd_trains_lstsq():
    """The kernel path reproduces the paper's fix end-to-end."""
    policy = get_policy("bf16_kahan")
    opt = fused_sgd_optimizer(policy, momentum=0.0)
    key = jax.random.PRNGKey(0)
    d, n = 8, 128
    X = jax.random.normal(key, (n, d))
    w_star = jax.random.uniform(jax.random.PRNGKey(1), (d,), minval=50., maxval=100.)
    y = X @ w_star
    params = {"w": jnp.zeros((d,), jnp.bfloat16)}
    state = opt.init(params)
    for i in range(1500):
        k = jax.random.fold_in(key, i)
        idx = jax.random.randint(jax.random.fold_in(k, 0), (), 0, n)
        g = {"w": ((X[idx] @ params["w"].astype(jnp.float32) - y[idx])
                   * X[idx]).astype(jnp.bfloat16)}
        params, state = opt.update(g, state, params, step=i, key=k, lr=0.01)
    mse = float(jnp.mean((X @ params["w"].astype(jnp.float32) - y) ** 2))
    assert mse < 5.0, mse


# ---------------------------------------------------------------------------
# Shard-local mode (mesh= / pspecs=): the update runs on local FSDP
# shards inside shard_map — 8 virtual devices, -m dist
# ---------------------------------------------------------------------------

@pytest.mark.dist
class TestShardLocal:
    def _setup(self, pol):
        from jax.sharding import NamedSharding, PartitionSpec as P
        policy = get_policy(pol)
        # "w" and "b" take the kernel's flat path on their local shards,
        # "u" its native one: the shard (8, 256) keeps a 128-multiple last dim
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 32),
                                         jnp.bfloat16),
                  "b": jax.random.normal(jax.random.PRNGKey(1), (32,),
                                         jnp.bfloat16),
                  "u": jax.random.normal(jax.random.PRNGKey(6), (64, 256),
                                         jnp.bfloat16)}
        grads = {"w": jax.random.normal(jax.random.PRNGKey(2), (64, 32),
                                        jnp.bfloat16),
                 "b": jax.random.normal(jax.random.PRNGKey(3), (32,),
                                        jnp.bfloat16),
                 "u": jax.random.normal(jax.random.PRNGKey(7), (64, 256),
                                        jnp.bfloat16)}
        mesh = make_mesh((8,), ("fsdp",))
        pspecs = {"w": P("fsdp", None), "b": P("fsdp"), "u": P("fsdp", None)}
        sharded = {k: jax.device_put(v, NamedSharding(mesh, pspecs[k]))
                   for k, v in params.items()}
        gsharded = {k: jax.device_put(v, NamedSharding(mesh, pspecs[k]))
                    for k, v in grads.items()}
        return policy, params, grads, mesh, pspecs, sharded, gsharded

    def test_nearest_bitexact_vs_global(self, eight_virtual_devices):
        """Nearest rounding is shard-oblivious: the shard-local update
        must be bit-for-bit the global fused update."""
        (policy, params, grads, mesh, pspecs,
         sharded, gsharded) = self._setup("bf16_kahan")
        g_opt = fused_adamw_optimizer(policy, b2=0.997)
        l_opt = fused_adamw_optimizer(policy, b2=0.997, mesh=mesh,
                                      pspecs=pspecs)
        key = jax.random.PRNGKey(4)
        pg, _ = g_opt.update(grads, g_opt.init(params), params,
                             step=0, key=key, lr=1e-3)
        with jax.set_mesh(mesh):
            pl_, _ = l_opt.update(gsharded, l_opt.init(sharded), sharded,
                                  step=0, key=key, lr=1e-3)
        for k in params:
            assert bool(jnp.all(pg[k] == jax.device_get(pl_[k]))), k

    def test_sr_deterministic_and_close(self, eight_virtual_devices):
        """SR folds the shard index into the key: not bitwise vs the
        global draw, but deterministic and within 1 ulp of it."""
        (policy, params, grads, mesh, pspecs,
         sharded, gsharded) = self._setup("bf16_sr")
        l_opt = fused_sgd_optimizer(policy, momentum=0.9, mesh=mesh,
                                    pspecs=pspecs)
        g_opt = fused_sgd_optimizer(policy, momentum=0.9)
        key = jax.random.PRNGKey(5)
        with jax.set_mesh(mesh):
            a, _ = l_opt.update(gsharded, l_opt.init(sharded), sharded,
                                step=0, key=key, lr=1e-2)
            b, _ = l_opt.update(gsharded, l_opt.init(sharded), sharded,
                                step=0, key=key, lr=1e-2)
        for k in params:
            assert bool(jnp.all(jax.device_get(a[k])
                                == jax.device_get(b[k]))), k
        pg, _ = g_opt.update(grads, g_opt.init(params), params,
                             step=0, key=key, lr=1e-2)
        for k in params:
            _close(jax.device_get(a[k]), pg[k], scale=params[k])
