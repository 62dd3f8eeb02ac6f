"""The FLOP and byte counts against closed forms worked by hand, at two
shapes; and the parameter count against the program's own tree."""
import json

import jax
import jax.numpy as jnp
import pytest

from harness import counts, spec
from toy import TINY

YI = json.loads((spec.BENCH_DIR / "configs" / "yi-9b.json").read_text())


def test_tiny_by_hand():
    # per layer: q 64x64, k and v 64x32 each, o 64x64, MLP 3 x 64x128
    assert counts.layer_matmul_params(TINY) == 4096 + 2 * 2048 + 4096 \
        + 3 * 8192
    # embed 256x64, 2 layers of (matmuls + 2 norms + q/k/v biases),
    # final norm, untied head 64x256
    assert counts.n_params(TINY) == 16384 + 2 * (36864 + 128 + 128) \
        + 64 + 16384
    # batch 1 x 4: dense 2*(2*36864) + head 2*64*256 per token; causal
    # attention 4*(p+1)*heads*head_dim*layers summed over p = 0..3
    dense = 2 * 2 * 36864 + 2 * 64 * 256
    attn = 4 * 4 * 16 * 2 * (1 + 2 + 3 + 4)
    assert counts.train_flops_per_step(TINY, 1, 4) == 3 * (4 * dense + attn)
    assert counts.adamw_bytes(TINY) == 18 * counts.n_params(TINY)


def test_yi_by_hand():
    d, f, L, v, s = 4096, 11008, 3, 64000, 4096
    layer = 2 * d * d + 2 * d * 512 + 3 * d * f
    assert counts.layer_matmul_params(YI) == layer == 173_015_040
    fwd = s * (2 * L * layer + 2 * d * v) + 4 * 32 * 128 * L * s * (s + 1) // 2
    assert counts.train_flops_per_step(YI, 1, s) == 3 * fwd
    assert counts.n_params(YI) == v * d + L * (layer + 2 * d) + d + d * v


@pytest.mark.parametrize("config", [TINY, YI], ids=["tiny", "yi-9b"])
def test_params_match_program_tree(config):
    from repro.models import registry as R
    tree = jax.eval_shape(lambda k: R.init(spec.model_config(config), k,
                                           jnp.bfloat16),
                          jax.random.PRNGKey(0))
    assert counts.n_params(config) == sum(
        x.size for x in jax.tree_util.tree_leaves(tree))
