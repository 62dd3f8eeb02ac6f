"""The expert architecture (``moe_gqa``, SDAR-30B-A3B's share): its
counts by hand, its reference against the program at toy size on the
CPU (with the check's control), the readers of its two metrics on
hand-made events, and its entries in BENCHMARK.json."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from harness import check, counts, scopes, seeds, spec, weights
from harness import trace as T
from harness.arch import moe_gqa
from harness.train import Record
from reference import moe_gqa as ref

CELL = "sdar-30b-a3b.train.8k"
SDAR = json.loads((spec.BENCH_DIR / "configs" / "sdar-30b-a3b.json")
                  .read_text())
TINY_MOE = {
    "name": "tiny-moe", "reference": "moe_gqa", "hidden_size": 64,
    "intermediate_size": 192, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "num_experts": 4, "expert_parallel": 4,
    "expert_offset": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.001, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "rope_scaling": None, "use_sliding_window": False,
}


def test_sdar_by_hand():
    d, f, L, v, s = 2048, 768, 4, 38016, 8192
    attn = d * 4096 + 2 * d * 512 + 4096 * d
    # per token a layer: attention, the 128-wide router, and 8 x 32 / 128
    # = 2 assignments through a held expert's three matrices
    assert counts.layer_matmul_params(SDAR) == attn + d * 128 + 2 * 3 * d * f
    per_layer = attn + 2 * 128 + 2 * d + d * 128 + 32 * 3 * d * f
    assert per_layer == 170_135_808
    assert counts.n_params(SDAR) == v * d + L * per_layer + d + d * v \
        == 836_258_816
    assert counts.train_flops_per_step(SDAR, 1, s) == 16_042_508_156_928
    assert counts.adamw_bytes(SDAR) == 18 * 836_258_816


@pytest.mark.parametrize("config", [TINY_MOE, SDAR], ids=["tiny", "sdar"])
def test_params_match_program_tree(config):
    from repro.models import registry as R
    tree = jax.eval_shape(lambda k: R.init(spec.model_config(config), k,
                                           jnp.bfloat16),
                          jax.random.PRNGKey(0))
    assert counts.n_params(config) == sum(
        x.size for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
    ("hidden_act", "gelu")])
def test_config_refuses_what_the_program_would_run_otherwise(key, value):
    with pytest.raises(ValueError, match=key):
        moe_gqa.model_config(dict(SDAR, **{key: value}))


def test_program_config_is_the_share():
    cfg = spec.model_config(SDAR)
    assert (cfg.n_experts, cfg.n_held, cfg.expert_offset) == (128, 32, 0)
    assert (cfg.top_k, cfg.d_ff, cfg.d_model) == (8, 768, 2048)
    assert cfg.qk_norm and cfg.norm_topk_prob
    assert cfg.moe_strategy == "dropless" and cfg.router_aux_coef == 0.001


def test_reference_is_the_programs_model():
    """In float32 the program and the reference compute the same logits
    (the same routing; the held share in the middle of the router's
    outputs) and the same router loss."""
    from repro.core.policy import get_policy
    from repro.core.qarith import QArith
    from repro.models import registry as R
    cfg = spec.model_config(TINY_MOE)
    shapes = jax.eval_shape(lambda k: R.init(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    params = weights.make(shapes, seeds.key(7, "weights"), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                TINY_MOE["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog, stats = R.forward_logits(QArith(get_policy("fp32")), params,
                                       cfg, {"tokens": tokens}, remat=False,
                                       attn_chunk=16, with_stats=True)
    h, router_loss = ref._stack(TINY_MOE, params, tokens, ref.exact)
    logits = ref.logits_rows(ref.frozen(TINY_MOE), params,
                             h.reshape(-1, h.shape[-1]), "exact")
    np.testing.assert_allclose(np.asarray(prog).reshape(logits.shape),
                               np.asarray(logits), atol=2e-4, rtol=2e-4)
    assert float(stats["router_loss"]) == pytest.approx(float(router_loss),
                                                        rel=1e-5)


def _toy_cell():
    return spec.Cell("toy.moe", 1, "tiny-moe", dict(TINY_MOE), "train.8k",
                     dict(toy._json("traffic", "train.8k.json"), seq=64),
                     toy._json("limits", f"{CELL}.json"),
                     toy._metrics("train_tokens_per_s", "setup_s"), [])


def test_program_passes_and_fp8_control_fails():
    cell = _toy_cell()
    prog = toy.measure(cell)
    ctl = toy.measure(cell, control="fp8")
    assert prog["correct"], prog["readings"]
    ctl_ok, shown = check.judge(ctl["readings"]["control"], cell.limits)
    assert not ctl_ok, shown


GMM = ('%gmm.44 = bf16[65536,768]{1,0} custom-call(s32[129]{0} %fusion.1, '
       'bf16[65536,2048]{1,0} %x), custom_call_target="tpu_custom_call"')
TGMM = ('%tgmm.13 = bf16[32,2048,768]{2,1,0} custom-call(s32[129]{0} '
        '%fusion.1), custom_call_target="tpu_custom_call"')
ADAMW = ('%fused_adamw.20 = (bf16[4,32,2048,768]{3,2,1,0}) custom-call(), '
         'custom_call_target="tpu_custom_call"')


def _record(steps=10, n_gmm=None):
    cell = spec.load_cell(CELL)
    calls = 12 * SDAR["num_hidden_layers"] * steps
    n_gmm = calls if n_gmm is None else n_gmm
    r = T.Reduction(window_s=5.0, busy_s=4.9, n_devices=1,
                    op_s={GMM: 0.3, TGMM: 0.2, ADAMW: 0.4, "%fusion.7": 1.0},
                    exposed_collective_s=0.0, collective_s=0.0,
                    idle_by_host={},
                    op_n={GMM: n_gmm * 3 // 4, TGMM: n_gmm - n_gmm * 3 // 4,
                          ADAMW: steps * 15, "%fusion.7": steps})
    return Record("train", cell, 5.0, steps, steps * 8192, trace=r,
                  device_kind="TPU v5 lite",
                  extra={"setup_s": 30.0, "param_leaves": 15})


def test_grouped_matmul_roofline_on_hand_made_events():
    read = spec.metric_reader("grouped_matmul_roofline")
    # 10 steps x 4 layers x 12 calls, each 2 x 16384 rows x 2048 x 768
    flops = 10 * 48 * 2 * 16384 * 2048 * 768
    assert read(_record()) == pytest.approx(100 * flops / 197e12 / 0.5)
    assert 0 < read(_record()) < 100


def test_grouped_matmul_roofline_refuses_a_miscount():
    with pytest.raises(ValueError, match="kernel events"):
        spec.metric_reader("grouped_matmul_roofline")(_record(n_gmm=479))


def test_grouped_matmul_roofline_silent_without_the_kernel():
    rec = _record()
    rec.trace = dataclasses.replace(rec.trace, op_s={"%fusion.7": 1.0},
                                    op_n={"%fusion.7": 10})
    assert spec.metric_reader("grouped_matmul_roofline")(rec) is None


def test_moe_ms_sums_the_expert_layer_in_every_direction():
    rec = _record()
    rec.extra["scopes"] = scopes.ScopeTimes({
        ("forward", "model/mlp"): 0.1, ("backward", "model/mlp"): 0.2,
        ("recompute", "model/mlp"): 0.1, ("forward", "model/attention"): 1.0,
        ("outside", "optimizer"): 0.5}, busy_s=4.9, window_s=5.0,
        n_devices=1)
    assert spec.metric_reader("moe_ms.train")(rec) == pytest.approx(40.0)
    rec.extra["scopes"] = None          # a program that names no scope
    assert spec.metric_reader("moe_ms.train")(rec) is None


def test_the_cells_entries_resolve():
    c = spec.load_cell(CELL)
    assert (c.chips, c.traffic["seq"], c.traffic["batch"]) == (1, 8192, 1)
    assert {m["name"] for m in c.end_to_end} == {"train_tokens_per_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "mfu.train", "fused_adamw_roofline", "forward_ms.train",
        "backward_ms.train", "recompute_ms.train", "optimizer_ms.train",
        "moe_ms.train", "grouped_matmul_roofline"}
    yi = spec.load_cell("yi-9b.train.4k")
    assert "moe_ms.train" not in {m["name"] for m in yi.per_layer}
    conf = next(x for x in spec.load_benchmark()["configs"]
                if x["name"] == "sdar-30b-a3b")
    assert sorted(conf["reduced"]) == sorted(SDAR["published"])
