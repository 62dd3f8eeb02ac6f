"""The plain references against the system at toy sizes on the CPU.

* In float32 the reference and the program's own fp32 path compute the
  same model: logits agree to float32 round-off.
* Through a whole toy run, the program's bf16 readings pass the cell's
  limits and the fp8 control's fail them (the control test; on the chip
  the same comparison runs at each cell's own size).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from harness import check, seeds, spec, weights
from reference import dense_gqa


@pytest.mark.parametrize("tie", [False, True])
def test_reference_is_the_programs_model(tie):
    from repro.core.policy import get_policy
    from repro.core.qarith import QArith
    from repro.models import registry as R
    cfg_d = dict(toy.TINY, tie_word_embeddings=tie)
    cfg = spec.model_config(cfg_d)
    shapes = jax.eval_shape(lambda k: R.init(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    params = weights.make(shapes, seeds.key(7, "weights"), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg_d["vocab_size"])
    with jax.default_matmul_precision("highest"):
        prog = R.forward_logits(QArith(get_policy("fp32")), params, cfg,
                                {"tokens": tokens}, remat=False,
                                attn_chunk=16)
    h = dense_gqa.hidden(cfg_d, params, tokens)
    ref = dense_gqa.logits_rows(dense_gqa.frozen(cfg_d), params,
                                h.reshape(-1, h.shape[-1]), "exact")
    np.testing.assert_allclose(np.asarray(prog).reshape(ref.shape),
                               np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_program_passes_and_fp8_control_fails():
    cell = toy.train_cell()
    prog = toy.measure(cell)
    ctl = toy.measure(cell, control="fp8")
    assert prog["correct"], prog["readings"]
    assert not ctl["correct"] and "program" not in ctl["readings"]
    ctl_ok, shown = check.judge(ctl["readings"]["control"], cell.limits)
    assert not ctl_ok, shown
