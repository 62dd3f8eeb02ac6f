"""The step's named scopes reach the compiled HLO, and the training
loop's host spans reach a profiler trace (repro.profile.scopes)."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import get_policy
from repro.data.synthetic import lm_batches
from repro.kernels.fused_adamw import tiling
from repro.models import registry as R
from repro.optim import constant
from repro.optim.fused import fused_adamw_optimizer
from repro.profile import scopes as S
from repro.train.loop import TrainLoopConfig, run_training
from repro.train.step import make_train_step
from repro.train.train_state import make_train_state

POLICY = get_policy("bf16_sr_kahan")
CFG = R.get_config("qwen2.5-3b").reduced()
OP_NAME = re.compile(r'op_name="([^"]*)"')
WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def _setup():
    params = R.init(CFG, jax.random.PRNGKey(0), POLICY.param_dtype)
    opt = fused_adamw_optimizer(POLICY, b2=0.997)
    state = make_train_state(params, opt)
    step = jax.jit(make_train_step(CFG, POLICY, opt, constant(1e-3),
                                   attn_chunk=8))
    return state, step


def _scopes(op_name: str) -> list[str]:
    """The name stack's components with autodiff's wrappers taken off:
    ``transpose(jvp(model))`` is ``model``."""
    out = []
    for part in op_name.split("/"):
        while (m := WRAPPER.match(part)):
            part = m.group(1)
        out.append(part)
    return out


@pytest.fixture(scope="module")
def hlo():
    state, step = _setup()
    batch = next(lm_batches(CFG.vocab, 2, 16))
    return step.lower(state, batch, 0).compile().as_text()


def test_step_and_optimizer_scopes_in_hlo(hlo):
    names = OP_NAME.findall(hlo)
    found = {s for n in names if n.startswith("jit(train_step)")
             for s in _scopes(n)}
    # (one device, no wire: the transport adds no op and so no name)
    for want in (S.MODEL, S.OPTIMIZER, S.SR_BITS, S.STEP_METRICS,
                 S.ATTENTION, S.MLP, S.NORM, S.EMBED, S.HEAD, S.LOSS):
        assert want in found, want
    # the reduced model has leaves of both layouts (e.g. the final norm's
    # (128,) is flat, the embedding's (512, 128) native); only the flat
    # ones are copied into tiles and back under optimizer/layout, each
    # slicing its four outputs (w, m, v, c) out of the tiles
    params = R.init(CFG, jax.random.PRNGKey(0), POLICY.param_dtype)
    native = [tiling(x.shape)[0] for x in jax.tree_util.tree_leaves(params)]
    assert any(native) and not all(native)
    slices = [n for n in names
              if n.endswith(f"/{S.OPTIMIZER}/{S.LAYOUT}/slice")]
    assert len(slices) == 4 * native.count(False)
    # interpret mode lowers the kernel to a loop that keeps its name
    assert any(f"/{S.OPTIMIZER}/{S.FUSED_ADAMW}/" in n for n in names)


def test_autodiff_wraps_the_model_scope(hlo):
    names = set(OP_NAME.findall(hlo))
    assert any(f"/jvp({S.MODEL})/" in n for n in names)
    assert any(f"/transpose(jvp({S.MODEL}))/" in n for n in names)
    assert any(f"/transpose(jvp({S.MODEL}))/" in n
               and "/rematted_computation/" in n for n in names)


def test_every_dot_sits_under_a_step_part(hlo):
    dots = [line for line in hlo.splitlines()
            if re.search(r"= \S+ (dot|convolution)\(", line)]
    assert dots
    for line in dots:
        m = OP_NAME.search(line)
        assert m, line
        assert {S.MODEL, S.TRANSPORT, S.OPTIMIZER} & set(_scopes(m.group(1))), \
            m.group(1)


def test_loop_spans_in_host_trace(tmp_path):
    state, step = _setup()
    batches = lm_batches(CFG.vocab, 2, 16)
    cfg = TrainLoopConfig(total_steps=3, log_every=1,
                          ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
    trace_dir = tmp_path / "trace"
    with jax.profiler.trace(str(trace_dir)):
        run_training(state, step, batches, cfg, log=lambda *_: None)
    files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert files
    pd = jax.profiler.ProfileData.from_file(files[0])
    host = {e.name for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events}
    for span in S.LOOP_SPANS:
        assert span in host, span
    assert len(S.LOOP_SPANS) == 5


def test_scope_names_are_distinct():
    names = S.STEP_SCOPES + S.MODEL_SCOPES + S.MLP_SCOPES + \
        S.OPTIMIZER_SCOPES + (S.FUSED_ADAMW, S.FUSED_SGD)
    assert len(set(names)) == len(names)
    assert all("/" not in n and n for n in names)


def test_fused_sgd_kernel_named():
    """A leaf on the native path lowers with no op under ``layout``; a
    leaf on the flat path is copied into tiles and back there."""
    from repro.kernels.fused_sgd import fused_sgd
    for shape, flat in (((16, 256), False), ((4, 64), True)):
        w = jnp.ones(shape, jnp.bfloat16)
        text = jax.jit(lambda w, m, g: fused_sgd(w, m, g, lr=1e-3)).lower(
            w, w, w).as_text(debug_info=True)
        assert S.FUSED_SGD in text
        assert (S.LAYOUT in text) == flat, shape
        assert tiling(shape)[0] != flat


def test_gradient_accumulation_under_transport():
    params = R.init(CFG, jax.random.PRNGKey(0), POLICY.param_dtype)
    opt = fused_adamw_optimizer(POLICY, b2=0.997)
    step = jax.jit(make_train_step(CFG, POLICY, opt, constant(1e-3),
                                   attn_chunk=8, grad_accum=2))
    batch = next(lm_batches(CFG.vocab, 4, 16))
    text = step.lower(make_train_state(params, opt), batch, 0).as_text(
        debug_info=True)
    assert f"jit(train_step)/{S.TRANSPORT}/" in text
