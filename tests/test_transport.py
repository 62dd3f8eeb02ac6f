"""Gradient-transport layer: strategy selection, error-feedback
correctness (property tests), microbatch accumulation, training parity of
the compressed wire, and old-checkpoint residual fallback.

Fast cases run on the single default device (the compressed wire with one
wire replica is SR quantization + error feedback, no collective); the
multi-device cases (2-pod virtual meshes, hierarchical FSDP composition,
elastic residual restore, launcher end-to-end) are ``dist``-marked
subprocesses like tests/test_dist.py.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.core import get_policy
from repro.core.formats import FORMATS
from repro.dist import partition as PT
from repro.dist import transport as T
from repro.models import registry as R
from repro.optim import adamw, constant
from repro.optim.grad_compress import compress_leaf
from repro.train.step import make_train_step
from repro.train.train_state import TrainState, make_train_state

SRC = str(Path(__file__).resolve().parent.parent / "src")

POLICY = get_policy("bf16_sr")
CFG = R.get_config("qwen2.5-3b").reduced()


class _SpecMesh:
    """Axis-name/size stand-in (enough surface for transport selection)."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


# ---------------------------------------------------------------------------
# error-feedback correctness (satellite: property tests, hypothesis-stub ok)
# ---------------------------------------------------------------------------

class TestErrorFeedback:
    @settings(max_examples=40, deadline=None)
    @given(st.sampled_from(["bf16", "bf14", "bf12", "e5m2", "e4m3"]),
           st.floats(min_value=float(np.float32(0.01)), max_value=100.0,
                     width=32),
           st.integers(min_value=0, max_value=2**31 - 1))
    def test_residuals_telescope(self, fname, scale, seed):
        """Σ_t q_t == Σ_t g_t − r_T, for EVERY wire format: the quantized
        stream transmits the true gradient sum exactly up to one final
        residual (the identity that makes error feedback 'compensation,
        not accumulation'). Format-generic by construction — the residual
        is computed against whatever landed on the wire, including values
        the fp8 formats clamped at max_finite."""
        fmt = FORMATS[fname]
        rng = np.random.default_rng(seed)
        steps = 8
        g_seq = [jnp.asarray(rng.normal(0, scale, 64), jnp.float32)
                 for _ in range(steps)]
        r = jnp.zeros(64, jnp.float32)
        q_sum = jnp.zeros(64, jnp.float32)
        for t, g in enumerate(g_seq):
            q, r = compress_leaf(g, r, jax.random.PRNGKey(seed + t), fmt)
            q_sum = q_sum + q.astype(jnp.float32)
        g_sum = sum(g_seq[1:], g_seq[0])
        lhs = np.asarray(q_sum + r)
        rhs = np.asarray(g_sum)
        tol = 1e-4 * max(float(jnp.max(jnp.abs(g_sum))), scale)
        assert float(np.max(np.abs(lhs - rhs))) <= tol

    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_bf16_wire_bit_parity(self, seed):
        """fmt=BF16 (and the default) is bit-identical to the original
        hard-coded SR-bf16 wire: same key, same noise draw, same bits —
        the regression pin for the format-generic refactor."""
        from repro.core.formats import stochastic_round_bf16
        rng = np.random.default_rng(seed)
        g = jnp.asarray(rng.normal(0, 3.0, 256), jnp.float32)
        r = jnp.asarray(rng.normal(0, 2.0 ** -9, 256), jnp.float32)
        key = jax.random.PRNGKey(seed)
        old = stochastic_round_bf16(g + r, key)
        q_default, _ = compress_leaf(g, r, key)
        q_explicit, _ = compress_leaf(g, r, key, FORMATS["bf16"])
        for q in (q_default, q_explicit):
            assert q.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(old).view(np.uint16),
                np.asarray(q).view(np.uint16))

    def test_fp32_leaf_is_lossless_passthrough(self):
        """The keep-policy leaf format: nothing quantized, residual zero
        (error feedback on a lossless leaf would only re-inject stale
        state)."""
        g = jnp.asarray([1.0 + 2.0 ** -20, -3.7, 0.0], jnp.float32)
        r0 = jnp.asarray([0.125, -0.25, 2.0 ** -24], jnp.float32)
        q, r1 = compress_leaf(g, r0, jax.random.PRNGKey(0), FORMATS["fp32"])
        assert q.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(q), np.asarray(g + r0))
        assert not np.asarray(r1).any()

    def test_fp8_wire_clamps_overflow(self):
        """An overflowing gradient saturates at max_finite on the wire
        (no ±inf in the fp8 grids) and the clamped-away mass lands in
        the residual — overflow-safe, not silently lost."""
        fmt = FORMATS["e4m3"]
        g = jnp.asarray([1.0e6, -1.0e6, 250.0, 1.0], jnp.float32)
        r0 = jnp.zeros(4, jnp.float32)
        q, r1 = compress_leaf(g, r0, jax.random.PRNGKey(0), fmt)
        qf = np.asarray(q, np.float64)
        assert np.isfinite(qf).all()
        assert abs(qf).max() <= fmt.max_finite
        assert qf[0] == fmt.max_finite and qf[1] == -fmt.max_finite
        np.testing.assert_allclose(qf + np.asarray(r1), np.asarray(g),
                                   rtol=0, atol=0)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_sr_quantization_is_unbiased(self, seed):
        """E[q(g)] = g per element: the empirical mean over many keys
        converges onto the true value well below one bf16 ulp — the
        property that keeps the compressed reduce unbiased."""
        g = jnp.linspace(-3.7, 3.7, 128, dtype=jnp.float32)
        zeros = jnp.zeros_like(g)
        n_keys = 4096
        keys = jax.random.split(jax.random.PRNGKey(seed), n_keys)
        q = jax.vmap(lambda k: compress_leaf(g, zeros, k)[0])(keys)
        mean = jnp.mean(q.astype(jnp.float32), axis=0)
        # per-element bf16 spacing; mean error should be ≲ ulp/√K
        ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(g), 1e-30)))
                      - 8 + 1)
        err = jnp.abs(mean - g)
        assert float(jnp.max(err / ulp)) < 6.0 / np.sqrt(n_keys) * 8

    def test_residual_carries_quantization_error_exactly(self):
        g = jnp.asarray([1.0 + 1 / 512, -2.0 - 1 / 256, 0.3], jnp.float32)
        r0 = jnp.asarray([0.25, -0.125, 0.0], jnp.float32)
        q, r1 = compress_leaf(g, r0, jax.random.PRNGKey(0))
        np.testing.assert_allclose(np.asarray(q.astype(jnp.float32) + r1),
                                   np.asarray(g + r0), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# strategy selection + residual state
# ---------------------------------------------------------------------------

class TestMakeTransport:
    def test_defaults_are_implicit_psum(self):
        tr = T.make_transport()
        assert isinstance(tr, T.Fp32Psum)
        assert tr.wire_replicas == 1 and tr.wire_axis is None
        assert tr.init_residuals({"w": jnp.ones(2)}) is None

    def test_fsdp_placement_selects_reduce_scatter(self):
        mesh = _SpecMesh(data=2, fsdp=2, model=2)
        pl = PT.Placement(fsdp_axis="fsdp")
        tr = T.make_transport(mesh=mesh, placement=pl,
                              pspecs={"w": P(None, "fsdp")})
        assert isinstance(tr, T.ReduceScatter)

    def test_fp32_wire_appears_only_with_a_pod_axis(self):
        assert isinstance(T.make_transport(mesh=_SpecMesh(data=4, model=2)),
                          T.Fp32Psum)
        tr = T.make_transport(mesh=_SpecMesh(pod=2, data=2, model=2))
        assert tr.wire_axis == "pod" and tr.wire_replicas == 2

    def test_compressed_wire_axis_defaults(self):
        tr = T.make_transport(mesh=_SpecMesh(pod=2, data=2, model=2),
                              wire="compressed")
        assert isinstance(tr, T.CompressedWire)
        assert tr.wire_axis == "pod"
        # no pod axis → the wire rides the data axis
        tr2 = T.make_transport(mesh=_SpecMesh(data=4, model=2),
                               wire="compressed")
        assert tr2.wire_axis == "data" and tr2.wire_replicas == 4
        # no mesh at all → single-replica local wire
        tr3 = T.make_transport(wire="compressed")
        assert tr3.wire_replicas == 1 and tr3.wire_axis is None

    def test_unknown_wire_rejected(self):
        with pytest.raises(ValueError, match="unknown gradient wire"):
            T.make_transport(wire="bf8")

    def test_wire_axis_may_not_collide_with_placement(self):
        """FSDP over `data` + compressed wire defaulting to `data` would
        put the same axis twice in one residual PartitionSpec — rejected
        with guidance at transport construction, not deep in sharding."""
        mesh = _SpecMesh(data=4, model=2)
        pl = PT.default_placement(mesh, fsdp=True)   # fsdp_axis == 'data'
        with pytest.raises(ValueError, match="already claimed"):
            T.make_transport(mesh=mesh, placement=pl,
                             pspecs={"w": P("data")}, wire="compressed")
        with pytest.raises(ValueError, match="already claimed"):
            T.make_transport(mesh=mesh, placement=pl,
                             pspecs={"w": P("data")}, wire="fp32",
                             wire_axis="data")
        # a dedicated fsdp axis frees `data` for the wire
        mesh2 = _SpecMesh(data=2, fsdp=2, model=2)
        tr = T.make_transport(mesh=mesh2,
                              placement=PT.Placement(fsdp_axis="fsdp"),
                              pspecs={"w": P("fsdp")}, wire="compressed")
        assert tr.wire_axis == "data"

    def test_residual_shapes_and_specs(self):
        mesh = _SpecMesh(pod=2, data=2, model=2)
        tr = T.make_transport(mesh=mesh, wire="compressed")
        params = {"w": jnp.ones((4, 6)), "b": jnp.ones((3,))}
        res = tr.init_residuals(params)
        assert res["w"].shape == (2, 4, 6) and res["b"].shape == (2, 3)
        assert all(l.dtype == jnp.float32
                   for l in jax.tree_util.tree_leaves(res))
        specs = tr.residual_specs({"w": P(None, "model"), "b": P()})
        assert specs["w"] == P("pod", None, "model")
        assert specs["b"] == P("pod")

    def test_compressed_wire_requires_residuals(self):
        tr = T.make_transport(wire="compressed")
        with pytest.raises(ValueError, match="residuals"):
            tr.reduce({"w": jnp.ones(3)}, None, jax.random.PRNGKey(0))

    @pytest.mark.parametrize(
        "fname", ["bf16", "bf14", "bf12", "bf10", "fp16", "e5m2", "e4m3"])
    def test_named_format_selects_compressed_wire(self, fname):
        """`wire=<format name>` is the format-generic spelling; the
        legacy `wire="compressed"` alias stays bf16."""
        tr = T.make_transport(wire=fname)
        assert isinstance(tr, T.CompressedWire)
        assert tr.fmt.name == fname and tr.wire_format == fname

    def test_compressed_alias_is_bf16(self):
        assert T.make_transport(wire="compressed").fmt.name == "bf16"

    def test_fp32_fmt_rejected_on_compressed_wire(self):
        # the lossless wire is Fp32Psum, not a degenerate CompressedWire
        with pytest.raises(ValueError, match="fp32"):
            T.CompressedWire(fmt=FORMATS["fp32"])


# ---------------------------------------------------------------------------
# per-leaf keep policy + payload accounting
# ---------------------------------------------------------------------------

class TestWirePolicy:
    def test_parse_specs(self):
        default = T.WirePolicy.parse("default")
        assert default == T.WirePolicy() == T.WirePolicy.parse("")
        none = T.WirePolicy.parse("none")
        assert none.keep_below == 0 and none.keep_patterns == ()
        custom = T.WirePolicy.parse("4096,embed,lm_head")
        assert custom.keep_below == 4096
        assert custom.keep_patterns == ("embed", "lm_head")

    def test_format_for_routes_leaves(self):
        pol = T.WirePolicy()
        low = FORMATS["bf12"]
        from repro.core.formats import FP32
        # bulk matmul leaf → low format
        assert pol.format_for("['layers'][0]['mlp']['w']", 10**6, low) is low
        # pattern match (case-insensitive, anywhere in the keystr) → fp32
        assert pol.format_for("['Embed']['embedding']", 10**6, low) is FP32
        assert pol.format_for("['ln']['scale']", 10**6, low) is FP32
        # small leaf → fp32 regardless of name
        assert pol.format_for("['w']", 2047, low) is FP32
        # the "none" policy compresses everything
        assert T.WirePolicy.parse("none").format_for(
            "['embed']", 4, low) is low

    def test_leaf_formats_and_wire_format_label(self):
        tr = T.make_transport(wire="bf12", wire_policy=T.WirePolicy())
        tree = {"embed": jnp.zeros((64, 64)),     # pattern keep
                "w": jnp.zeros((64, 64)),          # bulk → bf12
                "b": jnp.zeros((64,))}             # < keep_below → keep
        fmts = dict(zip(sorted(tree), tr.leaf_formats(tree)))
        assert fmts["embed"].name == "fp32"
        assert fmts["w"].name == "bf12"
        assert fmts["b"].name == "fp32"
        assert tr.wire_format.startswith("bf12+keep<2048|")

    def test_leaf_formats_divides_out_replica_dim(self):
        """Stacked residual leaves carry a leading (wire_replicas,) dim;
        size-based keeps must be judged on the per-replica leaf size."""
        mesh = _SpecMesh(pod=2, data=2, model=2)
        tr = T.make_transport(mesh=mesh, wire="bf12",
                              wire_policy=T.WirePolicy(keep_below=2048))
        flat = {"w": jnp.zeros((2, 1500))}    # 3000 global, 1500 per replica
        assert tr.leaf_formats(flat, stacked=True)[0].name == "fp32"
        assert tr.leaf_formats({"w": jnp.zeros((2, 3000))},
                               stacked=True)[0].name == "bf12"

    def test_payload_bytes_accounting(self):
        """Accounted wire bytes are fmt.bits-based (the honest payload),
        not carrier-dtype-based — bf12 counts 12 bits/element even
        though its CPU carrier is 16-bit bfloat16."""
        params = {"w": jnp.zeros((100, 100)), "bias": jnp.zeros((100,))}
        tr = T.make_transport(wire="bf12")
        assert tr.payload_bytes(params) == (10_100 * 12 + 7) // 8
        trp = T.make_transport(wire="bf12", wire_policy=T.WirePolicy())
        # bias rides fp32 under the default policy
        assert trp.payload_bytes(params) == (10_000 * 12 + 100 * 32 + 7) // 8
        tr8 = T.make_transport(wire="e4m3")
        assert tr8.payload_bytes(params) == 10_100  # 8 bits/element


# ---------------------------------------------------------------------------
# train-step integration (single device)
# ---------------------------------------------------------------------------

def _setup(transport=None, grad_accum=1, steps_fn=None):
    params = R.init(CFG, jax.random.PRNGKey(0), POLICY.param_dtype)
    opt = adamw(POLICY, b2=0.997)
    state = make_train_state(params, opt, transport=transport)
    step = jax.jit(make_train_step(CFG, POLICY, opt, constant(1e-3),
                                   attn_chunk=8, transport=transport,
                                   grad_accum=grad_accum))
    return state, step


def _batch(b=8, s=16, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, CFG.vocab)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}


class TestStepIntegration:
    def test_default_transport_matches_legacy_state(self):
        state, step = _setup()
        s1, m1 = step(state, _batch(), 0)
        assert s1.wire_residuals is None
        assert np.isfinite(float(m1["loss"]))

    def test_grad_accum_matches_full_batch_loss(self):
        """k microbatches of B/k == one batch of B: the reported loss and
        the gradient norm match (equal-size chunks → the mean of
        microbatch means IS the full-batch mean — a sum-instead-of-mean
        accumulation bug would double grad_norm), and the updated params
        agree to bf16 tolerance."""
        batch = _batch()
        state, step1 = _setup()
        s1, m1 = step1(state, batch, 0)
        s2_state, step2 = _setup(grad_accum=2)
        s2, m2 = step2(s2_state, batch, 0)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-2
        gn1, gn2 = float(m1["grad_norm"]), float(m2["grad_norm"])
        assert abs(gn1 - gn2) / gn1 < 0.1, (gn1, gn2)
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                                jax.tree_util.tree_leaves(s2.params)))
        assert 0 < d < 0.05  # moved, and within bf16 tolerance of k=1

    def test_grad_accum_must_divide_batch(self):
        state, step = _setup(grad_accum=3)
        with pytest.raises(ValueError, match="not divisible by grad_accum"):
            step(state, _batch(b=8), 0)

    def test_grad_accum_below_one_rejected(self):
        with pytest.raises(ValueError, match="grad_accum"):
            make_train_step(CFG, POLICY, adamw(POLICY), constant(1e-3),
                            grad_accum=0)

    def test_compressed_wire_updates_residuals(self):
        tr = T.make_transport(wire="compressed")
        state, step = _setup(transport=tr)
        assert state.wire_residuals is not None
        s1, _ = step(state, _batch(), 0)
        rmax = max(float(jnp.max(jnp.abs(l)))
                   for l in jax.tree_util.tree_leaves(s1.wire_residuals))
        assert 0 < rmax <= 2 ** -6  # nonzero, bounded by ~a bf16 ulp

    def test_compressed_wire_training_parity_with_fp32(self):
        """Acceptance: the compressed wire trains the tier-1 model to
        within bf16 tolerance of the fp32 wire (single wire replica: the
        wire noise is pure SR quantization + error feedback)."""
        from repro.data.synthetic import lm_batches
        from repro.train.loop import TrainLoopConfig, run_training

        finals = {}
        for wire in ("fp32", "compressed"):
            tr = T.make_transport(wire=wire)
            state, step = _setup(transport=tr)
            _, info = run_training(
                state, step, lm_batches(CFG.vocab, 8, 16, seed=3),
                TrainLoopConfig(total_steps=30, log_every=100),
                log=lambda *_: None)
            hist = info["history"]
            finals[wire] = sum(m["loss"] for m in hist[-5:]) / 5
            assert hist[-1]["loss"] < hist[0]["loss"]  # it trains
        assert abs(finals["fp32"] - finals["compressed"]) < 0.1, finals


# ---------------------------------------------------------------------------
# loop: history cap + old-checkpoint residual fallback
# ---------------------------------------------------------------------------

class TestLoop:
    def test_history_cap_bounds_host_memory(self):
        from repro.data.synthetic import lm_batches
        from repro.train.loop import TrainLoopConfig, run_training
        state, step = _setup()
        _, info = run_training(
            state, step, lm_batches(CFG.vocab, 4, 16),
            TrainLoopConfig(total_steps=7, log_every=100, history_cap=3),
            log=lambda *_: None)
        assert len(info["history"]) == 3

    def test_resume_zero_inits_residuals_from_old_checkpoint(self, tmp_path):
        """A checkpoint written before wire_residuals existed restores
        into a compressed-wire run: everything else round-trips, the
        error-feedback buffers start at zero (satellite: zero-init when
        absent in old checkpoints)."""
        from repro.data.synthetic import lm_batches
        from repro.train.loop import TrainLoopConfig, run_training

        state, step = _setup()          # stateless transport, no residuals
        state, _ = run_training(
            state, step, lm_batches(CFG.vocab, 4, 16, seed=9),
            TrainLoopConfig(total_steps=2, ckpt_dir=str(tmp_path),
                            ckpt_every=2), log=lambda *_: None)

        tr = T.make_transport(wire="compressed")
        state_c, step_c = _setup(transport=tr)
        resumed, info = run_training(
            state_c, step_c, lm_batches(CFG.vocab, 4, 16, seed=9),
            TrainLoopConfig(total_steps=4, ckpt_dir=str(tmp_path),
                            ckpt_every=1000), log=lambda *_: None)
        assert int(jax.device_get(resumed.step)) == 4
        assert resumed.wire_residuals is not None
        assert len(info["history"]) == 2      # resumed at step 2

    def test_resume_zero_inits_residuals_on_wire_replica_change(
            self, tmp_path):
        """A compressed-wire checkpoint whose residuals were shaped for a
        different wire replica count (pod-axis resize) resumes cleanly:
        params/optimizer restore, stale buffers are dropped and
        zero-initialized at the current shape."""
        from repro.data.synthetic import lm_batches
        from repro.train.checkpoint import CheckpointManager
        from repro.train.loop import TrainLoopConfig, run_training

        params = R.init(CFG, jax.random.PRNGKey(0), POLICY.param_dtype)
        opt = adamw(POLICY, b2=0.997)
        # a 2-replica wire (spec-mesh stand-in: residuals shaped (2, …))
        stale_tr = T.CompressedWire(axis="pod",
                                    mesh=_SpecMesh(pod=2, data=2, model=2))
        stale = make_train_state(params, opt, transport=stale_tr)
        stale = stale._replace(step=jnp.asarray(2, jnp.int32))
        CheckpointManager(str(tmp_path)).maybe_save(2, stale, force=True)

        tr = T.make_transport(wire="compressed")     # 1-replica local wire
        state, step = _setup(transport=tr)
        resumed, _ = run_training(
            state, step, lm_batches(CFG.vocab, 4, 16, seed=9),
            TrainLoopConfig(total_steps=3, ckpt_dir=str(tmp_path),
                            ckpt_every=1000), log=lambda *_: None)
        assert int(jax.device_get(resumed.step)) == 3
        r0 = jax.tree_util.tree_leaves(resumed.wire_residuals)[0]
        assert r0.shape[0] == 1               # current shape, not stored

    def test_resume_from_legacy_three_field_checkpoint(self, tmp_path):
        """A checkpoint written before TrainState grew wire_residuals
        (3-field namedtuple) resumes into a compressed-wire run."""
        import collections
        from repro.data.synthetic import lm_batches
        from repro.train.checkpoint import CheckpointManager
        from repro.train.loop import TrainLoopConfig, run_training

        params = R.init(CFG, jax.random.PRNGKey(0), POLICY.param_dtype)
        opt = adamw(POLICY, b2=0.997)
        Legacy = collections.namedtuple("TrainState",
                                        ["step", "params", "opt_state"])
        legacy = Legacy(jnp.asarray(2, jnp.int32), params, opt.init(params))
        CheckpointManager(str(tmp_path)).maybe_save(2, legacy, force=True)

        tr = T.make_transport(wire="compressed")
        state, step = _setup(transport=tr)
        resumed, _ = run_training(
            state, step, lm_batches(CFG.vocab, 4, 16, seed=9),
            TrainLoopConfig(total_steps=3, ckpt_dir=str(tmp_path),
                            ckpt_every=1000), log=lambda *_: None)
        assert int(jax.device_get(resumed.step)) == 3
        assert resumed.wire_residuals is not None

    def test_policy_drift_is_not_misdiagnosed_as_residual_drift(
            self, tmp_path):
        """Kahan ↔ non-Kahan policy changes also shift the leaf count by
        one param-shaped tree; the treedef gate keeps _restore from
        'helpfully' dropping Kahan state as if it were wire residuals."""
        from repro.train.checkpoint import CheckpointManager
        from repro.data.synthetic import lm_batches
        from repro.train.loop import TrainLoopConfig, run_training

        kahan = get_policy("bf16_sr_kahan")
        params = R.init(CFG, jax.random.PRNGKey(0), kahan.param_dtype)
        opt_k = adamw(kahan, b2=0.997)
        state_k = make_train_state(params, opt_k)
        CheckpointManager(str(tmp_path)).maybe_save(2, state_k, force=True)

        state, step = _setup()                # bf16_sr, stateless wire
        with pytest.raises(ValueError, match="leaves"):
            run_training(state, step, lm_batches(CFG.vocab, 4, 16),
                         TrainLoopConfig(total_steps=3,
                                         ckpt_dir=str(tmp_path),
                                         ckpt_every=1000),
                         log=lambda *_: None)

    def test_resume_drops_residuals_when_wire_downgraded(self, tmp_path):
        """A compressed-wire checkpoint resumes into a stateless-transport
        run (wire downgraded to fp32 across the restart): the stored
        buffers are dropped unread, everything else round-trips."""
        from repro.data.synthetic import lm_batches
        from repro.train.loop import TrainLoopConfig, run_training

        tr = T.make_transport(wire="compressed")
        state_c, step_c = _setup(transport=tr)
        saved, _ = run_training(
            state_c, step_c, lm_batches(CFG.vocab, 4, 16, seed=9),
            TrainLoopConfig(total_steps=2, ckpt_dir=str(tmp_path),
                            ckpt_every=2), log=lambda *_: None)

        state, step = _setup()                # fp32: no residual state
        resumed, info = run_training(
            state, step, lm_batches(CFG.vocab, 4, 16, seed=9),
            TrainLoopConfig(total_steps=4, ckpt_dir=str(tmp_path),
                            ckpt_every=1000), log=lambda *_: None)
        assert int(jax.device_get(resumed.step)) == 4
        assert resumed.wire_residuals is None
        assert len(info["history"]) == 2      # resumed at step 2


# ---------------------------------------------------------------------------
# multi-device: 2-pod parity, hierarchical FSDP, elastic residual restore,
# launcher end-to-end (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------

def _run(script: str, extra_env: dict | None = None) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env.update(extra_env or {})
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


@pytest.mark.dist
def test_two_pod_wires_match_single_device():
    """fp32 and compressed pod wires on 2 pod × 2 data × 2 model both
    match the single-device step to bf16 tolerance; the compressed wire
    additionally matches with the FSDP inner + grad_accum=2 (the full
    hierarchical composition)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.core import get_policy
        from repro.dist import partition as PT
        from repro.dist import fsdp as F
        from repro.dist import transport as T
        from repro.dist.axes import activation_sharding
        from repro.launch.mesh import make_local_mesh
        from repro.models import registry as R
        from repro.optim import adamw, constant
        from repro.train.step import make_train_step
        from repro.train.train_state import make_train_state

        policy = get_policy("bf16_sr_kahan")
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
        opt = adamw(policy, b2=0.997)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

        s1 = make_train_state(params, opt)
        step1 = make_train_step(cfg, policy, opt, constant(1e-3), attn_chunk=8)
        s1b, m1 = jax.jit(step1)(s1, batch, 0)

        def case(tag, mesh, pl, wire, accum):
            pspecs = PT.param_specs(params, cfg, mesh, pl)
            tr = T.make_transport(mesh=mesh, placement=pl, pspecs=pspecs,
                                  wire=wire)
            state = make_train_state(params, opt, transport=tr)
            state = jax.device_put(state, F.train_state_shardings(
                state, cfg, mesh, pl, transport=tr))
            step = make_train_step(cfg, policy, opt, constant(1e-3),
                                   attn_chunk=8, transport=tr,
                                   grad_accum=accum)
            hints, hsize = tr.hint_axes(mesh)
            with jax.set_mesh(mesh), activation_sharding(hints, hsize, "model", 2):
                sb, m = jax.jit(step)(state, batch, 0)
            d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32))))
                    for a, b in zip(jax.tree_util.tree_leaves(s1b.params),
                                    jax.tree_util.tree_leaves(sb.params)))
            print("maxdiff_" + tag, d)
            if sb.wire_residuals is not None:
                r0 = jax.tree_util.tree_leaves(sb.wire_residuals)[0]
                print("podres_" + tag, int(r0.sharding.spec[0] == "pod"))

        mesh = make_local_mesh(2, 2, pods=2)
        case("fp32", mesh, PT.Placement(), "fp32", 1)
        case("compressed", mesh, PT.Placement(), "compressed", 1)
        mesh2 = make_local_mesh(1, 2, fsdp=2, pods=2)
        case("hier", mesh2, PT.Placement(fsdp_axis="fsdp"), "compressed", 2)
    """)
    vals = {l.split()[0]: float(l.split()[1])
            for l in out.strip().splitlines()}
    # collectives reorder f32 sums; SR noise is keyed identically per leaf
    assert vals["maxdiff_fp32"] < 0.05, out
    assert vals["maxdiff_compressed"] < 0.05, out
    assert vals["maxdiff_hier"] < 0.05, out
    assert vals["podres_compressed"] == 1, out
    assert vals["podres_hier"] == 1, out


@pytest.mark.dist
def test_wire_residuals_survive_elastic_restore():
    """Acceptance: residuals checkpoint and re-shard onto a different
    mesh shape through the run_training state_shardings path."""
    out = _run("""
        import tempfile
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.core import get_policy
        from repro.dist import partition as PT
        from repro.dist import fsdp as F
        from repro.dist import transport as T
        from repro.launch.mesh import make_local_mesh
        from repro.models import registry as R
        from repro.optim import adamw
        from repro.train.checkpoint import CheckpointManager
        from repro.train.train_state import make_train_state

        policy = get_policy("bf16_sr")
        cfg = R.get_config("qwen2.5-3b").reduced()
        params = R.init(cfg, jax.random.PRNGKey(0), policy.param_dtype)
        opt = adamw(policy, b2=0.997)

        mesh = make_local_mesh(2, 2, pods=2)
        pl = PT.Placement()
        pspecs = PT.param_specs(params, cfg, mesh, pl)
        tr = T.make_transport(mesh=mesh, placement=pl, pspecs=pspecs,
                              wire="compressed")
        state = make_train_state(params, opt, transport=tr)
        # make the residuals distinctive so the round-trip is meaningful
        state = state._replace(wire_residuals=jax.tree_util.tree_map(
            lambda r: r + 0.125, state.wire_residuals))
        state = jax.device_put(state, F.train_state_shardings(
            state, cfg, mesh, pl, transport=tr))

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, every_steps=1)
            mgr.maybe_save(1, state, force=True)
            # elastic restore onto a different mesh shape (wider data dim)
            mesh2 = make_local_mesh(4, 1, pods=2)
            pspecs2 = PT.param_specs(params, cfg, mesh2, pl)
            tr2 = T.make_transport(mesh=mesh2, placement=pl, pspecs=pspecs2,
                                   wire="compressed")
            like = make_train_state(params, opt, transport=tr2)
            sh2 = F.train_state_shardings(like, cfg, mesh2, pl, transport=tr2)
            got, at = mgr.restore_latest(like, shardings=sh2)
            ok = all(np.array_equal(jax.device_get(a), jax.device_get(b))
                     for a, b in zip(jax.tree_util.tree_leaves(state),
                                     jax.tree_util.tree_leaves(got)))
            r0 = jax.tree_util.tree_leaves(got.wire_residuals)[0]
            print("restored_step", at)
            print("values_ok", int(ok))
            print("on_new_mesh", int(r0.sharding.mesh.shape == mesh2.shape))
            print("pod_sharded", int(r0.sharding.spec[0] == "pod"))
    """)
    vals = {l.split()[0]: float(l.split()[1])
            for l in out.strip().splitlines()}
    assert vals["restored_step"] == 1, out
    assert vals["values_ok"] == 1, out
    assert vals["on_new_mesh"] == 1, out
    assert vals["pod_sharded"] == 1, out


@pytest.mark.dist
def test_launcher_end_to_end_compressed_wire_with_accum():
    """Satellite: the launcher trains a few steps through
    --grad-wire=compressed --grad-accum=2 on a 2-pod virtual mesh."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "qwen2.5-3b", "--reduced", "--steps", "3",
         "--batch", "8", "--seq", "16", "--pods", "2",
         "--data-parallel", "2", "--model-parallel", "2",
         "--grad-wire", "compressed", "--grad-accum", "2"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "done at step 3" in r.stdout, r.stdout
    loss = float(r.stdout.split("final loss")[1].split(";")[0])
    assert np.isfinite(loss) and loss < 8.0, r.stdout
