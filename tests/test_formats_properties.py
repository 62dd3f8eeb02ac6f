"""Property-test suite for the rounding primitives, across the full
format grid (bf16 / bf14 / bf12 / bf10 / fp16 / e5m2 / e4m3).

Driven by ``hypothesis`` when installed, else by the deterministic stub
(``tests/_hypothesis_stub.py``) that conftest registers — either way the
properties themselves are the spec:

* **SR unbiasedness** at sub-ulp magnitudes — exactly where nearest
  rounding stalls (returns the same grid point every step, the paper's
  vanishing-update failure mode), stochastic rounding must hit the upper
  neighbor with probability (x−lo)/ulp. Checked against a 5σ binomial
  bound, so a false alarm is a ~3·10⁻⁷ event, not flake.
* **Idempotence** — both rounders are the identity on their own grid
  (round_nearest∘round_nearest = round_nearest, and SR of a grid point
  never moves regardless of the key).
* **ulp() monotonicity + subnormal boundary** — grid spacing never
  decreases with magnitude, equals ``sub_spacing`` at the format's
  smallest normal, and nearest rounding flushes to zero below half the
  subnormal spacing.
* **Overflow containment** — the small-exponent wire formats (e5m2 /
  e4m3, which carry no ±inf) saturate at ``max_finite``: no inf escapes
  a rounder, and ``clamp_finite`` maps ±inf onto ±max_finite for every
  format. (The e8 *storage* formats deliberately pass inf through —
  ``test_formats.py::test_nan_inf_passthrough`` pins that contract; the
  wire's clamping lives in ``compress_leaf``, tested in
  ``test_transport.py``.)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.formats import (E4M3, E5M2, FORMATS, clamp_finite,
                                round_nearest, round_stochastic, ulp,
                                wire_carrier_dtype)

GRID = ["bf16", "bf14", "bf12", "bf10", "fp16", "e5m2", "e4m3"]
SMALL_EXP = ["fp16", "e5m2", "e4m3"]   # formats with their own subnormal range

N_SAMPLES = 4096
FIVE_SIGMA = 5.0

def _f32(v: float) -> float:
    """``v`` rounded to float32: ``st.floats(width=32)`` wants bounds it can hold exactly."""
    return float(np.float32(v))


def _key(*ints) -> jax.Array:
    k = jax.random.PRNGKey(20240808)
    for v in ints:
        k = jax.random.fold_in(k, v & 0x7FFFFFFF)
    return k


# ---------------------------------------------------------------------------
# SR unbiasedness where nearest stalls
# ---------------------------------------------------------------------------

class TestStochasticUnbiased:
    # NOTE: format selection rides a sampled_from strategy, not
    # pytest.mark.parametrize — the hypothesis stub's runner exposes a
    # (*args) signature that parametrize can't inject names into (same
    # idiom as test_formats.py::test_hyp_monotonic_grid).
    @settings(max_examples=40, deadline=None)
    @given(st.sampled_from(GRID),
           st.floats(min_value=_f32(0.03), max_value=_f32(0.47), width=32))
    def test_unbiased_at_sub_ulp_offsets(self, fname, theta):
        """x = 1 + θ·ulp with θ < 1/2: nearest stalls at 1.0 forever;
        SR must average back to x (binomial mean within 5σ)."""
        fmt = FORMATS[fname]
        step = float(ulp(jnp.float32(1.0), fmt))
        x32 = np.float32(1.0 + theta * step)
        theta_eff = (float(x32) - 1.0) / step     # θ after f32 snapping
        if theta_eff <= 0.0:
            return                                # degenerate draw
        assert float(round_nearest(jnp.float32(x32), fmt)) == 1.0, \
            "nearest must stall below the midpoint"
        xs = jnp.full((N_SAMPLES,), x32, jnp.float32)
        q = np.asarray(round_stochastic(
            xs, _key(int(theta * 1e6)), fmt), np.float64)
        assert set(np.unique(q)) <= {1.0, 1.0 + step}, \
            "SR must land on the two neighbors only"
        p_hat = (q.mean() - 1.0) / step
        sigma = math.sqrt(theta_eff * (1 - theta_eff) / N_SAMPLES)
        assert abs(p_hat - theta_eff) < FIVE_SIGMA * sigma, \
            f"SR biased: p̂={p_hat:.4f} θ={theta_eff:.4f} σ={sigma:.4f}"

    @settings(max_examples=24, deadline=None)
    @given(st.sampled_from(SMALL_EXP),
           st.floats(min_value=_f32(0.06), max_value=_f32(0.94), width=32))
    def test_unbiased_on_subnormal_grid(self, fname, theta):
        """θ·sub_spacing (below min_normal, where the format's own
        subnormal lattice rules): SR splits between 0 and sub_spacing
        with P[up] = θ."""
        fmt = FORMATS[fname]
        sp = fmt.sub_spacing
        x32 = np.float32(theta * sp)
        theta_eff = float(x32) / sp
        xs = jnp.full((N_SAMPLES,), x32, jnp.float32)
        q = np.asarray(round_stochastic(
            xs, _key(1 + int(theta * 1e6)), fmt), np.float64)
        assert set(np.unique(q)) <= {0.0, sp}
        p_hat = q.mean() / sp
        sigma = math.sqrt(theta_eff * (1 - theta_eff) / N_SAMPLES)
        assert abs(p_hat - theta_eff) < FIVE_SIGMA * sigma


# ---------------------------------------------------------------------------
# Idempotence on the grid
# ---------------------------------------------------------------------------

class TestIdempotence:
    @settings(max_examples=120, deadline=None)
    @given(st.sampled_from(GRID),
           st.floats(min_value=_f32(-3e38), max_value=_f32(3e38), width=32))
    def test_round_nearest_idempotent(self, fname, x):
        fmt = FORMATS[fname]
        y = round_nearest(jnp.float32(x), fmt)
        z = round_nearest(y, fmt)
        assert _same(y, z), f"RNE not idempotent: {x} -> {y} -> {z}"

    @settings(max_examples=120, deadline=None)
    @given(st.sampled_from(GRID),
           st.floats(min_value=_f32(-3e38), max_value=_f32(3e38), width=32),
           st.integers(min_value=0, max_value=2 ** 30))
    def test_round_stochastic_fixes_grid_points(self, fname, x, seed):
        """A grid point is a fixed point of SR for every key."""
        fmt = FORMATS[fname]
        y = round_nearest(jnp.float32(x), fmt)
        z = round_stochastic(y, _key(seed), fmt)
        assert _same(y, z), f"SR moved a grid point: {y} -> {z}"

    @pytest.mark.parametrize("fname", GRID)
    def test_carrier_grid_contains_format(self, fname):
        """Round-tripping through the wire carrier dtype is lossless for
        every representable value — the property the CompressedWire
        carrier choice relies on."""
        fmt = FORMATS[fname]
        pts = jnp.float32(np.array(
            [0.0, fmt.sub_spacing, fmt.min_normal, 1.0, 1.0 + 2.0 ** -fmt.man_bits,
             -2.5, fmt.max_finite, -fmt.max_finite], np.float64))
        grid = round_nearest(pts, fmt)
        via_carrier = grid.astype(wire_carrier_dtype(fmt)).astype(jnp.float32)
        assert np.array_equal(np.asarray(grid), np.asarray(via_carrier)), \
            (np.asarray(grid), np.asarray(via_carrier))


def _same(a, b) -> bool:
    a, b = float(jax.device_get(a)), float(jax.device_get(b))
    return a == b or (math.isnan(a) and math.isnan(b))


# ---------------------------------------------------------------------------
# ulp(): monotone spacing, correct at the subnormal boundary
# ---------------------------------------------------------------------------

class TestUlp:
    @pytest.mark.parametrize("fname", GRID)
    def test_monotone_in_magnitude(self, fname):
        fmt = FORMATS[fname]
        lo = math.log2(fmt.min_normal) - fmt.man_bits - 1
        hi = math.log2(fmt.max_finite) - 0.001
        xs = jnp.float32(2.0 ** np.linspace(lo, hi, 200))
        us = np.asarray(ulp(xs, fmt), np.float64)
        assert (us > 0).all(), "spacing must be positive"
        assert (np.diff(us) >= 0).all(), "spacing must not shrink with |x|"

    @pytest.mark.parametrize("fname", GRID)
    def test_sub_spacing_at_boundary(self, fname):
        """At (and below) the smallest normal the spacing is the
        format's fixed subnormal spacing."""
        fmt = FORMATS[fname]
        mn = jnp.float32(fmt.min_normal)
        assert float(ulp(mn, fmt)) == fmt.sub_spacing
        assert float(ulp(mn / 2, fmt)) == fmt.sub_spacing

    @pytest.mark.parametrize("fname", SMALL_EXP)
    def test_flush_to_zero_below_half_spacing(self, fname):
        """RNE flushes to exactly 0 below sub_spacing/2 and up to the
        first subnormal above it — the boundary where tiny gradients
        start surviving the wire at all."""
        fmt = FORMATS[fname]
        sp = fmt.sub_spacing
        assert float(round_nearest(jnp.float32(0.49 * sp), fmt)) == 0.0
        assert float(round_nearest(jnp.float32(0.51 * sp), fmt)) == sp
        # stochastic: the flush region still reaches sp with P = θ > 0
        q = np.asarray(round_stochastic(
            jnp.full((512,), 0.25 * sp, jnp.float32), _key(3), fmt))
        assert set(np.unique(q)) <= {0.0, np.float32(sp)}
        assert (q > 0).any(), "SR must resolve sub-flush values sometimes"

    def test_e8_deep_subnormal_spacing_exact(self):
        """The FTZ-safe path: near f32's own subnormal boundary the e8
        grids' spacing underflows naive subtraction; ulp must still
        return the exact bit-level spacing."""
        for fname in ("bf16", "bf14", "bf12", "bf10"):
            fmt = FORMATS[fname]
            got = float(ulp(jnp.float32(2.0 ** -126), fmt))
            assert got == 2.0 ** (-126 - fmt.man_bits), (fname, got)


# ---------------------------------------------------------------------------
# Overflow containment (the wire-format contract)
# ---------------------------------------------------------------------------

class TestOverflow:
    @settings(max_examples=50, deadline=None)
    @given(st.sampled_from(["e5m2", "e4m3"]),
           st.floats(min_value=1.0, max_value=_f32(3e38), width=32),
           st.integers(min_value=0, max_value=2 ** 30))
    def test_no_inf_escapes_small_exp(self, fname, x, seed):
        """Every finite (or infinite) input maps to a finite grid value
        ≤ max_finite, under both rounders — fp8 wire values must never
        poison an all-reduce with inf."""
        fmt = FORMATS[fname]
        for v in (x, -x, float("inf"), float("-inf")):
            rn = float(round_nearest(jnp.float32(v), fmt))
            sr = float(round_stochastic(jnp.float32(v), _key(seed), fmt))
            assert math.isfinite(rn) and abs(rn) <= fmt.max_finite, (v, rn)
            assert math.isfinite(sr) and abs(sr) <= fmt.max_finite, (v, sr)

    @pytest.mark.parametrize("fname", ["e5m2", "e4m3"])
    def test_saturates_exactly_at_max_finite(self, fname):
        fmt = FORMATS[fname]
        big = jnp.float32([fmt.max_finite, fmt.max_finite * 4, float("inf")])
        out = np.asarray(round_nearest(big, fmt))
        assert (out == fmt.max_finite).all(), out

    @pytest.mark.parametrize("fname", GRID)
    def test_clamp_finite_contains_inf(self, fname):
        fmt = FORMATS[fname]
        x = jnp.float32([float("inf"), float("-inf"), 0.5, -0.5])
        out = np.asarray(clamp_finite(x, fmt), np.float64)
        assert out[0] == fmt.max_finite and out[1] == -fmt.max_finite
        assert out[2] == 0.5 and out[3] == -0.5

    @pytest.mark.parametrize("fname", ["e5m2", "e4m3"])
    def test_nan_passes_through(self, fname):
        """NaN is deliberately NOT clamped: a poisoned gradient should
        surface as NaN loss (and trip the spike monitor), not be
        silently laundered into max_finite."""
        fmt = FORMATS[fname]
        nan = jnp.float32(float("nan"))
        assert math.isnan(float(round_nearest(nan, fmt)))
        assert math.isnan(float(round_stochastic(nan, _key(9), fmt)))
        assert math.isnan(float(clamp_finite(nan, fmt)))


# ---------------------------------------------------------------------------
# Format metadata (the accounting the wire relies on)
# ---------------------------------------------------------------------------

class TestMetadata:
    @pytest.mark.parametrize("fname,bits", [
        ("bf16", 16), ("bf14", 14), ("bf12", 12), ("bf10", 10),
        ("fp16", 16), ("e5m2", 8), ("e4m3", 8), ("fp32", 32)])
    def test_bit_widths(self, fname, bits):
        assert FORMATS[fname].bits == bits

    def test_known_max_finite(self):
        # IEEE-style grids: fp16 = 65504; e5m2 = 57344; e4m3 (with
        # inf/nan space reserved, unlike OCP-fn's 448) = 240
        assert FORMATS["fp16"].max_finite == 65504.0
        assert E5M2.max_finite == 57344.0
        assert E4M3.max_finite == 240.0
        assert FORMATS["fp32"].max_finite == float(np.finfo(np.float32).max)

    def test_known_min_normals(self):
        assert E5M2.min_normal == 2.0 ** -14 == FORMATS["fp16"].min_normal
        assert E4M3.min_normal == 2.0 ** -6
        assert E5M2.sub_spacing == 2.0 ** -16
        assert E4M3.sub_spacing == 2.0 ** -9
