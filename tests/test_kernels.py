"""Per-kernel shape/dtype sweeps: Pallas (interpret on CPU) ≡ ref.py."""
import math

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.fused_adamw import fused_adamw, tiling
from repro.kernels.fused_sgd import fused_sgd
from repro.kernels.qmatmul import qmatmul
from repro.kernels.sr_cast import sr_cast

HP = dict(lr=1e-3, b1=0.9, b2=0.99609375, eps=1e-8, wd=0.01,
          c1=0.9, c2=0.99609375)


def _bits(key, shape):
    return jax.random.bits(key, shape=shape, dtype=jnp.uint32)


def assert_bf16_close(a, b, max_frac=0.005, scale=None, atol=None):
    """Fused-kernel vs op-by-op reference: FMA contraction inside the
    kernel may land one f32-ulp away from the two-rounding reference,
    which flips a bf16 tie ~0.1% of the time. Allow ≤1 bf16 ulp on a tiny
    fraction of elements; everything else must be bit-exact. ``scale``
    widens the ulp reference (the Kahan c-buffer carries residuals of the
    *weight*, so its 1-ulp flips scale with |w|, not |c|)."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    neq = a != b
    frac = float(neq.mean())
    assert frac <= max_frac, f"{frac:.4%} of elements differ"
    mag = jnp.maximum(jnp.abs(bf), 2.0 ** -126)
    if scale is not None:
        mag = jnp.maximum(mag, jnp.abs(scale.astype(jnp.float32)))
    tol = 2.0 ** -7 * mag
    if atol is not None:
        tol = tol + atol
    assert bool(jnp.all(jnp.abs(af - bf) <= tol + 1e-30)), "diff > 1 ulp"


@pytest.mark.parametrize("n", [1, 7, 128, 1000, 32768, 100_001])
def test_sr_cast_shapes(n):
    key = jax.random.PRNGKey(n)
    x = jax.random.normal(key, (n,), jnp.float32) * 7
    bits = _bits(key, (n,))
    assert bool(jnp.all(sr_cast(x, bits) == ref.sr_cast_ref(x, bits)))


def test_sr_cast_extreme_values():
    x = jnp.array([0.0, -0.0, 1e-38, -1e-38, 3e38, -3e38, jnp.inf, jnp.nan],
                  jnp.float32)
    bits = _bits(jax.random.PRNGKey(0), x.shape)
    a, b = sr_cast(x, bits), ref.sr_cast_ref(x, bits)
    both_nan = jnp.isnan(a) & jnp.isnan(b)
    assert bool(jnp.all((a == b) | both_nan))


def test_sr_cast_2d_input():
    x = jax.random.normal(jax.random.PRNGKey(1), (33, 65), jnp.float32)
    bits = _bits(jax.random.PRNGKey(2), x.shape)
    out = sr_cast(x, bits)
    assert out.shape == x.shape
    assert bool(jnp.all(out == ref.sr_cast_ref(x, bits)))


@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 128, 512),
                                 (384, 256, 640)])
@pytest.mark.parametrize("stochastic", [False, True])
def test_qmatmul_sweep(mnk, stochastic):
    M, N, K = mnk
    kx, ky, kb = jax.random.split(jax.random.PRNGKey(M + N + K), 3)
    x = jax.random.normal(kx, (M, K), jnp.bfloat16)
    y = jax.random.normal(ky, (K, N), jnp.bfloat16)
    bits = _bits(kb, (M, N)) if stochastic else None
    got = qmatmul(x, y, bits=bits, bm=128, bn=128, bk=128)
    want = ref.qmatmul_ref(x, y, bits=bits)
    if K == 128:
        # single K tile: identical contraction → bit-exact
        assert bool(jnp.all(got == want))
    else:
        # K-tiled f32 partial sums reassociate the contraction; both are
        # valid f32 accumulations — outputs may differ by 1 bf16 ulp
        assert_bf16_close(got, want)


def test_qmatmul_k_accumulation_in_f32():
    """Many small K contributions must not be lost to bf16 accumulation —
    the 32-bit-accumulator property of the paper's Table 1."""
    K = 1024
    x = jnp.full((128, K), 0.01, jnp.bfloat16)
    y = jnp.full((K, 128), 0.01, jnp.bfloat16)
    out = qmatmul(x, y, bm=128, bn=128, bk=128).astype(jnp.float32)
    expect = K * float(jnp.bfloat16(0.01)) ** 2
    assert abs(float(out[0, 0]) / expect - 1) < 0.01


# Flat cases (an int n: a leaf of shape (n,)) are raveled into (rows, 128)
# tiles; native cases (a shape) run where they lie, each with ragged edge
# blocks (rows or columns that no block divides). The kernel's
# arithmetic on random data is held to the reference within 1 ulp on the
# flat cases; every case is held bit for bit on the dyadic grid, against
# the reference and against the flat path on the raveled leaf.
NATIVE = [(3, 40, 1152), (300, 384), (2, 48, 1408)]
ADAMW_CASES = [5, 512, 4096, 50_000, *NATIVE]
SGD_CASES = [3, 1000, 8192, *NATIVE]
DYADIC_ADAMW = dict(lr=2.0 ** -6, b1=0.5, b2=0.5, eps=2.0 ** -10, wd=0.0,
                    c1=0.5, c2=0.5)
DYADIC_SGD = dict(lr=0.25, momentum=0.5, wd=0.0)


def _shape(n):
    shape = n if isinstance(n, tuple) else (n,)
    native, kshape, block, _ = tiling(shape)
    assert native == isinstance(n, tuple)
    if native:
        assert kshape[-2] % block[-2] or kshape[-1] % block[-1], "not ragged"
    return shape


def _assert_bitwise(got, want, flat, shape):
    """``got`` equals the reference and the flat path's outputs, bit for
    bit, element for element."""
    for a, b, f in zip(got, want, flat):
        if a is None:
            assert b is None and f is None
        else:
            assert bool(jnp.all(a == b))
            assert bool(jnp.all(a == f.reshape(shape)))


def _adamw_close_on_random_data(key, shape, bits, stochastic, kahan):
    w = jax.random.normal(key, shape, jnp.bfloat16)
    m = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16) * 0.1
    v = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), shape, jnp.bfloat16)) * 0.01
    g = jax.random.normal(jax.random.fold_in(key, 3), shape, jnp.bfloat16)
    c = jnp.zeros(shape, jnp.bfloat16) if kahan else None
    got = fused_adamw(w, m, v, g, c=c, bits=bits, stochastic=stochastic, **HP)
    want = ref.fused_adamw_ref(w, m, v, g, c=c, bits=bits,
                               stochastic=stochastic, **HP)
    for i, (a, b) in enumerate(zip(got, want)):
        if a is None:
            assert b is None
        else:
            # m-slot FMA under catastrophic cancellation: diff bounded by
            # f32 rounding of the ADDENDS (not of the tiny result)
            atol = (2.0 ** -22 * (jnp.abs(m.astype(jnp.float32))
                                  + jnp.abs(g.astype(jnp.float32)))
                    if i == 1 else None)
            assert_bf16_close(a, b, scale=w if i == 3 else None, atol=atol)


@pytest.mark.parametrize("n", ADAMW_CASES)
@pytest.mark.parametrize("stochastic,kahan", [(True, False), (False, False),
                                              (True, True), (False, True)])
def test_fused_adamw_sweep(n, stochastic, kahan):
    shape = _shape(n)
    key = jax.random.PRNGKey(math.prod(shape))
    bits = _bits(key, shape)
    if isinstance(n, int):
        _adamw_close_on_random_data(key, shape, bits, stochastic, kahan)
    # on the dyadic grid the arithmetic is exact, so the layout may change
    # no bit: the kernel equals the reference and the flat path
    ks = jax.random.split(jax.random.fold_in(key, 4), 5)
    w, g = _dyadic(ks[0], shape), _dyadic(ks[1], shape)
    m = _dyadic(ks[2], shape, scale=0.25)
    v = jnp.abs(_dyadic(ks[3], shape, scale=0.25))
    c = _dyadic(ks[4], shape, scale=2.0 ** -8) if kahan else None
    flat_in = [x.reshape(-1) for x in (w, m, v, g)]
    got = fused_adamw(w, m, v, g, c=c, bits=bits, stochastic=stochastic,
                      **DYADIC_ADAMW)
    want = ref.fused_adamw_ref(w, m, v, g, c=c, bits=bits,
                               stochastic=stochastic, **DYADIC_ADAMW)
    flat = fused_adamw(*flat_in, c=None if c is None else c.reshape(-1),
                       bits=bits.reshape(-1), stochastic=stochastic,
                       **DYADIC_ADAMW)
    _assert_bitwise(got, want, flat, shape)


def _sgd_close_on_random_data(key, shape, bits, stochastic, kahan):
    w = jax.random.normal(key, shape, jnp.bfloat16)
    m = jnp.zeros(shape, jnp.bfloat16)
    g = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    c = jnp.zeros(shape, jnp.bfloat16) if kahan else None
    got = fused_sgd(w, m, g, c=c, bits=bits, stochastic=stochastic,
                    lr=0.1, momentum=0.9, wd=1e-4)
    want = ref.fused_sgd_ref(w, m, g, c=c, bits=bits, stochastic=stochastic,
                             lr=0.1, momentum=0.9, wd=1e-4)
    for i, (a, b) in enumerate(zip(got, want)):
        if a is None:
            assert b is None
        else:
            atol = (2.0 ** -22 * (jnp.abs(m.astype(jnp.float32))
                                  + jnp.abs(g.astype(jnp.float32)))
                    if i == 1 else None)
            assert_bf16_close(a, b, scale=w if i == 2 else None, atol=atol)


@pytest.mark.parametrize("n", SGD_CASES)
@pytest.mark.parametrize("stochastic,kahan", [(True, False), (True, True),
                                              (False, True), (False, False)])
def test_fused_sgd_sweep(n, stochastic, kahan):
    shape = _shape(n)
    key = jax.random.PRNGKey(math.prod(shape) + 1)
    bits = _bits(key, shape)
    if isinstance(n, int):
        _sgd_close_on_random_data(key, shape, bits, stochastic, kahan)
    ks = jax.random.split(jax.random.fold_in(key, 2), 4)
    w, g = _dyadic(ks[0], shape), _dyadic(ks[1], shape)
    m = _dyadic(ks[2], shape, scale=0.25)
    c = _dyadic(ks[3], shape, scale=2.0 ** -8) if kahan else None
    got = fused_sgd(w, m, g, c=c, bits=bits, stochastic=stochastic,
                    **DYADIC_SGD)
    want = ref.fused_sgd_ref(w, m, g, c=c, bits=bits, stochastic=stochastic,
                             **DYADIC_SGD)
    flat = fused_sgd(w.reshape(-1), m.reshape(-1), g.reshape(-1),
                     c=None if c is None else c.reshape(-1),
                     bits=bits.reshape(-1), stochastic=stochastic,
                     **DYADIC_SGD)
    _assert_bitwise(got, want, flat, shape)


def test_fused_kahan_accumulates_small_updates():
    """End-to-end kernel-level replica of the paper's mechanism: tiny
    updates cancelled by nearest rounding are recovered by the Kahan
    variant of the fused kernel."""
    n = 256
    w = jnp.ones((n,), jnp.bfloat16)
    m = jnp.zeros((n,), jnp.bfloat16)
    c = jnp.zeros((n,), jnp.bfloat16)
    g = jnp.full((n,), 1e-4, jnp.bfloat16)
    w_n = w
    for i in range(500):
        w_n, m_n, _ = fused_sgd(w_n, jnp.zeros_like(m), g, c=None,
                                bits=None, stochastic=False, lr=1.0, momentum=0.0)
        w, m2, c = fused_sgd(w, jnp.zeros_like(m), g, c=c, bits=None,
                             stochastic=False, lr=1.0, momentum=0.0)
    assert float(w_n[0]) == 1.0                      # nearest: halted
    assert abs(float(w[0]) - (1 - 0.05)) < 0.01      # kahan: moved


# ---------------------------------------------------------------------------
# Bitwise nearest parity + SR unbiasedness (ISSUE 7 acceptance)
# ---------------------------------------------------------------------------

def _dyadic(key, shape, scale=1.0):
    """bf16 values whose products/sums stay exact in f32: k·2⁻⁴, |k|<16.

    With exact arithmetic an FMA contracts to the same value as mul+add,
    so kernel-vs-reference comparison is bitwise regardless of how the
    two lowerings fuse — the nearest-rounding parity the sweeps above can
    only assert to 1 ulp."""
    k = jax.random.randint(key, shape, -15, 16)
    return (k.astype(jnp.float32) * scale / 16.0).astype(jnp.bfloat16)


@pytest.mark.parametrize("kahan", [False, True])
def test_fused_adamw_nearest_bitwise_on_dyadic_grid(kahan):
    n = 4096
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 4)
    w = _dyadic(ks[0], (n,))
    m = _dyadic(ks[1], (n,), scale=0.25)
    v = jnp.abs(_dyadic(ks[2], (n,), scale=0.25))
    g = _dyadic(ks[3], (n,))
    c = jnp.zeros((n,), jnp.bfloat16) if kahan else None
    hp = dict(lr=2.0 ** -6, b1=0.5, b2=0.5, eps=2.0 ** -10, wd=0.0,
              c1=0.5, c2=0.5)
    got = fused_adamw(w, m, v, g, c=c, bits=None, stochastic=False, **hp)
    want = ref.fused_adamw_ref(w, m, v, g, c=c, bits=None,
                               stochastic=False, **hp)
    for a, b in zip(got, want):
        if a is None:
            assert b is None
        else:
            assert bool(jnp.all(a == b))


@pytest.mark.parametrize("kahan", [False, True])
def test_fused_sgd_nearest_bitwise_on_dyadic_grid(kahan):
    n = 4096
    key = jax.random.PRNGKey(12)
    ks = jax.random.split(key, 3)
    w = _dyadic(ks[0], (n,))
    m = _dyadic(ks[1], (n,), scale=0.25)
    g = _dyadic(ks[2], (n,))
    c = jnp.zeros((n,), jnp.bfloat16) if kahan else None
    got = fused_sgd(w, m, g, c=c, bits=None, stochastic=False,
                    lr=0.25, momentum=0.5, wd=0.0)
    want = ref.fused_sgd_ref(w, m, g, c=c, bits=None, stochastic=False,
                             lr=0.25, momentum=0.5, wd=0.0)
    for a, b in zip(got, want):
        if a is None:
            assert b is None
        else:
            assert bool(jnp.all(a == b))


def test_fused_sgd_sr_is_unbiased_where_nearest_stalls():
    """The paper's core claim at kernel level: a sub-ulp update (|η·g| <
    ulp(w)/2) is erased by nearest rounding but preserved in expectation
    by SR — the empirical mean over independent bit draws must match the
    exact f32 value, not the nearest-rounded one."""
    n = 1 << 16
    w = jnp.ones((n,), jnp.bfloat16)
    m = jnp.zeros((n,), jnp.bfloat16)
    g = jnp.full((n,), 2.0 ** -11, jnp.bfloat16)   # ulp(1)/8
    exact = 1.0 - 2.0 ** -11
    w_near, _, _ = fused_sgd(w, m, g, c=None, bits=None, stochastic=False,
                             lr=1.0, momentum=0.0, wd=0.0)
    assert bool(jnp.all(w_near == jnp.bfloat16(1.0)))       # halted
    bits = _bits(jax.random.PRNGKey(13), (n,))
    w_sr, _, _ = fused_sgd(w, m, g, c=None, bits=bits, stochastic=True,
                           lr=1.0, momentum=0.0, wd=0.0)
    mean = float(jnp.mean(w_sr.astype(jnp.float32)))
    # binomial mean: p = 1/8 of elements drop one ulp; 5σ ≈ 2.6e-5
    assert abs(mean - exact) < 3e-5, (mean, exact)
    assert mean < 1.0                                        # it moved


def test_fused_adamw_sr_is_unbiased_where_nearest_stalls():
    n = 1 << 16
    w = jnp.ones((n,), jnp.bfloat16)
    m = jnp.zeros((n,), jnp.bfloat16)
    v = jnp.zeros((n,), jnp.bfloat16)
    g = jnp.ones((n,), jnp.bfloat16)
    hp = dict(lr=2.0 ** -11, b1=0.9, b2=0.99609375, eps=0.0, wd=0.0,
              c1=0.9, c2=0.99609375)
    w_near, m1, v1, _ = fused_adamw(w, m, v, g, c=None, bits=None,
                                    stochastic=False, **hp)
    assert bool(jnp.all(w_near == jnp.bfloat16(1.0)))       # halted
    # exact pre-rounding value, mirroring the kernel's elementwise math
    mf = jnp.bfloat16(0.1 * 1.0).astype(jnp.float32)
    vf = jnp.bfloat16((1 - hp["b2"]) * 1.0).astype(jnp.float32)
    m_hat = jnp.bfloat16(mf / 0.1).astype(jnp.float32)
    v_hat = jnp.bfloat16(jnp.sqrt(vf / (1 - hp["c2"]))).astype(jnp.float32)
    u = jnp.bfloat16(hp["lr"] * m_hat / v_hat).astype(jnp.float32)
    exact = float(1.0 - u)
    bits = _bits(jax.random.PRNGKey(14), (n,))
    w_sr, _, _, _ = fused_adamw(w, m, v, g, c=None, bits=bits,
                                stochastic=True, **hp)
    mean = float(jnp.mean(w_sr.astype(jnp.float32)))
    assert abs(mean - exact) < 3e-5, (mean, exact)
    assert mean < 1.0


# ---------------------------------------------------------------------------
# Fused decode attention ≡ repro.models.layers.decode_attention
# ---------------------------------------------------------------------------

class TestFusedDecodeAttention:
    B, SC, HKV, GROUP, D = 4, 16, 2, 2, 8

    def _inputs(self, seed=0, filled=10):
        from repro.core import get_policy
        from repro.core.qarith import QArith
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 3)
        hq = self.HKV * self.GROUP
        q = jax.random.normal(ks[0], (self.B, 1, hq, self.D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (self.B, self.SC, self.HKV, self.D),
                              jnp.bfloat16)
        v = jax.random.normal(ks[2], (self.B, self.SC, self.HKV, self.D),
                              jnp.bfloat16)
        k_pos = jnp.where(jnp.arange(self.SC)[None, :] < filled,
                          jnp.arange(self.SC)[None, :],
                          -1).astype(jnp.int32).repeat(self.B, 0)
        q_pos = jnp.full((self.B,), filled - 1, jnp.int32)
        return QArith(get_policy("bf16_standard")), q, k, v, k_pos, q_pos

    def _both(self, qa, q, k, v, k_pos, q_pos, **kw):
        from repro.kernels import dispatch
        from repro.models.layers import decode_attention
        want = decode_attention(qa, q, k, v, k_pos, q_pos=q_pos, **kw)
        with dispatch.fused_decode():
            got = decode_attention(qa, q, k, v, k_pos, q_pos=q_pos, **kw)
        return got, want

    def test_bitwise_parity_plain(self):
        got, want = self._both(*self._inputs())
        assert got.dtype == want.dtype and got.shape == want.shape
        assert bool(jnp.all(got == want))

    def test_bitwise_parity_window_and_softcap(self):
        qa, q, k, v, k_pos, q_pos = self._inputs(seed=1, filled=12)
        got, want = self._both(qa, q, k, v, k_pos, q_pos,
                               window=5, softcap=30.0)
        assert bool(jnp.all(got == want))

    def test_parked_lanes_output_zero_and_match(self):
        qa, q, k, v, k_pos, q_pos = self._inputs(seed=2)
        q_pos = q_pos.at[1].set(-1).at[3].set(-1)   # park two lanes
        got, want = self._both(qa, q, k, v, k_pos, q_pos)
        assert float(jnp.abs(got[1]).max()) == 0.0
        assert float(jnp.abs(got[3]).max()) == 0.0
        # active lanes still match the reference bitwise
        assert bool(jnp.all(got[0] == want[0]))
        assert bool(jnp.all(got[2] == want[2]))

    def test_ragged_depths_jit(self):
        qa, q, k, v, k_pos, q_pos = self._inputs(seed=3)
        q_pos = jnp.asarray([2, 9, 0, 5], jnp.int32)
        from repro.kernels import dispatch
        from repro.models.layers import decode_attention

        @jax.jit
        def fused(q, k, v, kp, qp):
            with dispatch.fused_decode():
                return decode_attention(qa, q, k, v, kp, q_pos=qp)

        got = fused(q, k, v, k_pos, q_pos)
        want = decode_attention(qa, q, k, v, k_pos, q_pos=q_pos)
        assert bool(jnp.all(got == want))

    def test_dispatch_context_restores(self):
        from repro.kernels import dispatch
        assert not dispatch.fused_decode_enabled()
        with dispatch.fused_decode():
            assert dispatch.fused_decode_enabled()
            with dispatch.fused_decode(False):
                assert not dispatch.fused_decode_enabled()
            assert dispatch.fused_decode_enabled()
        assert not dispatch.fused_decode_enabled()
