"""FMAC-model arithmetic bound to a :class:`PrecisionPolicy`.

Models the paper's compute unit exactly (§2, Table 1): every operator takes
16-bit inputs, multiplies/accumulates in a 32-bit accumulator, and rounds
its output once to 16 bits.

* native formats (bf16 / fp16 / fp32): inputs stored in the native dtype;
  dots/einsums use ``preferred_element_type=float32`` (the 32-bit
  accumulator — on TPU this is literally the MXU) and the result is cast
  back once (XLA RNE cast = nearest rounding).
* simulated sub-16-bit formats (bf14/bf12/bf10): values are carried in f32
  *snapped to the format grid*; after every operator output we re-snap with
  :func:`round_nearest`. Accumulation inside a dot happens in f32 — again
  the FMAC accumulator — and only the operator output is rounded, matching
  QPyTorch's modelling in the paper.

Activations / normalizations follow the paper's footnote 4: computed as one
fused op in f32 internally, rounded once at the output.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.formats import round_nearest
from repro.core.policy import PrecisionPolicy

__all__ = ["QArith"]


class QArith:
    """Operator set for one precision policy. Stateless; safe under jit."""

    def __init__(self, policy: PrecisionPolicy):
        self.policy = policy
        self._fmt = policy.compute_format
        self._native = policy.native or policy.compute_format.name == "fp16"
        # XLA:CPU's DotThunk cannot execute some bf16×bf16→f32 dot layouts
        # (notably batched dots inside scanned bodies). Upcasting the
        # *already-rounded* bf16 inputs to f32 is bit-identical (bf16 ⊂
        # f32 exactly; accumulation is f32 either way) — a CPU-only
        # execution detail, not a numerics change. TPU path untouched.
        self._upcast_dots = jax.default_backend() == "cpu"

    def _fmac_in(self, x: jax.Array) -> jax.Array:
        y = self.cast(x)
        if self._upcast_dots and y.dtype in (jnp.bfloat16, jnp.float16):
            return y.astype(jnp.float32)
        return y

    # -- casts --------------------------------------------------------------
    def cast(self, x: jax.Array) -> jax.Array:
        """Snap a value onto the compute grid (= write it through the FPU)."""
        if self._native:
            return x.astype(self.policy.compute_dtype)
        return round_nearest(x, self._fmt)

    def cast_in(self, x: jax.Array) -> jax.Array:
        """Cast an input (e.g. embedded tokens, fp32 constants) for compute."""
        return self.cast(x)

    @property
    def dtype(self):
        return self.policy.compute_dtype

    # -- FMAC-backed contractions -------------------------------------------
    def dot(self, a: jax.Array, b: jax.Array) -> jax.Array:
        out = jnp.dot(self._fmac_in(a), self._fmac_in(b),
                      preferred_element_type=jnp.float32)
        return self.cast(out)

    def einsum(self, spec: str, *args: jax.Array) -> jax.Array:
        args = tuple(self._fmac_in(a) for a in args)
        out = jnp.einsum(spec, *args, preferred_element_type=jnp.float32)
        return self.cast(out)

    def grouped_matmul(self, x: jax.Array, w: jax.Array, group_sizes,
                       group_offset) -> jax.Array:
        """Rows of ``x`` sorted by group, each held group's rows through
        its matrix of ``w`` (:func:`repro.kernels.grouped_matmul.gmm`);
        the rows of groups not held come back 0. Rounded as ``einsum``."""
        from repro.kernels.grouped_matmul import gmm
        out_dtype = self.dtype if self._native else jnp.float32
        return self.cast(gmm(self._fmac_in(x), self._fmac_in(w), group_sizes,
                             group_offset, out_dtype=out_dtype))

    def matmul_f32out(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Contraction leaving the result in the 32-bit accumulator — used
        when the very next op consumes it fused (e.g. logits → softmax-CE)."""
        return jnp.dot(self._fmac_in(a), self._fmac_in(b),
                       preferred_element_type=jnp.float32)

    # -- elementwise ops (each = one FPU op, output rounded) -----------------
    def add(self, a, b):
        return self.cast(jnp.add(self._f32(a), self._f32(b)))

    def sub(self, a, b):
        return self.cast(jnp.subtract(self._f32(a), self._f32(b)))

    def mul(self, a, b):
        return self.cast(jnp.multiply(self._f32(a), self._f32(b)))

    def _f32(self, x):
        return jnp.asarray(x, jnp.float32) if not self._native else jnp.asarray(x, self.dtype)

    # -- fused activation / normalization (paper footnote 4) -----------------
    def act(self, fn, *args) -> jax.Array:
        """Apply ``fn`` in f32 internally, round the output once."""
        out = fn(*[jnp.asarray(a, jnp.float32) for a in args])
        return self.cast(out)

    def rmsnorm(self, x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
        # reductions in f32 (the accumulator), elementwise normalize in the
        # compute dtype — each elementwise op rounds to 16 bits under the
        # FMAC model anyway, and this halves the HBM traffic of the norm
        # (§Perf command-r iteration; matches TPU production practice)
        if not self._native:
            def _f(xf, sf):
                var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                return xf * jax.lax.rsqrt(var + eps) * sf
            return self.act(_f, x, scale)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps).astype(self.dtype)
        return (x.astype(self.dtype) * inv) * scale.astype(self.dtype)

    def layernorm(self, x: jax.Array, scale: jax.Array, bias: jax.Array,
                  eps: float = 1e-5) -> jax.Array:
        if not self._native:
            def _f(xf, sf, bf):
                mu = jnp.mean(xf, axis=-1, keepdims=True)
                var = jnp.var(xf, axis=-1, keepdims=True)
                return (xf - mu) * jax.lax.rsqrt(var + eps) * sf + bf
            return self.act(_f, x, scale, bias)
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps).astype(self.dtype)
        mu = mu.astype(self.dtype)
        return ((x.astype(self.dtype) - mu) * inv * scale.astype(self.dtype)
                + bias.astype(self.dtype))

    def softmax(self, x: jax.Array, axis: int = -1) -> jax.Array:
        return self.act(partial(jax.nn.softmax, axis=axis), x)

    def silu(self, x: jax.Array) -> jax.Array:
        return self.act(jax.nn.silu, x)

    def gelu(self, x: jax.Array) -> jax.Array:
        return self.act(partial(jax.nn.gelu, approximate=True), x)
