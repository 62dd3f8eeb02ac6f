"""The Pallas kernels compile natively for a TPU v5e, at qwen2.5-3b widths.

No chip is needed: the kernels are lowered for a *described* ``v5e:2x2``
topology and compiled by the TPU compiler that ships with ``libtpu``.
What that compiler refuses here (block shapes, VMEM budgets) it would
refuse on the chip. Each test asserts the kernel survived as a Mosaic
``tpu_custom_call`` rather than being lowered some other way.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (fused_decode_attention,
                                            fused_paged_decode_attention)
from repro.kernels.fused_adamw import fused_adamw
from repro.kernels.fused_sgd import fused_sgd
from repro.kernels.sr_cast import sr_cast

LEAF = (2048, 11008)                 # qwen2.5-3b MLP up-projection
B, HQ, HKV, D = 8, 16, 2, 128        # decode lanes; qwen2.5-3b heads
CONTEXT = 2048
PAGE = 16
POOL_ROWS = 4096                     # largest pool that compiled (docs/kernels.md)


@pytest.fixture(scope="module")
def topo():
    """One chip of a described v5e:2x2, with the compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no TPU compiler available: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, topo, *args):
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=topo) for s, d in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_adamw_kahan_sr(topo):
    _compile(lambda w, m, v, g, c, bits: fused_adamw(
        w, m, v, g, c=c, bits=bits, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
        wd=0.01, c1=0.1, c2=0.001, interpret=False), topo,
        *[(LEAF, jnp.bfloat16)] * 5, (LEAF, jnp.uint32))


@pytest.mark.parametrize("shape", [(36, 2048, 256), (2048,)],
                         ids=["native_stacked", "flat"])
def test_fused_adamw_layouts(topo, shape):
    """A stacked leaf (36 layers' k projections) runs where it lies, its
    leading dim squeezed; a norm scale takes the flat (rows, 128) path."""
    _compile(lambda w, m, v, g, c, bits: fused_adamw(
        w, m, v, g, c=c, bits=bits, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
        wd=0.01, c1=0.1, c2=0.001, interpret=False), topo,
        *[(shape, jnp.bfloat16)] * 5, (shape, jnp.uint32))


def test_fused_sgd_kahan_sr(topo):
    _compile(lambda w, m, g, c, bits: fused_sgd(
        w, m, g, c=c, bits=bits, lr=1e-3, interpret=False), topo,
        *[(LEAF, jnp.bfloat16)] * 4, (LEAF, jnp.uint32))


def test_sr_cast(topo):
    _compile(lambda x, bits: sr_cast(x, bits, interpret=False), topo,
             (LEAF, jnp.float32), (LEAF, jnp.uint32))


def test_decode_attention(topo):
    _compile(lambda *a: fused_decode_attention(*a, interpret=False), topo,
             ((B, 1, HQ, D), jnp.bfloat16),
             ((B, CONTEXT, HKV, D), jnp.bfloat16),
             ((B, CONTEXT, HKV, D), jnp.bfloat16),
             ((B, CONTEXT), jnp.int32), ((B,), jnp.int32))


def test_paged_decode_attention(topo):
    _compile(lambda *a: fused_paged_decode_attention(*a, interpret=False),
             topo,
             ((B, 1, HQ, D), jnp.bfloat16),
             ((POOL_ROWS, PAGE, HKV, D), jnp.bfloat16),
             ((POOL_ROWS, PAGE, HKV, D), jnp.bfloat16),
             ((POOL_ROWS, PAGE), jnp.int32),
             ((B, CONTEXT // PAGE), jnp.int32), ((B,), jnp.int32))
