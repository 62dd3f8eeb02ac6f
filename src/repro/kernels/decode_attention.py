"""Pallas kernel: fused single-token decode attention over the slotted KV pool.

One grid step per decode lane (slot). The whole per-lane pipeline —
QK^T scores, logit softcap, causal/ring/window masking, softmax, PV — runs
in one kernel launch with f32 internals, so the KV pool is read exactly
once per lane and the (Sc,)-sized score/probability rows never round-trip
through HBM. Lane masking happens *in the kernel*: a parked lane
(``q_pos < 0`` — the continuous-batching engine's ``active`` mask routed
through its position vector) takes the ``pl.when`` fast path that writes
zeros and never touches its KV block, so parked lanes cost zero HBM
traffic on the pool.

GQA stays in the grouped form (q reshaped ``(B, Hkv, G, D)``) — decode is
memory-bound on the cache, and the grouped contraction reads each KV head
once for its G query heads.

Numerics mirror :func:`repro.models.layers.decode_attention` op-for-op
(f32 scores, ``jax.nn.softmax``, probabilities cast to the compute dtype
before PV, one output rounding by the caller) so the engine's
token-for-token parity contract with ``generate()`` survives the swap
(tests/test_serve.py::TestFusedDecode).

CPU CI runs the same kernel in interpret mode (the module default off
TPU). On a real TPU the cache-length axis ``Sc`` should be padded to the
128-lane register width by the caller; the kernel itself is
shape-agnostic.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_decode_attention", "decode_attention_kernel",
           "fused_paged_decode_attention", "paged_decode_attention_kernel"]

NEG_INF = -1e30


def _attend(q_ref, k, v, k_pos, q_pos, out_ref, *, scale, window, softcap,
            p_dtype):
    """Score/mask/softmax/PV for one lane, one KV head at a time.

    q_ref (1,Hkv,G,D); k/v (Sc, Hkv·D) — the KV heads side by side on the
    lane axis, so a (Sc, 2, 128) cache row costs no sublane padding in
    VMEM; k_pos (1,Sc). Per head the contractions are plain 2-D matmuls.
    """
    hkv, d = q_ref.shape[1], q_ref.shape[3]
    ok = (k_pos <= q_pos) & (k_pos >= 0)                # (1, Sc)
    if window is not None:
        ok &= q_pos - k_pos < window
    for h in range(hkv):
        q = q_ref[0, h]                                 # (G, D)
        kh = k[:, h * d:(h + 1) * d]                    # (Sc, D)
        s = jax.lax.dot_general(q, kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out_ref[0, h] = jnp.dot(p.astype(p_dtype), v[:, h * d:(h + 1) * d],
                                preferred_element_type=jnp.float32)


def decode_attention_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref,
                            out_ref, *, scale: float, window, softcap,
                            p_dtype):
    """One lane: qpos (B,) in SMEM; q (1,Hkv,G,D); k/v (1,Sc,Hkv·D);
    kpos (1,1,Sc)."""
    q_pos = qpos_ref[pl.program_id(0)]

    @pl.when(q_pos >= 0)
    def _active():
        _attend(q_ref, k_ref[0], v_ref[0], kpos_ref[0], q_pos, out_ref,
                scale=scale, window=window, softcap=softcap, p_dtype=p_dtype)

    @pl.when(q_pos < 0)
    def _parked():
        # parked lane: zero output, KV block untouched (no HBM read)
        out_ref[...] = jnp.zeros_like(out_ref)


def fused_decode_attention(q, k_cache, v_cache, k_pos, q_pos, *,
                           window=None, softcap=None, p_dtype=jnp.bfloat16,
                           interpret: bool | None = None):
    """q: (B,1,Hq,D); caches: (B,Sc,Hkv,D); k_pos: (B,Sc) i32;
    q_pos: (B,) i32 (−1 ⇒ parked lane). Returns f32 (B,1,Hq,D) —
    unrounded, the caller applies the policy's single output rounding."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, _, Hq, D = q.shape
    _, Sc, Hkv, _ = k_cache.shape
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, D)
    scale = 1.0 / (D ** 0.5)

    q_bs = pl.BlockSpec((1, Hkv, group, D), lambda i, qp: (i, 0, 0, 0))
    kv_bs = pl.BlockSpec((1, Sc, Hkv * D), lambda i, qp: (i, 0, 0))
    out = pl.pallas_call(
        partial(decode_attention_kernel, scale=scale, window=window,
                softcap=softcap, p_dtype=p_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[q_bs, kv_bs, kv_bs,
                      pl.BlockSpec((1, 1, Sc), lambda i, qp: (i, 0, 0))],
            out_specs=q_bs),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), jnp.float32),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), qg, k_cache.reshape(B, Sc, Hkv * D),
      v_cache.reshape(B, Sc, Hkv * D), k_pos.reshape(B, 1, Sc))
    return out.reshape(B, 1, Hq, D)


def paged_decode_attention_kernel(qpos_ref, table_ref, q_ref, kp_ref, vp_ref,
                                  pp_ref, out_ref, *, scale: float,
                                  n_blocks: int, window, softcap, p_dtype):
    """One lane against the paged pool.

    qpos (B,) and table (B·n_blocks,) are scalar-prefetched into SMEM;
    q (1,Hkv,G,D); kp/vp (R,P,Hkv·D) and pp (R,1,P) are the *full* pool
    (block index maps pin them, so every lane reads the same blocks).
    The lane's KV view is gathered row-by-row with dynamic loads —
    ``n_blocks`` is static, so the gather unrolls — and then runs the
    exact score/mask/softmax/PV pipeline of :func:`decode_attention_kernel`:
    token at logical position p sits at view index p, so the result is
    bitwise-identical to the contiguous kernel on an equal-length cache.
    """
    i = pl.program_id(0)
    q_pos = qpos_ref[i]

    @pl.when(q_pos >= 0)
    def _active():
        ks, vs, ps = [], [], []
        for b in range(n_blocks):
            pg = table_ref[i * n_blocks + b]
            ks.append(kp_ref[pg])                      # (P, Hkv·D)
            vs.append(vp_ref[pg])
            ps.append(pp_ref[pg])                      # (1, P)
        _attend(q_ref, jnp.concatenate(ks, axis=0), jnp.concatenate(vs, axis=0),
                jnp.concatenate(ps, axis=1), q_pos, out_ref, scale=scale,
                window=window, softcap=softcap, p_dtype=p_dtype)

    @pl.when(q_pos < 0)
    def _parked():
        out_ref[...] = jnp.zeros_like(out_ref)


def fused_paged_decode_attention(q, k_pages, v_pages, pos_pages, block_table,
                                 q_pos, *, window=None, softcap=None,
                                 p_dtype=jnp.bfloat16,
                                 interpret: bool | None = None):
    """q: (B,1,Hq,D); pools: (R,P,Hkv,D) + (R,P) i32; block_table:
    (B,n_blocks) i32 (null rows' positions are −1, so they mask out);
    q_pos: (B,) i32 (−1 ⇒ parked lane). Returns f32 (B,1,Hq,D) —
    unrounded, the caller applies the policy's single output rounding.

    The pool rides into the kernel as one whole-array VMEM block per
    operand (the lane's pages are gathered in-kernel via the table), so
    the pool must fit the kernel's VMEM: the chip's compiler refuses a
    larger one (docs/kernels.md gives the largest that compiled). A
    kernel that streams pages by scalar prefetch is the real-size form.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, _, Hq, D = q.shape
    R, P, Hkv, _ = k_pages.shape
    n_blocks = block_table.shape[1]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, D)
    scale = 1.0 / (D ** 0.5)

    q_bs = pl.BlockSpec((1, Hkv, group, D), lambda i, qp, t: (i, 0, 0, 0))
    pool_bs = pl.BlockSpec((R, P, Hkv * D), lambda i, qp, t: (0, 0, 0))
    out = pl.pallas_call(
        partial(paged_decode_attention_kernel, scale=scale,
                n_blocks=n_blocks, window=window, softcap=softcap,
                p_dtype=p_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[q_bs, pool_bs, pool_bs,
                      pl.BlockSpec((R, 1, P), lambda i, qp, t: (0, 0, 0))],
            out_specs=q_bs),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), jnp.float32),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32), qg,
      k_pages.reshape(R, P, Hkv * D), v_pages.reshape(R, P, Hkv * D),
      pos_pages.reshape(R, 1, P))
    return out.reshape(B, 1, Hq, D)
