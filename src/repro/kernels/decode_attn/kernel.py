"""Pallas TPU kernel: KV-cache decode attention (memory-bound streaming).

One new token attends over a long cache: arithmetic intensity is O(1) flops
per cache byte, so the kernel is a pure HBM-bandwidth stream.  All G = H/Hkv
query heads of a KV group are processed together against each streamed
(TK, Dh) cache tile — the cache is read exactly once, the roofline optimum.
Online softmax state (m, l, acc) lives in VMEM scratch across the KV sweep.

Grid: (B * Hkv, S / TK).  Dynamic cache lengths are handled with a per-row
``pos`` operand masking cols >= pos; ``pos`` is a scalar-prefetch operand
(SMEM), read as a scalar per grid row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TK = 1024
NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, tk: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pl.program_id(0)]
    # skip tiles entirely past the valid length
    @pl.when(kb * tk < pos)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (G, Dh)
        k = k_ref[0].astype(jnp.float32)  # (TK, Dh)
        v = v_ref[0].astype(jnp.float32)  # (TK, Dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, TK)
        cols = kb * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "tk", "interpret"))
def decode_attention_pallas(q, k, v, pos, scale: float | None = None,
                            tk: int = DEFAULT_TK, interpret: bool = False):
    """q: (B, H, Dh); k, v: (B, Hkv, S, Dh); pos: (B,) -> (B, H, Dh)."""
    b, h, dh = q.shape
    hkv, s = k.shape[1], k.shape[2]
    group = h // hkv
    scale = scale if scale is not None else dh ** -0.5
    tk = min(tk, s)
    pad = (-s) % tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s = s + pad
    qr = q.reshape(b, hkv, group, dh).reshape(b * hkv, group, dh)
    kr = k.reshape(b * hkv, s, dh)
    vr = v.reshape(b * hkv, s, dh)
    pos_r = jnp.broadcast_to(pos[:, None], (b, hkv)).reshape(b * hkv).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hkv, s // tk),
            in_specs=[
                pl.BlockSpec((1, group, dh), lambda bh, kb, p: (bh, 0, 0)),
                pl.BlockSpec((1, tk, dh), lambda bh, kb, p: (bh, kb, 0)),
                pl.BlockSpec((1, tk, dh), lambda bh, kb, p: (bh, kb, 0)),
            ],
            out_specs=pl.BlockSpec((1, group, dh), lambda bh, kb, p: (bh, 0, 0)),
            scratch_shapes=[_vmem((group, 1)), _vmem((group, 1)),
                            _vmem((group, dh))],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, dh), q.dtype),
        interpret=interpret,
    )(pos_r, qr, kr, vr)
    return out.reshape(b, hkv, group, dh).reshape(b, h, dh)


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)
