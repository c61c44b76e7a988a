"""Pallas TPU decode attention — one new query token against a long KV
cache (the serve_step hot spot for decode_32k / long_500k).

Decode is bandwidth-bound: the whole valid cache prefix streams HBM→VMEM
once per step. Grid: (batch·kv_heads, n_s_blocks) with the cache-block dim
sequential; online-softmax state (m, l, acc) for the G query-group rows of
one KV head lives in VMEM scratch. Positions > pos are masked (the caller
has already written the new token's K/V at index pos).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG = -1.0e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, bs: int, ns: int):
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0]          # (G, Dh)
    k = k_ref[0]          # (bs, Dh)
    v = v_ref[0]
    pos = pos_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale             # (G, bs)
    kj = sb * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kj <= pos, s, NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(sb == ns - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "bs", "interpret")
)
def decode_attention(q, k_cache, v_cache, pos, *, scale: float,
                     bs: int = 512, interpret: Optional[bool] = None):
    """q (B,KV,G,Dh); caches (B,S,KV,Dh); pos scalar -> (B,KV,G,Dh).
    ``interpret=None`` compiles on TPU and interprets on CPU."""
    b, kvh, g, dh = q.shape
    s_cache = k_cache.shape[1]
    bs = min(bs, s_cache)
    assert s_cache % bs == 0
    ns = s_cache // bs

    qf = q.reshape(b * kvh, g, dh)
    kf = jnp.moveaxis(k_cache, 1, 2).reshape(b * kvh, s_cache, dh)
    vf = jnp.moveaxis(v_cache, 1, 2).reshape(b * kvh, s_cache, dh)
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32)[None], (b * kvh,)
    )

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=bs, ns=ns),
        grid=(b * kvh, ns),
        in_specs=[
            pl.BlockSpec((1,), lambda h, sb: (h,)),
            pl.BlockSpec((1, g, dh), lambda h, sb: (h, 0, 0)),
            pl.BlockSpec((1, bs, dh), lambda h, sb: (h, sb, 0)),
            pl.BlockSpec((1, bs, dh), lambda h, sb: (h, sb, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dh), lambda h, sb: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(pos_arr, qf, kf, vf)
    return out.reshape(b, kvh, g, dh)
