"""Pallas TPU flash-attention (forward) — the agent prefill / judge
prefill-only scoring hot spot.

Grid: (batch·kv_heads·groups, n_q_blocks, n_k_blocks); the last dim is
sequential ("arbitrary" semantics) so the online-softmax state (m, l, acc)
lives in VMEM scratch across k-blocks: initialised at k==0, folded every
step, written to the output block at the final k step. Causal/window masks
are computed from the grid coordinates; fully-masked (q,k) block pairs
still execute but contribute zeros — block-skipping via the index map is a
recorded hillclimb lever (EXPERIMENTS.md §Perf).

The pure-JAX oracle is kernels.ref.flash_attention_ref; the training path
uses nn.flash (same math, custom_vjp).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window, bq: int, bk: int,
                  nk: int):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0]          # (bq, dh)
    k = k_ref[0]          # (bk, dh)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale             # (bq, bk)
    qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask = kj <= qi
    if window is not None:
        mask = mask & (kj > qi - window)
    s = jnp.where(mask, s, NEG)

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

    @pl.when(kb == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "bq", "bk", "interpret"),
)
def flash_attention_fwd(q, k, v, *, scale: float, causal: bool = True,
                        window=None, bq: int = 512, bk: int = 512,
                        interpret: Optional[bool] = None):
    """q (B,Sq,KV,G,Dh); k/v (B,Sk,KV,Dh) -> (B,Sq,KV,G,Dh).
    ``interpret=None`` compiles on TPU and interprets on CPU."""
    b, sq, kvh, g, dh = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    nq, nk = sq // bq, sk // bk

    # flatten (B,KV,G) into one leading grid axis; per-head K/V reuse
    qf = jnp.moveaxis(q, 1, 3).reshape(b * kvh * g, sq, dh)
    kf = (
        jnp.moveaxis(k, 1, 2)[:, :, None]
        .repeat(g, axis=2)
        .reshape(b * kvh * g, sk, dh)
    )
    vf = (
        jnp.moveaxis(v, 1, 2)[:, :, None]
        .repeat(g, axis=2)
        .reshape(b * kvh * g, sk, dh)
    )

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nk=nk,
        ),
        grid=(b * kvh * g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, qb, kb: (h, qb, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, qb, kb: (h, kb, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, qb, kb: (h, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, qb, kb: (h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh * g, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),     # running max m
            pltpu.VMEM((bq,), jnp.float32),     # running denom l
            pltpu.VMEM((bq, dh), jnp.float32),  # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    out = out.reshape(b, kvh, g, sq, dh)
    return jnp.moveaxis(out, 3, 1)
