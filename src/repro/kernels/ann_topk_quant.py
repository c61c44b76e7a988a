"""Pallas TPU kernel for the WARM tier's quantized stage-1 (DESIGN.md §10).

The warm tier stores int8 symmetric per-row quantized embeddings (4× the
rows per HBM byte of the hot tier's fp32 matrix), so its coarse scan is an
int8×int8 matmul with int32 accumulation — the MXU runs these at 2–4× the
fp32 rate, and the slab streamed per grid step is a quarter the bytes.

Two-phase retrieval: this kernel performs the COARSE phase only — it
returns the per-query top-R candidates by *approximately* rescaled int8
scores (R = rescore_k, a small multiple of the final k). The host then
rescores those R finalists exactly: fp32 query · dequantized row, which
removes the query-quantization error from the final ordering and the
τ_sim gate (``core/tiers.py::QuantIndex.search_batch``).

Structure mirrors ``ann_topk.py``: the quantized matrix (N, D) streams
HBM→VMEM in (TILE_N, D) int8 slabs; the quantized query block (B, D) stays
resident; each grid step computes a (B, TILE_N) int32 score tile, rescales
to fp32 with the per-row and per-query scales, masks inactive rows, and
reduces to per-tile top-R along the lanes. The (ntiles · R) finalists
merge in one lax.top_k outside the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ann_topk import _NT, NEG, TILE_N, merge_tiles, tile_topk
from repro.kernels.platform import resolve_interpret


def _annq_kernel(qq_ref, qs_ref, emb_ref, scale_ref, mask_ref, vals_ref,
                 idx_ref, *, k: int):
    """One grid step: int8 scores for a (tile_n, D) slab; per-tile top-k."""
    s = jax.lax.dot_general(
        qq_ref[...], emb_ref[...], _NT, preferred_element_type=jnp.int32,
    )                                        # (B, tile_n) exact int32
    # rescale: float(i32) * row_scale, then * query_scale — the numpy
    # reference path multiplies in the same order, so both sides agree
    # bit-for-bit on the coarse scores
    s = s.astype(jnp.float32) * scale_ref[...]   # (1, tile_n) row scales
    s = s * qs_ref[...]                          # (B, 1) query scales
    s = jnp.where(mask_ref[...] > 0, s, NEG)
    vals_ref[0], idx_ref[0] = tile_topk(s, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "tile_n"))
def ann_topk_quant(emb_q, scales, active, qq, q_scales, k: int = 16, *,
                   interpret: Optional[bool] = None, tile_n: int = TILE_N):
    """emb_q (N, D) int8; scales (N,) f32; active (N,); qq (B, D) int8;
    q_scales (B,) f32 -> (vals (B,k), rows (B,k)) coarse candidates.

    ``vals`` are the approximate (fully-quantized) scores — callers must
    rescore in fp32 before applying a similarity gate. Rows that fall off
    the active set carry ``NEG`` values; filter on ``vals > NEG / 2``.
    ``interpret=None`` compiles on TPU and interprets on CPU.
    """
    n, d = emb_q.shape
    b = qq.shape[0]
    pad = (-n) % tile_n
    active = active.astype(jnp.int32)
    if pad:
        emb_q = jnp.pad(emb_q, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, (0, pad))
        active = jnp.pad(active, (0, pad))
    ntiles = (n + pad) // tile_n

    vals, idx = pl.pallas_call(
        functools.partial(_annq_kernel, k=k),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec((b, d), lambda t: (0, 0)),        # qq resident
            pl.BlockSpec((b, 1), lambda t: (0, 0)),        # q_scales resident
            pl.BlockSpec((tile_n, d), lambda t: (t, 0)),   # int8 emb slab
            pl.BlockSpec((1, tile_n), lambda t: (0, t)),   # row scales slab
            pl.BlockSpec((1, tile_n), lambda t: (0, t)),   # active slab
        ],
        out_specs=[
            pl.BlockSpec((1, b, k), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, b, k), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ntiles, b, k), jnp.float32),
            jax.ShapeDtypeStruct((ntiles, b, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(qq, q_scales[:, None], emb_q, scales[None, :], active[None, :])
    return merge_tiles(vals, idx, tile_n, k)
