"""Pallas TPU kernel for Seri stage-1: fused cosine-similarity + top-k.

TPU adaptation of the paper's Faiss ANN stage (DESIGN.md §3): graph/IVF
traversal is pointer-chasing and MXU-hostile; on TPU, brute-force tiled
matmul over the embedding matrix hits ~peak MXU throughput for cache sizes
up to millions of entries and gives exact (recall=1.0) top-k.

Tiling: the embedding matrix (N, D) streams HBM→VMEM in (TILE_N, D) tiles;
the query block (B, D) stays resident in VMEM; each grid step computes a
(B, TILE_N) score tile on the MXU (fp32 accumulation, full-fp32 contract
precision for fp32 operands), masks inactive rows with a (1, TILE_N) row
mask, and reduces it to per-tile top-K candidates along the lanes (K
passes of max / lowest-index-of-max on the VPU — K is small). The
(ntiles · K) finalists are merged by a single lax.top_k outside the
kernel (tiny).

Queries sit on sublanes and rows on lanes, so the mask and the row scales
of the int8 variant broadcast along sublanes and the per-query reductions
run along lanes — the layout Mosaic lowers without shape casts.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

TILE_N = 512
NEG = -3.0e38  # plain float: jnp scalars would be captured consts in pallas

_NT = (((1,), (1,)), ((), ()))  # contract the last dim of both operands


def contract_precision(dtype):
    """fp32 operands contract at full fp32 precision: the MXU's default
    for fp32 is a single bf16 pass, which moves scores by ~1e-3 and so
    changes which rows clear τ_sim against the numpy reference."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def tile_topk(s, k: int):
    """Per-row top-k of a (R, L) score tile along the lanes: k passes of
    max, the LOWEST lane index among the maxima (the tie rule every
    stage-1 path shares), then knock that lane out. Returns
    ``(vals (R, k) f32, idx (R, k) i32)``."""
    r, n = s.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)
    vals = jnp.full((r, k), NEG, jnp.float32)
    idx = jnp.zeros((r, k), jnp.int32)
    for j in range(k):
        v = jnp.max(s, axis=1, keepdims=True)                    # (R, 1)
        i = jnp.min(jnp.where(s == v, cols, n), axis=1, keepdims=True)
        vals = jnp.where(slot == j, v, vals)
        idx = jnp.where(slot == j, i, idx)
        s = jnp.where(cols == i, NEG, s)
    return vals, idx


def merge_tiles(vals, idx, tile_n: int, k: int):
    """(ntiles, B, k) per-tile finalists (tile-local rows) -> global
    (B, kk) top-k. Flat order is tile-major, so lax.top_k's lowest-index
    tie rule keeps the lowest row first."""
    ntiles, b, _ = vals.shape
    base = (jnp.arange(ntiles, dtype=jnp.int32) * tile_n)[:, None, None]
    flat_v = jnp.moveaxis(vals, 0, 1).reshape(b, ntiles * k)
    flat_i = jnp.moveaxis(idx + base, 0, 1).reshape(b, ntiles * k)
    top_v, pos = jax.lax.top_k(flat_v, min(k, ntiles * k))
    return top_v, jnp.take_along_axis(flat_i, pos, axis=1)


def _ann_kernel(q_ref, emb_ref, mask_ref, vals_ref, idx_ref, *, k: int):
    """One grid step: scores for a (tile_n, D) slab; per-tile top-k."""
    emb = emb_ref[...]
    s = jax.lax.dot_general(
        q_ref[...], emb, _NT, preferred_element_type=jnp.float32,
        precision=contract_precision(emb.dtype),
    )                                        # (B, tile_n)
    s = jnp.where(mask_ref[...] > 0, s, NEG)
    vals_ref[0], idx_ref[0] = tile_topk(s, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "tile_n"))
def ann_topk(emb, active, q, k: int = 4, *,
             interpret: Optional[bool] = None, tile_n: int = TILE_N):
    """emb (N, D); active (N,); q (B, D) -> (vals (B,k), rows (B,k)).

    ``interpret=None`` compiles on TPU and interprets on CPU
    (``kernels/platform.py``)."""
    n, d = emb.shape
    b = q.shape[0]
    pad = (-n) % tile_n
    active = active.astype(jnp.int32)
    if pad:
        emb = jnp.pad(emb, ((0, pad), (0, 0)))
        active = jnp.pad(active, (0, pad))
    ntiles = (n + pad) // tile_n

    vals, idx = pl.pallas_call(
        functools.partial(_ann_kernel, k=k),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec((b, d), lambda t: (0, 0)),            # q resident
            pl.BlockSpec((tile_n, d), lambda t: (t, 0)),       # emb slab
            pl.BlockSpec((1, tile_n), lambda t: (0, t)),       # active slab
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
    )(q, emb, active[None, :])
    return merge_tiles(vals, idx, tile_n, k)
