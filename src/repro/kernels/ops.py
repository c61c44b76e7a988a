"""jit'd public wrappers for the Pallas kernels.

Every kernel compiles on TPU and runs interpreted on CPU; the platform
decides (``kernels/platform.py``), so no wrapper takes a mode. The
pure-jnp oracles live in kernels.ref; tests sweep shapes/dtypes and
assert_allclose kernel-vs-ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ann_topk import ann_topk
from repro.kernels.ann_topk_ivf import NEG, ann_topk_ivf, ann_topk_ivf_quant
from repro.kernels.ann_topk_quant import ann_topk_quant
from repro.kernels.ann_topk_sharded import (ann_topk_ivf_quant_sharded,
                                            ann_topk_ivf_sharded)
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention_fwd

__all__ = ["ann_topk", "ann_topk_quant", "ann_topk_ivf",
           "ann_topk_ivf_quant", "ann_topk_ivf_sharded",
           "ann_topk_ivf_quant_sharded", "flash_attention_fwd",
           "decode_attention", "ann_topk_jit", "ann_topk_quant_jit",
           "ann_topk_ivf_jit", "ann_topk_ivf_quant_jit",
           "ann_topk_ivf_sharded_jit", "ann_topk_ivf_quant_sharded_jit"]


_B_ALIGN = 8  # fp32 sublane count: pad the query block to aligned shapes


def ann_topk_jit(emb, active, q, k: int = 4):
    """VectorIndex backend adapter: (D,) or (B, D) queries -> (sims, rows).

    The batched cache runtime sends variable-size query blocks (engine
    micro-batches, DESIGN.md §8); padding B up to a multiple of the fp32
    sublane count keeps the kernel's (B, D) block shape TPU-aligned and
    bounds jit retraces to one per padded size. Each query column is
    reduced independently inside the kernel, so the zero-padded rows are
    sliced off without affecting real results."""
    single = q.ndim == 1
    if single:
        q = q[None]
    b = q.shape[0]
    pad = (-b) % _B_ALIGN
    if pad:
        q = jnp.pad(jnp.asarray(q), ((0, pad), (0, 0)))
    vals, rows = ann_topk(
        jnp.asarray(emb), jnp.asarray(active), jnp.asarray(q), k
    )
    vals, rows = vals[:b], rows[:b]
    if single:
        return vals[0], rows[0]
    return vals, rows


def _route(centroids, live, q, nprobe: int):
    """Centroid scoring + top-``nprobe`` cluster selection — the routing
    half of the fused IVF scan, in the same jit scope as the
    ``pallas_call`` (it cannot live inside it: the scan grid's
    scalar-prefetch index maps need ``sel`` before the first step).
    Full fp32 precision: at the TPU's default single bf16 pass the
    selected clusters differ from the numpy router's, which changes
    hits, not just scores."""
    scores = jnp.matmul(jnp.asarray(q), jnp.asarray(centroids).T,
                        precision=jax.lax.Precision.HIGHEST)
    cs = jnp.where(jnp.asarray(live) > 0, scores, NEG)
    svals, sel = jax.lax.top_k(cs, nprobe)
    return sel.astype(jnp.int32), (svals > NEG / 2).astype(jnp.int32)


def _merge_probes(vals, slots, sel, bucket_rows, k: int):
    """(B, nprobe, k) per-probe finalists -> (B, kk) global top-k.
    Disabled probes carry NEG vals and row -1; callers filter on
    ``vals > NEG / 2``."""
    rows = jnp.where(vals > NEG / 2,
                     jnp.asarray(bucket_rows)[sel[:, :, None], slots], -1)
    b, nprobe, kk_in = vals.shape
    flat_v = vals.reshape(b, nprobe * kk_in)
    flat_r = rows.reshape(b, nprobe * kk_in)
    kk = min(k, nprobe * kk_in)
    top_v, pos = jax.lax.top_k(flat_v, kk)
    top_r = jnp.take_along_axis(flat_r, pos, axis=1)
    return top_v, top_r


def ann_topk_ivf_jit(centroids, live, buckets, bucket_rows, bucket_valid,
                     q, nprobe: int, k: int = 4):
    """Clustered VectorIndex backend adapter: route the (B, D) query
    block against the centroids, scan only the selected buckets
    (scalar-prefetch Pallas kernel), merge per-probe finalists. Returns
    ``(vals (B, kk), rows (B, kk), sel, enabled)`` — rows are global
    index rows (-1 where masked); sel/enabled feed the host's
    rows-scanned accounting."""
    b = q.shape[0]
    pad = (-b) % _B_ALIGN
    q = jnp.asarray(q)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
    sel, enabled = _route(centroids, live, q, nprobe)
    vals, slots = ann_topk_ivf(sel, enabled, q, jnp.asarray(buckets),
                               jnp.asarray(bucket_valid), k)
    top_v, top_r = _merge_probes(vals, slots, sel, bucket_rows, k)
    return top_v[:b], top_r[:b], sel[:b], enabled[:b]


def ann_topk_ivf_quant_jit(centroids, live, buckets_q, bucket_scale,
                           bucket_rows, bucket_valid, q, qq, q_scales,
                           nprobe: int, k: int = 16):
    """Clustered QuantIndex backend adapter (coarse phase only): routing
    runs on the fp32 query against the fp32 centroids; the bucket scan
    is fully quantized (int8 × int8, int32 accumulate), mirroring the
    brute ``ann_topk_quant`` coarse/rescore split."""
    b = qq.shape[0]
    pad = (-b) % _B_ALIGN
    q, qq, q_scales = jnp.asarray(q), jnp.asarray(qq), jnp.asarray(q_scales)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        qq = jnp.pad(qq, ((0, pad), (0, 0)))
        q_scales = jnp.pad(q_scales, (0, pad))
    sel, enabled = _route(centroids, live, q, nprobe)
    vals, slots = ann_topk_ivf_quant(
        sel, enabled, qq, q_scales, jnp.asarray(buckets_q),
        jnp.asarray(bucket_scale), jnp.asarray(bucket_valid), k,
    )
    top_v, top_r = _merge_probes(vals, slots, sel, bucket_rows, k)
    return top_v[:b], top_r[:b], sel[:b], enabled[:b]


def _merge_shards(vals, rows, k: int):
    """(S, B, nprobe, k) shard stacks -> (B, kk) finalists via ONE
    cross-shard ``lax.top_k`` — the §13 merge step. Rows already carry
    GLOBAL index ids (-1 where masked), so no translation here. Exact-
    score ties across shards resolve in shard-major flat order — the
    same class of kernel-backend tie caveat as ``_merge_probes``'s
    between-bucket order (the numpy sharded path does not share it)."""
    b = vals.shape[1]
    v = jnp.moveaxis(jnp.asarray(vals), 0, 1).reshape(b, -1)
    r = jnp.moveaxis(jnp.asarray(rows), 0, 1).reshape(b, -1)
    kk = min(k, v.shape[1])
    top_v, pos = jax.lax.top_k(v, kk)
    top_r = jnp.take_along_axis(r, pos, axis=1)
    return top_v, top_r


def ann_topk_ivf_sharded_jit(centroids, live, shard_buckets, shard_rows,
                             shard_valid, bounds, q, nprobe: int,
                             k: int = 4):
    """Sharded clustered VectorIndex backend adapter (DESIGN.md §13):
    routing stays GLOBAL (same ``_route`` as the unsharded wrapper, so
    the probed cluster set is shard-count invariant); the scan fans out
    per shard (``kernels/ann_topk_sharded``) and the S·nprobe·k
    finalists merge with one cross-shard ``lax.top_k``. Returns
    ``(vals, rows, sel, enabled)`` like ``ann_topk_ivf_jit``."""
    b = q.shape[0]
    pad = (-b) % _B_ALIGN
    q = jnp.asarray(q)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
    sel, enabled = _route(centroids, live, q, nprobe)
    vals, rows = ann_topk_ivf_sharded(sel, enabled, q, shard_buckets,
                                      shard_valid, shard_rows, bounds, k)
    top_v, top_r = _merge_shards(vals, rows, k)
    return top_v[:b], top_r[:b], sel[:b], enabled[:b]


def ann_topk_ivf_quant_sharded_jit(centroids, live, shard_bq, shard_scale,
                                   shard_rows, shard_valid, bounds, q, qq,
                                   q_scales, nprobe: int, k: int = 16):
    """Sharded clustered QuantIndex backend adapter (coarse phase only):
    fp32 global routing, int8 per-shard scans, one cross-shard merge —
    mirrors ``ann_topk_ivf_quant_jit`` exactly as
    ``ann_topk_ivf_sharded_jit`` mirrors ``ann_topk_ivf_jit``."""
    b = qq.shape[0]
    pad = (-b) % _B_ALIGN
    q, qq, q_scales = jnp.asarray(q), jnp.asarray(qq), jnp.asarray(q_scales)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        qq = jnp.pad(qq, ((0, pad), (0, 0)))
        q_scales = jnp.pad(q_scales, (0, pad))
    sel, enabled = _route(centroids, live, q, nprobe)
    vals, rows = ann_topk_ivf_quant_sharded(
        sel, enabled, qq, q_scales, shard_bq, shard_scale, shard_valid,
        shard_rows, bounds, k,
    )
    top_v, top_r = _merge_shards(vals, rows, k)
    return top_v[:b], top_r[:b], sel[:b], enabled[:b]


def ann_topk_quant_jit(emb_q, scales, active, qq, q_scales, k: int = 16):
    """Warm-tier QuantIndex backend adapter (coarse phase only).

    Queries arrive already int8-quantized — the host quantizes them with
    the same routine the numpy path uses, so both backends score identical
    integers. B is padded to the sublane multiple like ``ann_topk_jit``;
    padded query lanes carry scale 0 (all-zero scores) and are sliced off.
    """
    b = qq.shape[0]
    pad = (-b) % _B_ALIGN
    if pad:
        qq = jnp.pad(jnp.asarray(qq), ((0, pad), (0, 0)))
        q_scales = jnp.pad(jnp.asarray(q_scales), (0, pad))
    vals, rows = ann_topk_quant(
        jnp.asarray(emb_q), jnp.asarray(scales), jnp.asarray(active),
        jnp.asarray(qq), jnp.asarray(q_scales), k,
    )
    return vals[:b], rows[:b]
