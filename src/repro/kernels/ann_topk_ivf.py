"""Pallas TPU kernels for the clustered (IVF) stage-1 routed scan.

Brute-force ``ann_topk`` streams the WHOLE embedding matrix HBM→VMEM on
every lookup; at million-entry cache sizes stage 1 becomes bandwidth-
bound on its own index (DESIGN.md §12). The IVF layout fixes the
bytes-moved term: embeddings live in **cluster-major buckets** (C,
bucket_cap, D) maintained by ``core/clustering.py``, and the kernel
scans only the ``nprobe`` buckets each query routed to.

Routing is data-dependent, so the scan uses
``pltpu.PrefetchScalarGridSpec``: the per-(query, probe) selected
cluster ids ``sel`` are scalar-prefetched, and the bucket BlockSpec's
index map reads ``sel[b, j]`` to DMA exactly the selected bucket for
grid step (b, j) — the TPU equivalent of Faiss's inverted-list gather.
The centroid scoring + top-``nprobe`` selection happens in the same jit
scope (``kernels/ops.py`` wrappers) with a plain MXU matmul: it cannot
live inside the scan's ``pallas_call`` because the grid's index maps
need ``sel`` before the first step launches.

Two variants share the structure (mirroring ``ann_topk`` vs
``ann_topk_quant``):

  * ``ann_topk_ivf``       — fp32 buckets, exact scores (HOT tier);
  * ``ann_topk_ivf_quant`` — int8 buckets + per-row scales, int32
    accumulate, approximate coarse scores for the WARM tier's
    coarse/rescore pipeline (the host rescores finalists in fp32).

Per grid step: one query row · one (bucket_cap, D) slab on the MXU,
giving a (1, bucket_cap) score row; invalid slots and disabled probes
are masked to NEG, and ``ann_topk.tile_topk`` reduces the row to the
step's top-k. The (nprobe · k) finalists per query merge in one
``lax.top_k`` outside the kernel. Disabled probes (query routed to fewer
than ``nprobe`` non-empty clusters) emit NEG rows that callers drop via
``vals > NEG / 2``.

Per-query and per-bucket operands carry a unit middle axis ((B, 1, D)
queries, (C, 1, cap) valid/scale rows, (B, nprobe, 1, k) outputs) so
every block's last two dims equal the array's — the TPU block-shape
rule — while the grid still picks one query and one bucket per step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ann_topk import _NT, NEG, contract_precision, tile_topk
from repro.kernels.platform import resolve_interpret

__all__ = ["NEG", "ann_topk_ivf", "ann_topk_ivf_quant"]


def _ivf_kernel(sel_ref, en_ref, q_ref, bucket_ref, valid_ref, vals_ref,
                idx_ref, *, k: int):
    """Grid step (b, j): scan bucket ``sel[b, j]`` for query b."""
    en = en_ref[pl.program_id(0), pl.program_id(1)]
    bucket = bucket_ref[0]                   # (cap, D)
    s = jax.lax.dot_general(
        q_ref[0], bucket, _NT, preferred_element_type=jnp.float32,
        precision=contract_precision(bucket.dtype),
    )                                        # (1, cap)
    s = jnp.where(valid_ref[0] * en > 0, s, NEG)
    vals_ref[0, 0], idx_ref[0, 0] = tile_topk(s, k)


def _ivf_quant_kernel(sel_ref, en_ref, qq_ref, qs_ref, bucket_ref,
                      scale_ref, valid_ref, vals_ref, idx_ref, *, k: int):
    """int8 variant: int32-exact scores rescaled like ann_topk_quant
    (row scale first, then query scale — bit-matching the numpy path)."""
    en = en_ref[pl.program_id(0), pl.program_id(1)]
    s = jax.lax.dot_general(
        qq_ref[0], bucket_ref[0], _NT, preferred_element_type=jnp.int32,
    )                                        # (1, cap) exact int32
    s = s.astype(jnp.float32) * scale_ref[0]
    s = s * qs_ref[0]                        # (1, 1) query scale
    s = jnp.where(valid_ref[0] * en > 0, s, NEG)
    vals_ref[0, 0], idx_ref[0, 0] = tile_topk(s, k)


def _query_spec(width: int):
    """Query b's (1, width) row of a (B, 1, width) operand."""
    return pl.BlockSpec((1, 1, width), lambda bi, j, sel, en: (bi, 0, 0))


def _routed_call(kernel, sel, enabled, operands, in_specs, k: int,
                 interpret):
    """The (B, nprobe) scalar-prefetch grid both routed scans share."""
    b, nprobe = sel.shape
    vals, slots = pl.pallas_call(
        functools.partial(kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,           # sel, enabled
            grid=(b, nprobe),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, 1, k),
                                    lambda bi, j, sel, en: (bi, j, 0, 0))] * 2,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, nprobe, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((b, nprobe, 1, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(sel, enabled, *operands)
    return vals[:, :, 0], slots[:, :, 0]


def _bucket_specs(cap: int, d: int, n_rows: int):
    """The selected bucket's (cap, D) slab, then ``n_rows`` (1, cap)
    per-slot rows (valid mask, scales) of the same bucket."""
    def bucket(bi, j, sel, en):
        return (sel[bi, j], 0, 0)

    return [pl.BlockSpec((1, cap, d), bucket)] + \
        [pl.BlockSpec((1, 1, cap), bucket)] * n_rows


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ann_topk_ivf(sel, enabled, q, buckets, bucket_valid, k: int = 4, *,
                 interpret: Optional[bool] = None):
    """Routed fp32 scan. sel/enabled (B, nprobe) int32; q (B, D);
    buckets (C, cap, D); bucket_valid (C, cap) -> per-probe finalists
    (vals (B, nprobe, k), slots (B, nprobe, k)). ``interpret=None``
    compiles on TPU and interprets on CPU."""
    c, cap, d = buckets.shape
    return _routed_call(
        _ivf_kernel, sel, enabled,
        (q[:, None, :], buckets, bucket_valid.reshape(c, 1, cap)),
        [_query_spec(d)] + _bucket_specs(cap, d, 1), k, interpret,
    )


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ann_topk_ivf_quant(sel, enabled, qq, q_scales, buckets_q, bucket_scale,
                       bucket_valid, k: int = 16, *,
                       interpret: Optional[bool] = None):
    """Routed int8 coarse scan. qq (B, D) int8; q_scales (B,) f32;
    buckets_q (C, cap, D) int8; bucket_scale (C, cap) f32 -> per-probe
    coarse finalists (vals, slots) as in :func:`ann_topk_ivf`. ``vals``
    are approximate — callers rescore in fp32 before the τ_sim gate.
    """
    c, cap, d = buckets_q.shape
    return _routed_call(
        _ivf_quant_kernel, sel, enabled,
        (qq[:, None, :], q_scales[:, None, None], buckets_q,
         bucket_scale.reshape(c, 1, cap), bucket_valid.reshape(c, 1, cap)),
        [_query_spec(d), _query_spec(1)] + _bucket_specs(cap, d, 2), k,
        interpret,
    )
