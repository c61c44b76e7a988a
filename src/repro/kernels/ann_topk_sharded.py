"""Shard-parallel routed scans over the IVF Pallas kernels (DESIGN.md §13).

Sharding lives ABOVE the kernel: ``ann_topk_ivf`` / ``ann_topk_ivf_quant``
run unmodified, once per mesh shard. ``sel`` carries GLOBAL cluster ids
from the shared router; each shard masks the probes down to the
contiguous cluster range it owns (``lo ≤ sel < hi``), translates them to
its local bucket space, scans its ``(Cmax, cap[, D])`` slice, and
translates winning bucket slots back to GLOBAL index rows. Probes a
shard does not own run disabled (the kernel's existing ``enabled=0``
path), so every shard launches the same grid — no data-dependent shapes.

Two execution modes produce identical ``(S, B, nprobe, k)`` stacks:

  * ``jax.shard_map`` over a 1-D ``("shards",)`` device mesh
    (``launch/mesh.make_shard_mesh``) — one program per device, the
    bucket slices placed device-local;
  * an unrolled loop on one device, for CPU interpret runs on hosts
    with fewer devices than shards. On TPU a mesh with fewer devices
    than shards is an error: the loop would put every shard's slice on
    one chip and hide that the mesh path never ran.

``kernels/ops.py`` merges the stacks with one cross-shard
``jax.lax.top_k`` (the ``_merge_shards`` step).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.ann_topk_ivf import NEG, ann_topk_ivf, ann_topk_ivf_quant
from repro.kernels.platform import on_tpu

__all__ = ["ann_topk_ivf_sharded", "ann_topk_ivf_quant_sharded",
           "mesh_available", "mesh_scan", "shard_placement", "NEG"]


def mesh_available(n_shards: int) -> bool:
    """True when the host can lay one cache shard per device (CPU tests
    fake devices with ``XLA_FLAGS=--xla_force_host_platform_device_count
    =8``). Raises on TPU when it cannot."""
    if jax.device_count() >= n_shards:
        return True
    if on_tpu():
        raise RuntimeError(
            f"{n_shards} stage-1 shards need {n_shards} devices; this "
            f"host has {jax.device_count()}")
    return False


def shard_placement(n_shards: int):
    """Where a caller puts the per-shard stacks: one shard per device
    (the mesh layout the ``shard_map`` program reads, so its own
    ``device_put`` moves nothing) when the mesh path runs, else None,
    the default device of the unrolled loop."""
    if n_shards > 1 and mesh_available(n_shards):
        from repro.launch.mesh import make_shard_mesh

        return NamedSharding(make_shard_mesh(n_shards), P("shards"))
    return None


def _own_probes(sel, en, lo, hi, cmax):
    """Mask ``sel`` down to one shard's owned cluster range and
    translate to its local bucket ids. Non-owned probes come back
    disabled with a clipped (in-range, never scanned) local id."""
    own = (sel >= lo) & (sel < hi)
    loc = jnp.clip(sel - lo, 0, cmax - 1).astype(jnp.int32)
    return loc, (en * own).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def mesh_scan(mesh, k: int, quant: bool, interpret: Optional[bool] = None):
    """The jitted ``shard_map`` program for one mesh and config (built
    once): sharded operands carry a leading length-1 shard axis inside
    the body. ``interpret=None`` lets the platform choose."""
    if quant:
        def body(bkt, bsc, vld, rws, lo, hi, qq, qs, sel, en):
            loc, en_s = _own_probes(sel, en, lo[0, 0], hi[0, 0],
                                    bkt.shape[1])
            vals, slots = ann_topk_ivf_quant(
                loc, en_s, qq, qs, bkt[0], bsc[0], vld[0], k,
                interpret=interpret,
            )
            rows = jnp.where(vals > NEG / 2,
                             rws[0][loc[:, :, None], slots], -1)
            return vals[None], rows[None]

        in_specs = (P("shards"),) * 6 + (P(), P(), P(), P())
    else:
        def body(bkt, vld, rws, lo, hi, q, sel, en):
            loc, en_s = _own_probes(sel, en, lo[0, 0], hi[0, 0],
                                    bkt.shape[1])
            vals, slots = ann_topk_ivf(loc, en_s, q, bkt[0], vld[0], k,
                                       interpret=interpret)
            rows = jnp.where(vals > NEG / 2,
                             rws[0][loc[:, :, None], slots], -1)
            return vals[None], rows[None]

        in_specs = (P("shards"),) * 5 + (P(), P(), P())
    # check_vma off: pallas_call outputs carry no varying-axis
    # annotation, and every operand here is fully manual anyway
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=(P("shards"), P("shards")),
                                 check_vma=False))


def _run_mesh(n_shards: int, k: int, quant: bool, sharded, bounds,
              replicated):
    """Place the per-shard stacks (and each shard's cluster range) on
    their own devices straight from the host — never via one device —
    then run the mesh program. Stacks the caller already placed with
    :func:`shard_placement` stay where they are."""
    placement = shard_placement(n_shards)
    lo = np.asarray(bounds[:-1], np.int32).reshape(n_shards, 1)
    hi = np.asarray(bounds[1:], np.int32).reshape(n_shards, 1)
    args = jax.device_put((*sharded, lo, hi), placement)
    return mesh_scan(placement.mesh, k, quant)(
        *args, *map(jnp.asarray, replicated))


def ann_topk_ivf_sharded(sel, enabled, q, shard_buckets, shard_valid,
                         shard_rows, bounds, k: int = 4):
    """fp32 shard-parallel routed scan. Returns ``(vals, rows)`` each
    ``(S, B, nprobe, k)``; rows are GLOBAL index rows, -1 where masked.
    ``bounds`` is the router's (S+1,) cluster-ownership prefix."""
    s = shard_buckets.shape[0]
    if s > 1 and mesh_available(s):
        return _run_mesh(s, k, False,
                         (shard_buckets, shard_valid, shard_rows), bounds,
                         (q, sel, enabled))
    sel, en, q = jnp.asarray(sel), jnp.asarray(enabled), jnp.asarray(q)
    cmax = shard_buckets.shape[1]
    vs, rs = [], []
    for si in range(s):
        loc, en_s = _own_probes(sel, en, int(bounds[si]),
                                int(bounds[si + 1]), cmax)
        vals, slots = ann_topk_ivf(
            loc, en_s, q, jnp.asarray(shard_buckets[si]),
            jnp.asarray(shard_valid[si]), k,
        )
        rs.append(jnp.where(
            vals > NEG / 2,
            jnp.asarray(shard_rows[si])[loc[:, :, None], slots], -1))
        vs.append(vals)
    return jnp.stack(vs), jnp.stack(rs)


def ann_topk_ivf_quant_sharded(sel, enabled, qq, q_scales, shard_bq,
                               shard_scale, shard_valid, shard_rows,
                               bounds, k: int = 16):
    """int8 shard-parallel routed coarse scan — the quantized sibling of
    :func:`ann_topk_ivf_sharded` (same ownership masking, same global
    row translation)."""
    s = shard_bq.shape[0]
    if s > 1 and mesh_available(s):
        return _run_mesh(s, k, True,
                         (shard_bq, shard_scale, shard_valid, shard_rows),
                         bounds, (qq, q_scales, sel, enabled))
    sel, en = jnp.asarray(sel), jnp.asarray(enabled)
    qq, q_scales = jnp.asarray(qq), jnp.asarray(q_scales)
    cmax = shard_bq.shape[1]
    vs, rs = [], []
    for si in range(s):
        loc, en_s = _own_probes(sel, en, int(bounds[si]),
                                int(bounds[si + 1]), cmax)
        vals, slots = ann_topk_ivf_quant(
            loc, en_s, qq, q_scales, jnp.asarray(shard_bq[si]),
            jnp.asarray(shard_scale[si]), jnp.asarray(shard_valid[si]), k,
        )
        rs.append(jnp.where(
            vals > NEG / 2,
            jnp.asarray(shard_rows[si])[loc[:, :, None], slots], -1))
        vs.append(vals)
    return jnp.stack(vs), jnp.stack(rs)
