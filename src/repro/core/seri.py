"""Seri — the Semantic Retrieval Index (paper §4.2).

Stage 1 (coarse): exact cosine top-k over the SE embedding matrix with the
τ_sim gate. The platform picks the backend (``kernels/platform.py``): on
TPU the Pallas ``ann_topk`` family (brute-force MXU matmul — the
TPU-idiomatic replacement for Faiss graph traversal, see DESIGN.md §3),
on CPU the numpy path, which is also the reference the kernels are
tested against.

Stage 2 (fine): the semantic judge validates each candidate's *result*
against the new query; the first candidate with S_lsm ≥ τ_lsm is a
semantic-aware cache hit.

Both stages are batched (DESIGN.md §8): ``search_batch`` pushes a whole
(B, D) query block through one masked matmul (or one ``ann_topk`` launch,
which always had the B dimension), and ``CortexCache._judge_blocks``
scores the candidates of *all* queries in a single ``judge.score_pairs``
call. The scalar entry points are one-query wrappers over the batched
path, so scalar and batched execution are the same code and produce
identical results.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semantic_element import SemanticElement
from repro.kernels.platform import resolve_backend
from repro.obs.trace import NULL_TRACER

# rows per in-place update of a device mirror: the one scatter shape that
# compiles, whatever the number of dirty rows (padding rows are dropped)
SYNC_CHUNK = 64


@functools.partial(jax.jit, donate_argnums=0)
def _sync_rows(mirror, rows, vals):
    """The row update of a device mirror: ``mirror`` (a tuple of row
    arrays) takes ``vals`` at ``rows``; rows out of range are dropped.
    The mirror's buffers are donated, so XLA writes the rows in place and
    the device never holds two copies of the index."""
    return tuple(a.at[rows].set(v, mode="drop")
                 for a, v in zip(mirror, vals))


class RowIndex:
    """Fixed-capacity free-list row allocator — the management half
    shared by the fp32 hot index below and the int8 warm index
    (``core/tiers.py::QuantIndex``): active mask, row→se_id mapping, row
    alloc/free. Subclasses own the storage arrays and zero them in
    ``_clear_rows``, so the two tiers' row lifecycles cannot drift.

    ``row_se`` is an int64 array (-1 = free) so batched search paths
    resolve row→se_id with one fancy-indexed gather instead of a
    per-candidate Python loop. On the kernel backend the arrays the
    brute scan reads (``_mirrored``) also live on the device, synced by
    the rows changed since the last pass (``_sync_mirror``, DESIGN.md
    §8); the host arrays stay authoritative. An optional
    :class:`~repro.core.clustering.ClusterRouter` observes the row
    lifecycle (``note_add``/``note_remove``) to keep its cluster
    buckets free-list-consistent (DESIGN.md §12)."""

    def __init__(self, capacity: int, dim: int, router=None):
        self.capacity = capacity
        self.dim = dim
        self.active = np.zeros(capacity, bool)
        self.row_se = np.full(capacity, -1, np.int64)
        self.router = router
        # rows touched by the most recent search_batch call (active rows
        # for brute force; centroids + gathered members for the routed
        # scan) — the engine's scan-proportional latency term
        self.last_scanned = 0
        # the busiest shard's share of last_scanned (DESIGN.md §13):
        # shards scan in parallel, so the engine's critical path is the
        # max-over-shards term, not the total. Equal to last_scanned
        # for brute force and unsharded routing.
        self.last_scanned_max_shard = 0
        # stage-1 passes by path: with ``backend`` they say which scan
        # actually ran (the chip smoke asserts the Pallas routed scan did)
        self.passes = {"routed": 0, "brute": 0}
        # bytes the most recent search_batch put from host to device
        # (kernel backend; ScanMetrics.h2d_bytes sums them per pass), and
        # how its mirror sync went: whole-array puts, rows scattered
        self.last_h2d_bytes = 0
        self.last_full_uploads = 0
        self.last_synced_rows = 0
        # the times it blocked on a copy back from the device, and the
        # bytes those copies brought (ScanMetrics.d2h_waits / d2h_bytes)
        self.last_d2h_waits = 0
        self.last_d2h_bytes = 0
        # the device mirror of _mirrored() (kernel backend, built by the
        # first brute pass) and the rows changed on the host since its
        # last sync
        self._mirror = None
        self._dirty: set = set()
        # wall spans of the search (seri.search / .layout / .h2d /
        # .scan / .select); bound by CortexCache.bind_tracer
        self.tracer = NULL_TRACER
        # the kernel backend sets these (Pallas wrappers in kernels/ops)
        self._kernel_fn = None
        self._ivf_kernel_fn = None
        self._ivf_sharded_fn = None
        self._free = list(range(capacity - 1, -1, -1))

    def __len__(self) -> int:
        return int(self.active.sum())

    @property
    def full(self) -> bool:
        return not self._free

    def _alloc(self, se_id: int) -> int:
        if not self._free:
            raise RuntimeError("index full — evict first")
        row = self._free.pop()
        self.active[row] = True
        self.row_se[row] = se_id
        self._touch(row)
        return row

    def _touch(self, rows) -> None:
        """Note rows whose host values changed (every write to a row
        passes here: ``_alloc``, ``add_batch``, ``remove_rows``) for the
        mirror's next sync. Without a mirror there is nothing to note:
        its first sync puts the whole arrays."""
        if self._mirror is not None:
            self._dirty.update(np.atleast_1d(rows).tolist())

    def _clear_rows(self, ra: np.ndarray) -> None:
        raise NotImplementedError

    def _mirrored(self) -> tuple:
        """The host row arrays the brute kernel scans, in its order."""
        raise NotImplementedError

    def _routed_dispatch(self, q: np.ndarray, kernel_scan, routed_scan,
                         brute_scan):
        """The one stage-1 dispatch both index flavors share (the same
        anti-drift rationale as ``topk_desc``). The backend picks the
        path and never falls back: a ``"kernel"`` index runs the Pallas
        routed scan once clusters exist and the Pallas brute scan
        otherwise; a ``"numpy"`` index (the reference) runs the numpy
        routed scan once the router is trained, brute force otherwise.
        Returns ``(rows, scores, routed)`` — ``routed`` tells the caller
        to apply the kernel NEG-slot row filter."""
        ready = self.router is not None and self.router.ready
        if self.backend == "kernel":
            if ready and np.any(self.router.counts > 0):
                self.passes["routed"] += 1
                # the routed scan reads the bucket layout, not the rows
                self._mirror, self._dirty = None, set()
                return (*kernel_scan(), True)
        elif ready:
            info = self.router.route(q)
            if info is not None:
                self.passes["routed"] += 1
                return (*routed_scan(info), True)
        self.passes["brute"] += 1
        self.last_scanned = len(self)
        self.last_scanned_max_shard = self.last_scanned
        return (*brute_scan(), False)

    def _store_rows(self, ra: np.ndarray, embs: np.ndarray) -> None:
        raise NotImplementedError

    def _new_pass(self) -> None:
        """Zero the per-pass transfer counters (``last_h2d_bytes``,
        ``last_full_uploads``, ``last_synced_rows``, ``last_d2h_waits``,
        ``last_d2h_bytes``)."""
        self.last_h2d_bytes = 0
        self.last_full_uploads = 0
        self.last_synced_rows = 0
        self.last_d2h_waits = 0
        self.last_d2h_bytes = 0

    def _sync_mirror(self) -> tuple:
        """The device mirror of ``_mirrored()``, brought up to date.

        Without a mirror, or with more dirty rows than scattering them
        is worth (their padded chunks would move at least the bytes of
        the whole arrays), one ``jax.device_put`` of the whole arrays;
        the scatter then runs once on an all-padding chunk made on the
        device, so its one shape compiles with the mirror and never
        inside a served window. Otherwise only the dirty rows move, in
        chunks of ``SYNC_CHUNK`` (padded with out-of-range rows), each
        scattered in place by ``_sync_rows``. Counts the bytes into
        ``last_h2d_bytes``."""
        host = self._mirrored()
        whole = sum(a.nbytes for a in host)
        rows = np.array(sorted(self._dirty), np.int32)
        self._dirty = set()
        n_pad = -(-len(rows) // SYNC_CHUNK) * SYNC_CHUNK
        row_bytes = whole // self.capacity + rows.itemsize
        if self._mirror is None or n_pad * row_bytes >= whole:
            self._mirror = None            # free the stale copy first
            self._mirror = _sync_rows(
                jax.device_put(host),
                jnp.full(SYNC_CHUNK, self.capacity, jnp.int32),
                tuple(jnp.zeros((SYNC_CHUNK,) + a.shape[1:], a.dtype)
                      for a in host))
            self.last_h2d_bytes += whole
            self.last_full_uploads += 1
            return self._mirror
        for off in range(0, len(rows), SYNC_CHUNK):
            part = rows[off:off + SYNC_CHUNK]
            chunk = np.full(SYNC_CHUNK, self.capacity, np.int32)
            chunk[:len(part)] = part
            src = np.minimum(chunk, self.capacity - 1)
            r, *vals = jax.device_put((chunk, *(a[src] for a in host)))
            self._mirror = _sync_rows(self._mirror, r, tuple(vals))
            self.last_h2d_bytes += r.nbytes + sum(v.nbytes for v in vals)
        self.last_synced_rows += len(rows)
        return self._mirror

    def _put(self, *arrays, sharded=(), placement=None,
             mirror: bool = False) -> tuple:
        """Send a kernel pass's host operands to the device in one
        ``jax.device_put`` (``sharded`` ones to ``placement``, the mesh
        layout of a sharded scan), so the kernel wrappers receive device
        arrays. With ``mirror`` the device mirror of the brute scan's row
        arrays comes first, synced by its dirty rows (``_sync_mirror``):
        a brute pass sends only those rows and its queries, padded on
        the host, not the index. Counts the bytes into
        ``last_h2d_bytes``. Under an
        enabled tracer the ``seri.h2d`` span waits for the copies to
        land, so that it holds the whole transfer; untraced, they stay
        asynchronous and the kernel waits for them as it always did."""
        tr = self.tracer
        with tr.wall("seri.h2d"):
            out = self._sync_mirror() if mirror else ()
            put = jax.device_put(arrays)
            if sharded:
                put += jax.device_put(sharded, placement)
            out += put
            if tr.enabled:
                jax.block_until_ready(out)
        self.last_h2d_bytes += sum(x.nbytes for x in put)
        return out

    def _fetch(self, *arrays) -> tuple:
        """Copy a kernel pass's device results to the host with one
        blocking wait: ``jax.device_get`` starts every copy before it
        waits for any. Counts the wait into ``last_d2h_waits`` and the
        bytes into ``last_d2h_bytes``."""
        out = jax.device_get(arrays)
        self.last_d2h_waits += 1
        self.last_d2h_bytes += sum(x.nbytes for x in out)
        return out

    def add_batch(self, se_ids, embeddings) -> np.ndarray:
        """Bulk add for large prefills (the million-entry sweeps): one
        vectorized alloc+store per block instead of n scalar calls.

        Stays on the scalar ``add`` path until the router trains —
        the first refresh must trigger at the same index size as a
        sequential loop would hit — then switches to bulk allocation +
        ``note_add_batch`` (which itself splits at the router's exact
        refresh points). Returns the allocated rows, ascending.
        """
        embs = np.asarray(embeddings, np.float32)
        ids = np.asarray(se_ids, np.int64)
        n = len(ids)
        if len(self._free) < n:
            raise RuntimeError("index full — evict first")
        rows = np.empty(n, np.int64)
        i = 0
        while i < n and self.router is not None \
                and not self.router.trained:
            rows[i] = self.add(int(ids[i]), embs[i])
            i += 1
        rt = self.router
        while i < n:
            # allocate only up to the router's next refresh boundary: a
            # refresh sees exactly the rows a sequential loop would have
            # active (bulk-allocating ahead would leak not-yet-noted
            # rows into the training sample and re-bucketing pass)
            take = n - i
            if rt is not None:
                take = min(take, max(1, rt.cfg.refresh_every - rt._muts))
            ra = np.array([self._free.pop() for _ in range(take)],
                          np.int64)
            self.active[ra] = True
            self.row_se[ra] = ids[i:i + take]
            self._store_rows(ra, embs[i:i + take])
            self._touch(ra)
            rows[i:i + take] = ra
            if rt is not None:
                rt.note_add_batch(ra, embs[i:i + take], self)
            i += take
        return rows

    def remove_rows(self, rows) -> None:
        """Batched removal: one fancy-indexed store per field."""
        rows = [r for r in rows if self.active[r]]
        if not rows:
            return
        ra = np.asarray(rows)
        self.active[ra] = False
        self._clear_rows(ra)
        self.row_se[ra] = -1
        self._touch(ra)
        if self.router is not None:
            self.router.note_remove(ra)
        for r in rows:
            self._free.append(r)


def topk_desc(s: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-row top-k, similarity-descending, over a (B, N) score matrix
    (mutates ``s``): negate in place, ``argpartition``, then a
    boundary-tie-exact stable sort — the one selection idiom both the
    fp32 and int8 (core/tiers.py) indexes use, so their tie-break
    semantics cannot drift. Returns (rows (B, k), vals (B, k)).

    Ties break by ascending COLUMN index — an exact rule, not
    argpartition luck: the candidate set is expanded to every value
    tying the k-th (the ``topk_desc_stable`` idiom) so the result is
    independent of the matrix layout. That is what makes the clustered
    index's nprobe=all mode bit-identical to brute force (DESIGN.md
    §12): the routed union scores the same values at different column
    positions, and a layout-dependent tie pick (exact-duplicate
    embeddings — judge false-negative re-inserts — tying at the
    boundary) would diverge. Ascending-column also matches the Pallas
    kernels' tie order (per-tile argmax + lax.top_k both prefer the
    lowest index)."""
    b, m = s.shape
    k_eff = min(k, m)
    np.negative(s, out=s)                             # sort ascending
    part = np.argpartition(s, k_eff - 1, axis=1)[:, :k_eff]
    psc = np.take_along_axis(s, part, axis=1)
    rows = np.empty((b, k_eff), part.dtype)
    vals = np.empty((b, k_eff), s.dtype)
    for i in range(b):
        thr = psc[i].max()
        sel = np.flatnonzero(s[i] <= thr)   # superset incl. boundary ties
        order = sel[np.argsort(s[i, sel], kind="stable")][:k_eff]
        rows[i] = order
        vals[i] = -s[i, order]
    return rows, vals


def topk_desc_stable(v: np.ndarray, k: int) -> np.ndarray:
    """Indices of the ``k`` largest values of 1-D ``v``, descending,
    ties broken by ascending position — EXACTLY
    ``np.argsort(-v, kind="stable")[:k]``, but O(n + t·log t) via
    ``argpartition`` with the boundary-tie expansion trick the SoA
    victim selector uses (``se_store._smallest_in_order``): the
    partition's candidate set is widened to every value tying the k-th,
    so a tie group split by the partition boundary cannot change which
    elements survive. The per-candidate rescore selections
    (``core/tiers.py``) use this instead of a full sort."""
    m = v.shape[0]
    k = min(k, m)
    if k <= 0:
        return np.zeros(0, np.intp)
    if k >= m:
        return np.argsort(-v, kind="stable")
    neg = -v
    part = np.argpartition(neg, k - 1)[:k]
    thr = neg[part].max()
    sel = np.flatnonzero(neg <= thr)       # superset incl. boundary ties
    return sel[np.argsort(neg[sel], kind="stable")][:k]


def sharded_topk_merge(s: np.ndarray, owners: np.ndarray, n_shards: int,
                       k: int) -> tuple[np.ndarray, np.ndarray]:
    """Shard-parallel top-k over a (B, G) score matrix whose columns
    are partitioned by ``owners`` (column → shard), merged to one
    global (rows, vals) — bit-identical to ``topk_desc(s, k)``.

    Each shard runs :func:`topk_desc` over its own column slice, then
    the ≤ S·k finalists merge under the same total order topk_desc
    uses: value descending, GLOBAL column ascending (``lexsort`` keys).
    Every global winner is by definition inside its own shard's top-k
    under that order, so the shard union always contains the global
    top-k and the merge reproduces it exactly — including duplicate
    scores straddling a shard boundary. This is the host-path model of
    the shard_map + cross-shard ``lax.top_k`` kernel (DESIGN.md §13);
    unlike :func:`topk_desc` it does NOT mutate ``s`` (the per-shard
    column gathers are copies).
    """
    b, m = s.shape
    k_eff = min(k, m)
    ccols, cvals = [], []
    for sh in range(n_shards):
        cols = np.flatnonzero(owners == sh)
        if not len(cols):
            continue
        lr, lv = topk_desc(s[:, cols], k)    # fancy-index copy of s
        ccols.append(cols[lr])
        cvals.append(lv)
    cc = np.concatenate(ccols, axis=1)
    cv = np.concatenate(cvals, axis=1)
    rows = np.empty((b, k_eff), np.intp)
    vals = np.empty((b, k_eff), s.dtype)
    for i in range(b):
        order = np.lexsort((cc[i], -cv[i]))[:k_eff]
        rows[i] = cc[i][order]
        vals[i] = cv[i][order]
    return rows, vals


class VectorIndex(RowIndex):
    """Fixed-capacity embedding store with free-list row management.

    With a :class:`~repro.core.clustering.ClusterRouter` attached,
    stage 1 runs as a clustered (IVF-style) routed scan — centroids
    first, then only the selected clusters' member rows — instead of
    the full-matrix brute force (DESIGN.md §12). Until the router has
    trained (or without one) the brute path runs unchanged."""

    def __init__(self, capacity: int, dim: int,
                 backend: Optional[str] = None, router=None):
        super().__init__(capacity, dim, router=router)
        self.backend = resolve_backend(backend)
        self.emb = np.zeros((capacity, dim), np.float32)
        if self.backend == "kernel":
            from repro.kernels.ops import (
                ann_topk, ann_topk_ivf_jit, ann_topk_ivf_sharded_jit,
                pad_queries)

            self._pad_queries = pad_queries
            self._kernel_fn = ann_topk
            self._ivf_kernel_fn = ann_topk_ivf_jit
            self._ivf_sharded_fn = ann_topk_ivf_sharded_jit

    def add(self, se_id: int, embedding: np.ndarray) -> int:
        row = self._alloc(se_id)
        self.emb[row] = embedding
        if self.router is not None:
            self.router.note_add(row, self.emb[row], self)
        return row

    def _store_rows(self, ra: np.ndarray, embs: np.ndarray) -> None:
        self.emb[ra] = embs

    def _clear_rows(self, ra: np.ndarray) -> None:
        self.emb[ra] = 0.0

    def _mirrored(self) -> tuple:
        return self.emb, self.active

    def route_embs(self, rows: np.ndarray) -> np.ndarray:
        """Unit-norm fp32 rows for centroid training/assignment."""
        return self.emb[rows]

    # ----------------------------------------------------------- search

    def search(self, q: np.ndarray, k: int, tau_sim: float):
        """Top-k rows with cosine ≥ tau_sim. q: (dim,) unit-norm.
        Returns (se_ids, sims) sorted by similarity desc."""
        return self.search_batch(q[None], k, tau_sim)[0]

    def _search_routed(self, q: np.ndarray, k: int, routed):
        """Scan only the routed clusters' member rows. The gathered
        union is in ascending row order and the not-allowed mask uses
        the same -1.0 sentinel as the brute path's inactive mask, so at
        nprobe=all the scored matrix is exactly the brute matrix
        restricted to active rows — same values, same tie order."""
        with self.tracer.wall("seri.scan"):
            g_rows, allowed, self.last_scanned = routed
            rt = self.router
            s = np.where(allowed, q @ self.emb[g_rows].T, -1.0)
            if rt.n_shards > 1:
                # shard-parallel selection over the SAME score matrix: each
                # shard top-k's its owned member columns, finalists merge
                # under topk_desc's (value desc, row asc) order — so the
                # result is bit-identical to the unsharded path and the
                # float-reduction tolerance across shard counts is zero
                owners = rt.shard_of[rt.assign[g_rows]]
                n_cent = self.last_scanned - len(g_rows)
                self.last_scanned_max_shard = n_cent + int(
                    np.bincount(owners, minlength=rt.n_shards).max())
                lrows, sims = sharded_topk_merge(s, owners, rt.n_shards, k)
            else:
                self.last_scanned_max_shard = self.last_scanned
                lrows, sims = topk_desc(s, k)                      # (B, k)
            return g_rows[lrows], sims

    def _search_routed_kernel(self, q: np.ndarray, k: int):
        """Routed scan on the Pallas backend: routing (centroid scores +
        top-nprobe) runs INSIDE the jit wrapper, so no host-side
        route()/gather happens at all — rows-scanned accounting derives
        from the kernel's own cluster selection."""
        rt = self.router
        if rt.n_shards > 1:
            return self._search_routed_kernel_sharded(q, k)
        tr = self.tracer
        with tr.wall("seri.layout"):
            layout, bucket_rows, bucket_valid = rt.kernel_buckets(self)
        nprobe = rt.cfg.n_clusters if rt.cfg.nprobe is None \
            else min(rt.cfg.nprobe, rt.cfg.n_clusters)
        live = rt.counts > 0
        args = self._put(rt.centroids, live.astype(np.int32), layout,
                         bucket_rows, bucket_valid, q)
        with tr.wall("seri.scan"):
            sims, rows, sel, en = self._ivf_kernel_fn(*args, nprobe, k)
            (sel,), (en,) = self._fetch(sel), self._fetch(en)
            probed = np.unique(sel[en > 0])
            (rows,), (sims,) = self._fetch(rows), self._fetch(sims)
        self.last_scanned = int(live.sum() + rt.counts[probed].sum())
        self.last_scanned_max_shard = self.last_scanned
        return rows, sims

    def _search_routed_kernel_sharded(self, q: np.ndarray, k: int):
        """Shard-parallel Pallas routed scan (DESIGN.md §13): routing
        stays global (centroid top-nprobe inside the jit wrapper); each
        mesh shard scans only its owned probes under ``shard_map`` and
        the S·nprobe·k finalists merge with one cross-shard
        ``lax.top_k``. Scan accounting splits the probed members by
        owner so the engine can charge max-over-shards."""
        from repro.kernels.ann_topk_sharded import shard_placement

        rt = self.router
        tr = self.tracer
        with tr.wall("seri.layout"):
            layout, shard_rows, shard_valid, bounds = \
                rt.kernel_shard_buckets(self)
        nprobe = rt.cfg.n_clusters if rt.cfg.nprobe is None \
            else min(rt.cfg.nprobe, rt.cfg.n_clusters)
        live = rt.counts > 0
        cent, live_d, qd, layout, shard_rows, shard_valid = self._put(
            rt.centroids, live.astype(np.int32), q,
            sharded=(layout, shard_rows, shard_valid),
            placement=shard_placement(rt.n_shards))
        with tr.wall("seri.scan"):
            sims, rows, sel, en = self._ivf_sharded_fn(
                cent, live_d, layout, shard_rows, shard_valid, bounds, qd,
                nprobe, k,
            )
            (sel,), (en,) = self._fetch(sel), self._fetch(en)
            probed = np.unique(sel[en > 0])
            (rows,), (sims,) = self._fetch(rows), self._fetch(sims)
        n_cent = int(live.sum())
        per_shard = np.bincount(
            rt.shard_of[probed], weights=rt.counts[probed],
            minlength=rt.n_shards)
        self.last_scanned = n_cent + int(rt.counts[probed].sum())
        self.last_scanned_max_shard = n_cent + int(per_shard.max())
        return rows, sims

    def _search_brute(self, q: np.ndarray, k: int):
        """The brute scan of every row. On the kernel backend a pass is
        one put, one dispatch and one fetch: the queries are zero-padded
        on the host to the kernel's aligned block (``pad_queries``) and
        put with the mirror's dirty rows, the jitted ``ann_topk`` runs on
        the resident mirror, its rows and scores come back in one
        blocking copy, and the pad rows are sliced off on the host."""
        if self.backend == "kernel":
            b = q.shape[0]
            emb, active, qd = self._put(self._pad_queries(q), mirror=True)
            with self.tracer.wall("seri.scan"):
                sims, rows = self._kernel_fn(emb, active, qd, k)
                rows, sims = self._fetch(rows, sims)
            return rows[:b], sims[:b]
        with self.tracer.wall("seri.scan"):
            return self.numpy_brute(q, k)

    def numpy_brute(self, q: np.ndarray, k: int):
        """The numpy brute scan: the reference the Pallas kernel is
        checked against, callable on either backend."""
        # (B, N) row-major so the per-query partition/sort runs over
        # contiguous lanes (axis=0 on (N, B) is strided and ~3× slower
        # at large N·B)
        s = np.where(self.active[None, :], q @ self.emb.T, -1.0)
        rows, sims = topk_desc(s, k)                           # (B, k)
        return rows, sims

    def search_batch(self, q: np.ndarray, k: int, tau_sim: float):
        """Batched stage-1: q (B, dim) -> list of B (se_ids, sims) pairs.

        One masked matmul over the whole query block (brute) or over the
        routed cluster union (IVF); per-column top-k via ``argpartition``
        along axis 0. Each column's result is identical to the
        single-query path (numpy partitions/sorts each 1-D lane
        independently), so batching never changes retrieval semantics.
        """
        b = q.shape[0]
        self._new_pass()
        if len(self) == 0:
            self.last_scanned = 0
            self.last_scanned_max_shard = 0
            empty = ([], np.zeros(0, np.float32))
            return [empty] * b
        tr = self.tracer
        with tr.wall("seri.search"):
            q = np.asarray(q, np.float32)
            rows, sims, routed = self._routed_dispatch(
                q,
                lambda: self._search_routed_kernel(q, k),
                lambda info: self._search_routed(q, k, info),
                lambda: self._search_brute(q, k),
            )
            out = []
            with tr.wall("seri.select"):
                for i in range(b):
                    keep = sims[i] >= tau_sim
                    if routed:
                        keep &= rows[i] >= 0   # kernel NEG slots: row -1
                    r = rows[i][keep]
                    # row→se_id as ONE int64 gather (no per-candidate
                    # Python loop)
                    out.append((self.row_se[r].tolist(),
                                sims[i][keep].astype(np.float32)))
        return out


@dataclasses.dataclass
class SeriResult:
    hit: bool
    se: Optional[SemanticElement]
    n_candidates: int
    judge_calls: int
    best_score: float
    # stage-1 similarities ALIGNED with the surviving candidate list:
    # sims[j] is the cosine of the j-th candidate the judge scored
    # (expired stage-1 matches are dropped from both)
    sims: np.ndarray


class Seri:
    """Two-stage retrieval configuration over a SE store.

    Holds the stage-1 index, the judge, and the thresholds. The
    retrieval pipeline itself lives in ``CortexCache._stage1_blocks`` /
    ``_judge_blocks`` (one implementation for the scalar, batched, and
    engine-staged paths — and the seam the tiered cache overrides);
    keeping a second copy here is how sims/candidate misalignment bugs
    happen twice."""

    def __init__(self, index: VectorIndex, judge, *, tau_sim: float = 0.9,
                 tau_lsm: float = 0.9, top_k: int = 4):
        from repro.core.judge_pipeline import as_pipeline

        self.index = index
        # every stage-2 interaction goes through ONE JudgePipeline
        # (DESIGN.md §14); a raw judge object is wrapped in a default
        # pipeline (no admission band, FLOPs-derived token cost)
        self.pipeline = as_pipeline(judge)
        self.tau_sim = tau_sim
        self.tau_lsm = tau_lsm
        self.top_k = top_k

    @property
    def judge(self):
        """Back-compat: the decision scorer behind the pipeline."""
        return self.pipeline.decisions

    @property
    def stage1_gate(self) -> float:
        """Similarity gate stage 1 applies: the admission band's lower
        edge when armed, τ_sim otherwise."""
        return self.pipeline.stage1_gate(self.tau_sim)
