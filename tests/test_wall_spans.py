"""Wall-clock spans and transfer counters of the served lookup path
(DESIGN.md §15, "Wall spans"), on a tiny kernel-backend cache whose
Pallas kernels run interpreted on the CPU."""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from repro.core.cache import make_cache
from repro.core.clustering import ClusterConfig
from repro.core.embedder import ModelEmbedder
from repro.core.seri import SYNC_CHUNK
from repro.core.judge import ModelJudge
from repro.core.judge_pipeline import JudgePipeline
from repro.launch.serve import run_once
from repro.obs import trace as obs_trace
from repro.obs.analyze import wall_breakdown
from repro.obs.trace import NULL_TRACER, Tracer

DIM, ROWS = 64, 1024
# the chip benchmark's own span names (benchmarks/chip/tracing.SPANS):
# a program span may never take one
BENCH_SPANS = {"window", "batch", "embed", "lookup", "stage1", "judge",
               "insert"}
PROGRAM_SPANS = {
    "embedder.embed_batch", "embedder.tokenize", "embedder.encode",
    "cache.lookup_batch", "seri.search", "seri.layout", "seri.h2d",
    "seri.scan", "seri.select", "judge.score_pairs", "judge.tokenize",
    "judge.prefill", "cache.insert", "cache.evict", "seri.add"}


class _Decide:
    """Decision scorer: every pair is a match."""

    def score_pairs(self, queries, keys):
        return np.full(len(queries), 0.95, np.float32)

    def staticity(self, query):
        return 5


@pytest.fixture(scope="module")
def judge_model():
    return ModelJudge(max_len=32)


def _unit(rng, n):
    x = rng.standard_normal((n, DIM)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _cache(judge_model, cluster=None, n=ROWS - 8, seed=0):
    judge_model.bind_tracer(NULL_TRACER)     # shared across tests
    cache = make_cache(
        capacity_bytes=1 << 40, dim=DIM, index_capacity=ROWS,
        judge=JudgePipeline(_Decide(), compute=judge_model),
        backend="kernel", cluster=cluster)
    rng = np.random.default_rng(seed)
    embs = _unit(rng, n)
    cache.insert_block([f"k{i}" for i in range(n)], embs, ["v"] * n,
                       now=0.0, cost=0.01, latency=0.1, size=10,
                       staticity=5, ttl=1e9)
    return cache, embs


def _queries(embs, idx):
    """Queries close to the given rows, so stage 1 finds candidates."""
    q = embs[idx] + 1e-3
    return q / np.linalg.norm(q, axis=1, keepdims=True)


def _serve(cache, embs, batches=((0, 1, 2), (3, 4))):
    for idx in batches:
        cache.lookup_batch([f"q{i}" for i in idx], _queries(embs, list(idx)),
                           now=1.0)
    cache.insert("new", _queries(embs, [5])[0], "v", now=2.0, cost=0.01,
                 latency=0.1, size=10)


def test_spans_nest_and_share_one_call_id(judge_model):
    cache, embs = _cache(judge_model)
    tr = Tracer()
    cache.bind_tracer(tr)
    emb = ModelEmbedder(dim=64, max_len=16)
    emb.bind_tracer(tr)
    emb.embed_batch(["a", "bb"])
    _serve(cache, embs)
    spans = tr.wall_spans
    assert spans and all(s is not None for s in spans)
    names = {s.name for s in spans}
    assert names <= PROGRAM_SPANS and not names & BENCH_SPANS
    assert {"seri.h2d", "seri.scan", "judge.prefill", "cache.evict",
            "seri.add", "embedder.encode"} <= names
    # every span closes inside its parent; siblings do not overlap
    by_parent: dict = {}
    for i, s in enumerate(spans):
        assert s.t0 <= s.t1
        if s.parent >= 0:
            p = spans[s.parent]
            assert s.parent < i and p.t0 <= s.t0 and s.t1 <= p.t1
            assert s.call == p.call
        by_parent.setdefault(s.parent, []).append(s)
    for sib in by_parent.values():
        sib = sorted(sib, key=lambda s: s.t0)
        assert all(a.t1 <= b.t0 for a, b in zip(sib, sib[1:]))
    # one identifier per root span: per lookup_batch, per insert
    roots = [s for s in spans if s.parent < 0]
    assert [s.name for s in roots] == [
        "embedder.embed_batch", "cache.lookup_batch", "cache.lookup_batch",
        "cache.insert"]
    assert len({s.call for s in roots}) == len(roots)
    for r in roots[1:3]:
        inside = {s.name for s in spans if s.call == r.call}
        assert {"seri.search", "seri.h2d", "seri.scan", "seri.select",
                "judge.score_pairs", "judge.prefill"} <= inside
    # a window's breakdown: self time is what the children leave
    bd = wall_breakdown(spans, start=1)
    assert bd["cache.lookup_batch"]["n"] == 2
    lb = bd["cache.lookup_batch"]
    kids = sum(bd[n]["total_s"] for n in ("seri.search",
                                          "judge.score_pairs"))
    assert lb["self_s"] == pytest.approx(lb["total_s"] - kids, abs=1e-9)


def test_null_tracer_records_nothing(judge_model, monkeypatch):
    assert NULL_TRACER.wall("a") is NULL_TRACER.wall("b")
    assert not hasattr(NULL_TRACER, "wall_spans")

    def boom(*a, **k):
        raise AssertionError("the disabled path touched the clock or "
                             "the profiler")

    synced = []
    orig_sync = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: synced.append(1) or orig_sync(x))
    cache, embs = _cache(judge_model)
    monkeypatch.setattr(obs_trace, "perf_counter", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    _serve(cache, embs)
    assert cache.tracer is NULL_TRACER and not synced
    monkeypatch.undo()
    # bound, the copy is waited for inside seri.h2d: once per pass
    tr = Tracer()
    cache.bind_tracer(tr)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: synced.append(1) or orig_sync(x))
    _serve(cache, embs)
    n_h2d = sum(s.name == "seri.h2d" for s in tr.wall_spans)
    assert n_h2d == 2 and len(synced) == n_h2d


@pytest.mark.parametrize("case", ["first", "clean", "dirty"])
def test_brute_pass_counts_its_bytes(judge_model, case):
    """The first brute pass puts the whole index; later passes keep it on
    the device and send their queries and the rows changed since. The
    queries are padded on the host to the kernel's 8-row block before
    the put: three queries move 8 fp32 rows."""
    cache, embs = _cache(judge_model)
    ix = cache.seri.index
    queries = 8 * DIM * 4
    want = ix.emb.nbytes + ix.active.nbytes + queries
    if case != "first":
        cache.lookup_batch(["x"], _queries(embs, [5]), now=0.5)
        want = queries
    if case == "dirty":
        ix.add(10 ** 6, embs[7])
        ix.remove_rows([0])
        # one chunk of rows: fp32 row, mask byte, int32 row index
        want += SYNC_CHUNK * (DIM * 4 + 1 + 4)
    before = cache.scan.h2d_bytes
    cache.lookup_batch(["a", "b", "c"], _queries(embs, [1, 2, 3]), now=1.0)
    assert ix.last_h2d_bytes == want
    assert cache.scan.h2d_bytes - before == want
    assert cache.scan.full_uploads == 1
    assert cache.scan.synced_rows == (2 if case == "dirty" else 0)


@pytest.mark.parametrize("shards", [1, 2])
def test_routed_pass_counts_its_bytes(judge_model, shards):
    cfg = ClusterConfig(n_clusters=8, nprobe=2, min_train=256,
                        refresh_every=1 << 20, seed=1, n_shards=shards)
    cache, embs = _cache(judge_model, cluster=cfg)
    ix, rt = cache.seri.index, cache.seri.index.router
    assert rt.ready
    before = cache.scan.h2d_bytes
    res = cache.lookup_batch(["a", "b"], _queries(embs, [0, 1]), now=1.0)
    assert ix.passes["routed"] == 1 and all(r.hit for r in res)
    if shards == 1:
        layout = rt.kernel_buckets(ix)
    else:
        layout = rt.kernel_shard_buckets(ix)[:3]
    want = (rt.centroids.nbytes + rt.cfg.n_clusters * 4
            + sum(x.nbytes for x in layout) + 2 * DIM * 4)
    assert cache.scan.h2d_bytes - before == want


@pytest.mark.parametrize("shards", [0, 1, 2])
def test_each_pass_counts_its_blocking_fetches(judge_model, shards):
    """A brute kernel pass (``shards`` 0) waits on the device once, for
    its rows and scores together; a routed pass waits four times (the
    probed clusters, their mask, the rows, the scores)."""
    cfg = None if not shards else ClusterConfig(
        n_clusters=8, nprobe=2, min_train=256, refresh_every=1 << 20,
        seed=1, n_shards=shards)
    cache, embs = _cache(judge_model, cluster=cfg)
    sc, k = cache.scan, cache.seri.top_k
    for idx in ([0, 1, 2], [3], [4, 5]):
        w0, b0 = sc.d2h_waits, sc.d2h_bytes
        cache.lookup_batch(["q"] * len(idx), _queries(embs, idx), now=1.0)
        if shards:
            assert sc.d2h_waits - w0 == 4
            assert sc.d2h_bytes - b0 > 0
        else:
            # int32 rows and fp32 scores of the 8-row padded block
            assert sc.d2h_waits - w0 == 1
            assert sc.d2h_bytes - b0 == 8 * k * (4 + 4)
    assert cache.seri.index.passes["routed" if shards else "brute"] == 3


def test_the_numpy_backend_moves_nothing(judge_model):
    cache = make_cache(capacity_bytes=1 << 40, dim=DIM, index_capacity=64,
                       judge=_Decide(), backend="numpy")
    embs = _unit(np.random.default_rng(3), 8)
    cache.insert_block([f"k{i}" for i in range(8)], embs, ["v"] * 8,
                       now=0.0, cost=0.01, latency=0.1, size=10,
                       staticity=5, ttl=1e9)
    cache.lookup_batch(["a"], _queries(embs, [0]), now=1.0)
    assert cache.scan.total_rows == 8 and cache.scan.h2d_bytes == 0
    assert cache.scan.d2h_waits == 0 and cache.scan.d2h_bytes == 0


@pytest.mark.parametrize("n,padded,chunks", [(1, 0, 1), (3, 1, 1),
                                             (33, 0, 2), (70, 2, 3)])
def test_judge_counts_its_pad_pairs(judge_model, n, padded, chunks):
    tr = Tracer()
    judge_model.bind_tracer(tr)
    s0, p0 = judge_model.pairs_scored, judge_model.pairs_padded
    out = judge_model.score_pairs(["q"] * n, ["k"] * n)
    judge_model.bind_tracer(NULL_TRACER)
    assert out.shape == (n,)
    assert judge_model.pairs_scored - s0 == n
    assert judge_model.pairs_padded - p0 == padded
    names = [s.name for s in tr.wall_spans]
    assert names.count("judge.prefill") == chunks
    assert names[:2] == ["judge.score_pairs", "judge.tokenize"]


def test_simulator_exports_ignore_wall_spans(tmp_path, monkeypatch):
    """The engine draws no wall spans of its own; with its tracer bound
    to its cache, the virtual-time exports are byte-identical."""
    from repro.serving.engine import Engine

    kw = dict(n_requests=60, concurrency=4, judge_band=0.1, seed=3)
    seen = []
    run_once(trace=str(tmp_path / "unbound"),
             on_done=lambda e: seen.append(len(e.trace.wall_spans)), **kw)
    init = Engine.__init__

    def bound(self, *a, **k):
        init(self, *a, **k)
        self.cache.bind_tracer(self.trace)

    monkeypatch.setattr(Engine, "__init__", bound)
    run_once(trace=str(tmp_path / "bound"),
             on_done=lambda e: seen.append(len(e.trace.wall_spans)), **kw)
    assert seen[0] == 0 and seen[1] > 0
    for ext in ("jsonl", "chrome.json"):
        assert (tmp_path / f"bound.{ext}").read_bytes() \
            == (tmp_path / f"unbound.{ext}").read_bytes()


def test_spans_reach_the_profiler_trace(judge_model, tmp_path):
    """Each wall span is a host event of the same name in the xplane, the
    file the device ops are read from, nested as the program nests."""
    from jax.profiler import ProfileData

    cache, embs = _cache(judge_model)
    cache.bind_tracer(Tracer())
    _serve(cache, embs, batches=((0, 1),))        # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    _serve(cache, embs, batches=((2, 3),))
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    ev: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in PROGRAM_SPANS:
                        ev.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    assert {"cache.lookup_batch", "seri.search", "seri.h2d", "seri.scan",
            "judge.prefill", "cache.insert"} <= set(ev)
    (lb,) = ev["cache.lookup_batch"]
    (h2d,) = ev["seri.h2d"]
    assert lb[0] <= h2d[0] < h2d[1] <= lb[1]
