"""The system under test, wired through its public calls, and the window.

One lookup is one agent tool call. A micro-batch of lookups runs the
program's served path:

    ModelEmbedder.embed_batch                      (embedder forward)
    CortexCache.lookup_batch
        VectorIndex.search_batch -> Pallas kernels (stage 1)
        JudgePipeline.score_pairs -> ModelJudge    (stage 2 prefill)
    CortexCache.insert, once a miss's fetch has come back

Decisions come from the program's calibration shim,
``JudgePipeline(OracleJudge, compute=ModelJudge)``: oracle verdicts, real
judge prefill paid. The index is driven by the frozen world's embeddings,
while the embedder's forward is paid on every micro-batch and its output
kept for the check: random weights cannot give semantic embeddings.

The benchmark instruments only the instances it builds: each call above
runs inside a span of its own (``jax.profiler.TraceAnnotation`` too, in a
traced run), and the index's ``search_batch``, ``add`` and
``remove_rows`` are wrapped on the instance so that the check can replay
the index state each stage-1 pass saw.
"""
from __future__ import annotations

import contextlib
import heapq
import time
from collections import deque
from typing import Any, NamedTuple

import numpy as np

import model_ref

SIZES = (64, 32, 16, 8, 4, 2, 1)   # micro-batch sizes; set-up warms each
DRAIN_S = 60.0                      # how long past the close a lookup may take


class Answer(NamedTuple):
    """A lookup's decision as it was served. The program's result holds a
    live view of a cache row, which a later eviction may hand to another
    entry (an expired entry scores lowest under LCFU), so the check reads
    this copy, taken when the decision was made."""
    hit: bool
    key: Any
    value: Any
    n_candidates: int
    judge_calls: int

    @classmethod
    def of(cls, r) -> "Answer":
        return cls(bool(r.hit), r.se.key if r.hit else None,
                   r.se.value if r.hit else None, int(r.n_candidates),
                   int(r.judge_calls))


class Spans:
    """(name, start, end) on the host's perf_counter clock."""

    def __init__(self, annotate: bool):
        self.rows: list = []
        self.annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.rows.append((name, t0, time.perf_counter()))


class TimedJudge:
    """The pipeline's ``compute``: ModelJudge prefill in a span, its
    scores kept (the pipeline itself discards them)."""

    def __init__(self, model, spans: Spans):
        self.model, self.spans = model, spans
        self.cfg, self.max_len = model.cfg, model.max_len
        self.calls: list = []

    def score_pairs(self, queries, keys):
        with self.spans("judge"):
            s = self.model.score_pairs(queries, keys)
        self.calls.append((list(queries), list(keys), np.asarray(s)))
        return s


class RecordedDecisions:
    """The pipeline's ``decisions``: the OracleJudge, every call kept in
    order so the check can replay the oracle from its first score."""

    def __init__(self, oracle):
        self.oracle = oracle
        self.calls: list = []
        self.batch = -1          # window micro-batch the next call belongs to

    def score_pairs(self, queries, keys):
        s = self.oracle.score_pairs(queries, keys)
        self.calls.append((self.batch, list(queries), list(keys),
                           np.array(s)))
        return s

    def staticity(self, query):
        return self.oracle.staticity(query)


class IndexLog:
    """Stage-1 passes and index mutations, recorded once ``on``."""

    def __init__(self, index, spans: Spans):
        self.passes: list = []
        self.muts: list = []
        self.on = False
        search, add, remove = index.search_batch, index.add, \
            index.remove_rows

        def search_batch(q, k, gate):
            with spans("stage1"):
                out = search(q, k, gate)
            if self.on:
                self.passes.append(dict(q=np.array(q, np.float32), k=k,
                                        gate=float(gate), out=out,
                                        at=len(self.muts),
                                        shape=scan_shape(index, len(q))))
            return out

        def add_row(se_id, emb):
            row = add(se_id, emb)
            if self.on:
                self.muts.append(("add", int(row), int(se_id),
                                  np.array(emb, np.float32)))
            return row

        def remove_rows(rows):
            if self.on:
                ra = np.asarray(list(rows), np.int64)
                self.muts.append(("remove", ra, index.row_se[ra].copy()))
            return remove(rows)

        index.search_batch = search_batch
        index.add = add_row
        index.remove_rows = remove_rows


def scan_shape(index, b: int):
    """What the pass's kernel scanned, for the FLOP/byte counts: None off
    the kernel backend (nothing ran on the device)."""
    if index.backend != "kernel":
        return None
    rt = index.router
    if rt is not None and rt.ready:
        layout = rt.kernel_buckets(index)[0]
        c, cap, d = layout.shape
        nprobe = c if rt.cfg.nprobe is None else min(rt.cfg.nprobe, c)
        return dict(kind="ann_topk_ivf", B=b, C=c, cap=cap, D=d,
                    nprobe=nprobe, itemsize=layout.dtype.itemsize)
    n, d = index.emb.shape
    return dict(kind="ann_topk", B=b, N=n, D=d,
                itemsize=index.emb.dtype.itemsize)


def _swap_params(model, weights) -> None:
    """Serve the benchmark's weights: same tree, shapes and dtypes as the
    program's own initialisation, which is dropped first."""
    import jax

    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.params)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    if want != got:
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")
    model.params = None
    model.params = weights


class Served:
    """One run's system under test, built from a configuration file, its
    judge and embedder (``model_ref.served_models``) and a traffic mix."""

    def __init__(self, cfg: dict, models: dict, mix, seed: int,
                 spans: Spans):
        from repro.core.cache import make_cache
        from repro.core.clustering import ClusterConfig
        from repro.core.embedder import ModelEmbedder
        from repro.core.judge import ModelJudge, OracleJudge
        from repro.core.judge_pipeline import JudgePipeline
        from traffic.gen.remote import RemoteDataService

        dep = cfg["deployment"]
        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        self.world = mix.world
        jm, em = models["judge"], models["embedder"]
        self.judge_w = jm.family.make_weights(jm.hf, seed + 1)
        self.embed_w = em.family.make_weights(em.hf, seed + 2)
        judge = ModelJudge(cfg=jm.family.program_config(jm.hf, cfg["name"]),
                           max_len=dep["judge_max_len"], seed=0)
        _swap_params(judge, self.judge_w)
        self.embedder = ModelEmbedder(
            cfg=em.family.program_config(em.hf, cfg["name"]),
            max_len=dep["embed_max_len"], seed=0)
        _swap_params(self.embedder, self.embed_w)
        self.judge = TimedJudge(judge, spans)
        self.decisions = RecordedDecisions(OracleJudge(
            self.world, accuracy=dep["judge_accuracy"],
            seed=dep["oracle_seed"]))
        pipe = JudgePipeline(self.decisions, compute=self.judge)
        ix, ca = dep["index"], dep["cache"]
        router = ix.get("router")
        self.cache = make_cache(
            capacity_bytes=1 << 62, dim=ix["dim"], judge=pipe,
            index_capacity=ix["rows"], tau_sim=ca["tau_sim"],
            tau_lsm=ca["tau_lsm"], top_k=ca["top_k"],
            eviction=ca["eviction"], max_ttl=ca["max_ttl"],
            cluster=ClusterConfig(**router) if router else None)
        self.index = self.cache.seri.index
        self.log = IndexLog(self.index, spans)
        self.remote = RemoteDataService(seed=seed, **mix.remote)
        self.embeds: list = []      # (texts, program embeddings), window
        self.inserted: list = []    # (se_id, query, world embedding)
        self.t_clock = time.perf_counter()   # the cache's clock starts

    def now(self) -> float:
        return time.perf_counter() - self.t_clock

    # ---------------------------------------------------------- set-up

    def fill(self, background) -> None:
        """Prefilled entries, then seeded background rows up to the
        index's capacity: one bulk ``insert_block`` each. Background rows
        are random unit vectors, which never come within tau_sim of a
        query; they carry the lowest value, so LCFU evicts them first."""
        w = self.world
        pre = self.mix.prefill
        if pre:
            embs = np.stack([w.embed(q) for q in pre])
            self.cache.insert_block(
                pre, embs, [w.answer(q) for q in pre], now=0.0, cost=0.005,
                latency=0.4, size=2304, staticity=10, ttl=1e9)
        n = len(background)
        self.cache.insert_block(
            [f"bg:-1:{i}" for i in range(n)], background, ["bg"] * n,
            now=0.0, cost=0.0005, latency=0.04, size=2304, staticity=10,
            ttl=1e9)

    def warm(self) -> None:
        """Compile every shape the window can use, then bring the cache
        to steady state: the judge at each pair bucket, and the warm-up
        stream in micro-batches of every size, largest first, then the
        largest that fit. The first round's misses are admitted after it
        (one index rebuild, not one per size); later misses at once."""
        stream = list(self.mix.warmup)
        if len(stream) < sum(SIZES):
            raise ValueError("the warm-up stream must cover every "
                             f"micro-batch size: {sum(SIZES)} lookups")
        pairs = ["warm"] * SIZES[0]
        for m in sorted(SIZES):
            if m <= 32:
                self.judge.model.score_pairs(pairs[:m], pairs[:m])
        w = self.world
        sizes = list(SIZES)
        deferred: list = []
        off = 0
        while off < len(stream):
            b = sizes.pop(0) if sizes else next(
                s for s in SIZES if s <= len(stream) - off)
            texts = stream[off:off + b]
            off += b
            self.embedder.embed_batch(texts)
            res = self.cache.lookup_batch(
                texts, np.stack([w.embed(q) for q in texts]), self.now())
            deferred.extend(q for q, r in zip(texts, res) if not r.hit)
            if not sizes:
                for q in deferred:
                    self._admit(q, w.embed(q), self._fetch_delay(q),
                                log=False)
                deferred = []

    # ---------------------------------------------------------- window

    def window(self, seconds: float) -> dict:
        """Serve the mix's window open-loop and return what happened.
        Latency runs from a lookup's due time to its decision; a miss's
        fetch is never waited for, and its answer is admitted once the
        remote model's delay has passed."""
        w, spans = self.world, self.spans
        due = np.array([d for d, _ in self.mix.window], np.float64)
        queries = [q for _, q in self.mix.window]
        n = len(queries)
        wemb = np.stack([w.embed(q) for q in queries]) if n else \
            np.zeros((0, self.index.dim), np.float32)
        decided = np.full(n, np.nan)
        results: list = [None] * n
        batch_of = np.full(n, -1)
        fetches = 0
        queue: deque = deque()
        pending: list = []          # (ready_s, seq, lookup)
        lateness: list = []
        nxt = nb = 0
        self.log.on = True
        t0 = time.perf_counter()
        with spans("window"):
            while True:
                now = time.perf_counter() - t0
                while nxt < n and due[nxt] <= now:
                    queue.append(nxt)
                    nxt += 1
                while pending and pending[0][0] <= now:
                    _, i, delay = heapq.heappop(pending)
                    self._admit(queries[i], wemb[i], delay)
                if now > seconds + DRAIN_S:
                    break
                if queue:
                    b = next(s for s in SIZES if s <= len(queue))
                    idx = [queue.popleft() for _ in range(b)]
                    self.decisions.batch = nb
                    res = self._batch([queries[i] for i in idx], wemb[idx])
                    t = time.perf_counter() - t0
                    for i, r in zip(idx, res):
                        decided[i], batch_of[i] = t, nb
                        results[i] = Answer.of(r)
                        if not r.hit:
                            fetches += 1
                            delay = self._fetch_delay(queries[i])
                            heapq.heappush(pending, (t + delay, i, delay))
                    nb += 1
                    continue
                if nxt >= n:
                    break
                wake = min(due[nxt], pending[0][0] if pending else np.inf)
                time.sleep(max(0.0, wake - now))
                lateness.append(time.perf_counter() - t0 - wake)
        t_end = time.perf_counter() - t0
        self.log.on = False
        self.decisions.batch = -1
        return dict(due=due, decided=decided, results=results,
                    batch_of=batch_of, queries=queries, fetches=fetches,
                    seconds=seconds, t_end=t_end, t0=t0,
                    lateness=np.array(lateness))

    def _batch(self, texts, embs):
        with self.spans("batch"):
            with self.spans("embed"):
                e = self.embedder.embed_batch(texts)
            self.embeds.append((list(texts), e))
            with self.spans("lookup"):
                return self.cache.lookup_batch(texts, embs, self.now())

    def _fetch_delay(self, query) -> float:
        """Seconds the remote tool takes to answer (never slept in a
        lookup's latency): the copied WAN model, x4 for premium intents."""
        out = self.remote.fetch(0.0,
                                latency_mult=self.world.latency_mult(query))
        return out.finish

    def _admit(self, query, emb, delay: float, log: bool = True) -> None:
        """Admit a fetched answer, which took ``delay`` seconds to come."""
        w = self.world
        with self.spans("insert"):
            se = self.cache.insert(
                query, emb, w.fetch(query), now=self.now(),
                cost=0.005 * w.cost_mult(query), latency=delay,
                size=w.value_size(query), intent=w.intent_of(query))
        if log:
            self.inserted.append((se.se_id, query,
                                  np.array(emb, np.float32)))


def background_rows(n: int, d: int, seed: int) -> np.ndarray:
    """n seeded random unit rows, drawn on the device in one jitted call
    and copied to the host, where the index lives."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        x = jax.random.normal(key, (n, d), jnp.float32)
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    return np.asarray(draw(model_ref.jax_key(seed)))
