"""The lightweight semantic judge (LSM) — Seri stage 2 (paper §4.2).

Given (new query, cached result) the judge emits a confidence score
S_lsm ∈ [0,1] that the cached result answers the query, plus a staticity
estimate (1–10) at admission time.

* ``OracleJudge`` — decision-faithful judge for behavioural experiments:
  knows the synthetic world's ground-truth intent equivalence and flips
  decisions with configurable TPR/FPR noise. Its *scores* are drawn from
  two calibrated beta-like distributions so threshold recalibration
  (Algorithm 1) has a real precision curve to sweep.
* ``ModelJudge`` — a real cross-encoder in JAX (prefill-only, single
  score token — the profile that makes co-location cheap, §4.4), at the
  qwen3-0.6b published widths or a shrink. With random weights its
  decisions are meaningless; it exists to measure the judge's true
  compute footprint and to drive the co-location scheduler.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from repro.obs.trace import NULL_TRACER


@dataclasses.dataclass
class JudgeVerdict:
    score: float
    equivalent: bool      # score >= threshold decided by caller (Seri)
    staticity: int = 5


class OracleJudge:
    """Ground-truth-backed judge with calibrated score noise.

    Score noise is seeded per **(pair, nth-scoring-of-that-pair)** from a
    stable hash of the pair text — not drawn from one shared stream — so
    scores do not depend on how requests are micro-batched, reordered
    across lanes, or interleaved with other requests (DESIGN.md §8:
    batched and scalar execution stay bit-identical). Re-scoring the
    same pair later re-rolls (the judge's borderline mistakes stay
    transient, so threshold recalibration sees fresh noise, as with the
    original shared-stream model)."""

    def __init__(self, world, accuracy: float = 0.98, seed: int = 0,
                 max_pairs: int = 65536):
        self.world = world
        self.seed = seed
        # score distributions: equivalent pairs ~ high, others ~ low
        self.acc = accuracy
        # nth-scoring counter per pair, LRU-bounded at max_pairs (same
        # idiom as MarkovPrefetcher._prev): an evicted pair that comes
        # back re-rolls from n=0, which only perturbs borderline-noise
        # replay on workloads with > max_pairs distinct live pairs
        self.max_pairs = max_pairs
        self._pair_counts: dict = {}

    @staticmethod
    def _u01(x: int, salt: int) -> float:
        """splitmix64 finalizer -> uniform in [0, 1). Counter-based
        hashing is ~10× cheaper than constructing a Generator per pair,
        which matters because scoring sits on the hot lookup path."""
        m = (1 << 64) - 1
        x = (x + salt * 0x9E3779B97F4A7C15) & m
        x ^= x >> 30
        x = (x * 0xBF58476D1CE4E5B9) & m
        x ^= x >> 27
        x = (x * 0x94D049BB133111EB) & m
        x ^= x >> 31
        return x / 2.0**64

    def _pair_score(self, q: str, c: str) -> float:
        import zlib

        n = self._pair_counts.pop((q, c), 0)
        self._pair_counts[(q, c)] = n + 1  # reinsert = move to LRU tail
        if len(self._pair_counts) > self.max_pairs:
            self._pair_counts.pop(next(iter(self._pair_counts)))
        ent = zlib.crc32(f"{q}\x00{c}".encode())
        base = (ent << 32) ^ (n << 8) ^ (self.seed & 0xFF)
        same = self.world.same_intent(q, c)
        correct = self._u01(base, 1) < self.acc
        positive = same if correct else not same
        # Beta(1, b) via inverse CDF: x = 1 - (1-u)^(1/b)
        u = self._u01(base, 2)
        if positive:
            # P(score < 0.9) ≈ 0.04 — a few true matches fall below
            # τ_lsm=0.9; with capacity/TTL misses this lands at the
            # paper's ~85-88% steady-state hit rates
            return (1.0 - u) ** (1.0 / 30.0)
        return 1.0 - (1.0 - u) ** (1.0 / 19.0)

    def score_pairs(
        self, queries: Sequence[str], cached_keys: Sequence[str]
    ) -> np.ndarray:
        """S_lsm per (query, cached) pair."""
        out = np.empty(len(queries), np.float32)
        for i, (q, c) in enumerate(zip(queries, cached_keys)):
            out[i] = self._pair_score(q, c)
        return out

    def staticity(self, query: str) -> int:
        return self.world.staticity(query)


PAIR_BUCKETS = (1, 2, 4, 8, 16, 32)  # padded pair counts: one jit each


class ModelJudge:
    """Cross-encoder: prefill-only classification (one score per pair).

    Runs any registered config — the qwen3-0.6b judge at its published
    widths, or a shrink of it. ``score_pairs`` pads the pair count up to
    the next of :data:`PAIR_BUCKETS` (longer lists go in chunks of the
    largest), so the jitted prefill compiles at most once per bucket;
    ``shapes_compiled`` records the buckets used, ``pairs_scored`` the
    real pairs and ``pairs_padded`` the pad pairs the buckets added."""

    def __init__(self, cfg=None, max_len: int = 128, seed: int = 1):
        import jax
        import jax.numpy as jnp

        from repro.configs import get_config, shrink
        from repro.core.embedder import byte_tokens
        from repro.models.lm import LM
        from repro.nn.param import init_tree
        from repro.nn.sharding import ShardCtx

        cfg = cfg or shrink(get_config("qwen3-0.6b"), d_model=128, vocab=512,
                            n_repeat=2)
        self.cfg = cfg
        self.max_len = max_len
        self._byte_tokens = byte_tokens
        self.lm = LM(cfg)
        self.ctx = ShardCtx(None)
        key = jax.random.PRNGKey(seed)
        self.params = init_tree(key, self.lm.param_specs())

        def score(params, tokens):
            x = self.lm._embed(self.ctx, params, tokens)
            pos = self.lm._positions(tokens)
            x, _, _ = self.lm._run_stack(self.ctx, params, x, pos)
            # single-token classification readout (prefill-only profile)
            logit = jnp.mean(x[:, -1, :].astype(jnp.float32), axis=-1)
            return jax.nn.sigmoid(logit)

        self._score = jax.jit(score)
        self.shapes_compiled: set[int] = set()
        self.pairs_scored = 0
        self.pairs_padded = 0
        self.tracer = NULL_TRACER

    def bind_tracer(self, tracer) -> None:
        """Draw ``judge.*`` wall spans into ``tracer`` (DESIGN.md §15)."""
        self.tracer = tracer

    def score_pairs(self, queries, cached_keys) -> np.ndarray:
        tr = self.tracer
        with tr.wall("judge.score_pairs"):
            with tr.wall("judge.tokenize"):
                toks = np.array([
                    self._byte_tokens(f"{q} [SEP] {c}", self.max_len)
                    for q, c in zip(queries, cached_keys)
                ], np.int32).reshape(-1, self.max_len) \
                    % self.cfg.vocab_size
                chunks = []
                top = PAIR_BUCKETS[-1]
                for off in range(0, len(toks), top):
                    chunk = toks[off:off + top]
                    m = len(chunk)
                    size = next(b for b in PAIR_BUCKETS if b >= m)
                    self.shapes_compiled.add(size)
                    padded = np.zeros((size, self.max_len), np.int32)
                    padded[:m] = chunk
                    chunks.append((m, padded))
                    self.pairs_scored += m
                    self.pairs_padded += size - m
            out = []
            for m, padded in chunks:
                with tr.wall("judge.prefill"):
                    out.append(
                        np.asarray(self._score(self.params, padded))[:m])
            return np.concatenate(out).astype(np.float32) if out \
                else np.zeros(0, np.float32)

    def staticity(self, query: str) -> int:
        # stable across processes (Python's hash() is salted per run,
        # which made admission TTLs irreproducible)
        import zlib

        return 1 + (zlib.crc32(query.encode()) % 10)


class HybridJudge:
    """Oracle decisions + model compute (used by e2e benchmarks so both the
    semantics AND the measured judge cost are faithful).

    Kept for back-compat; ``core/judge_pipeline.JudgePipeline(oracle,
    compute=model)`` is the same shim plus admission and cost derivation,
    and is what the serving stack threads through."""

    def __init__(self, oracle: OracleJudge, model: Optional[ModelJudge] = None):
        self.oracle = oracle
        self.model = model

    def score_pairs(self, queries, cached_keys) -> np.ndarray:
        if self.model is not None:
            self.model.score_pairs(queries, cached_keys)  # pay the compute
        return self.oracle.score_pairs(queries, cached_keys)

    def staticity(self, query: str) -> int:
        return self.oracle.staticity(query)
