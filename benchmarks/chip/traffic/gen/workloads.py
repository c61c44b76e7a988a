# Frozen copy of src/repro/data/workloads.py (Request, _zipf_probs,
# zipf_workload, SWE_FILE_FREQ, swe_workload; the rest of that file is left
# out) at commit
# 5e6ac64b82b261ed6acc080e8e0ba073b8b770da, kept with the chip benchmark so
# that the traffic it generates cannot move when the program changes.
# Do not edit: a changed generator is a new file.
"""Workload generators matching the paper's three evaluation families
(§6.1): skewed (Zipf-0.99) search, trend-driven bursty search, and
SWE-bench-style code-file access.

Each generator yields a list of :class:`Request` with arrival times (for
open-loop runs) — the engine can also replay them closed-loop at a fixed
concurrency (Fig 10).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from traffic.gen.world import SemanticWorld


@dataclasses.dataclass
class Request:
    rid: int
    arrival: float
    query: str                 # round-0 tool query
    session: int = 0
    n_rounds: int = 1          # agent think→tool→observe rounds
    round_queries: tuple = ()  # per-round queries (len == n_rounds);
                               # defaults to (query,) — real agents refine
                               # the query each round, so generators fill
                               # this with distinct paraphrases/intents

    def query_for_round(self, r: int) -> str:
        if self.round_queries:
            return self.round_queries[min(r, len(self.round_queries) - 1)]
        return self.query


def _zipf_probs(n: int, s: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-s)
    return w / w.sum()


def zipf_workload(
    world: SemanticWorld,
    n_requests: int,
    *,
    zipf_s: float = 0.99,
    n_paraphrases: int = 100,
    rate: float = 4.0,
    n_rounds: int = 2,
    seed: int = 0,
) -> list[Request]:
    """Skewed search workload: intents drawn Zipf(s), each query a random
    paraphrase — exact-match caches miss on wording changes, semantic
    caches group them (paper Fig 7)."""
    rng = np.random.default_rng(seed)
    probs = _zipf_probs(world.n_intents, zipf_s)
    # shuffle intent ranks so confusable pairs land across the popularity
    # spectrum rather than only in the head
    perm = rng.permutation(world.n_intents)
    out = []
    t = 0.0
    for i in range(n_requests):
        t += rng.exponential(1.0 / rate)
        intent = int(perm[rng.choice(world.n_intents, p=probs)])
        rounds = []
        for r in range(n_rounds):
            # each reasoning round issues a fresh phrasing; ~30% of later
            # rounds drill into a correlated follow-up intent
            it = intent
            if r > 0 and rng.random() < 0.3:
                it = (intent + 1) % world.n_intents
            rounds.append(world.query(it, int(rng.integers(0, n_paraphrases))))
        out.append(
            Request(i, t, rounds[0], session=i, n_rounds=n_rounds,
                    round_queries=tuple(rounds))
        )
    return out


# SWE-bench file-access frequencies for sqlfluff (paper Table 2)
SWE_FILE_FREQ = [1.0, 0.28, 0.22, 0.14, 0.1, 0.08, 0.04, 0.04, 0.04]


def swe_workload(
    world: SemanticWorld,
    n_tasks: int,
    *,
    files_per_task: tuple[int, int] = (3, 8),
    n_paraphrases: int = 6,
    rate: float = 2.0,
    tail_files: int = 60,
    seed: int = 2,
) -> list[Request]:
    """Code-agent workload: each task (GitHub issue) touches a set of repo
    files; hot core files recur across tasks per Table 2, the long tail is
    task-specific. One request per file access; requests of one task share
    a session (prefetcher learns file→file transitions)."""
    rng = np.random.default_rng(seed)
    n_core = len(SWE_FILE_FREQ)
    freqs = np.array(SWE_FILE_FREQ + [0.02] * tail_files)
    probs = freqs / freqs.sum()
    n_files = len(freqs)
    out = []
    t = 0.0
    rid = 0
    for task in range(n_tasks):
        t += rng.exponential(1.0 / rate)
        n_f = int(rng.integers(files_per_task[0], files_per_task[1] + 1))
        # core file 0 is required by nearly all tasks (freq 1.0)
        files = [0] if rng.random() < 0.95 else []
        files += list(
            rng.choice(n_files, size=n_f, replace=False, p=probs)
        )
        dt = 0.0
        for f in dict.fromkeys(files):  # dedupe, keep order
            intent = int(f) % world.n_intents
            para = int(rng.integers(0, n_paraphrases))
            out.append(
                Request(
                    rid, float(t + dt), world.query(intent, para),
                    session=task, n_rounds=1,
                )
            )
            rid += 1
            dt += float(rng.exponential(0.5))
    out.sort(key=lambda r: r.arrival)
    for i, r in enumerate(out):
        r.rid = i
    return out

