"""Generator ``zipf``: Zipf(s) search lookups over the world's intents
(``traffic/zipf.json``)."""
from __future__ import annotations

import numpy as np

from traffic.gen.mix import instants
from traffic.gen.workloads import zipf_workload
from traffic.gen.world import SemanticWorld


def generate(spec: dict, world: SemanticWorld, seed: int, seconds: float):
    """Zipf(s) over the world's intents, one lookup per arrival; the most
    popular ``prefill_intents`` intents start in the cache."""
    pop = int(spec["population_seed"])
    n = int(round(spec["rate_per_s"] * seconds))
    n_warm = int(spec["warmup_lookups"])
    reqs = zipf_workload(world, n_warm + n, zipf_s=spec["zipf_s"],
                         n_paraphrases=spec["n_paraphrases"], n_rounds=1,
                         seed=pop)
    # zipf_workload's first draw maps popularity rank -> intent
    by_rank = np.random.default_rng(pop).permutation(world.n_intents)
    paras = np.random.default_rng(pop + 1).integers(
        0, spec["n_paraphrases"], spec["prefill_intents"])
    prefill = [world.query(int(i), int(p))
               for i, p in zip(by_rank[:spec["prefill_intents"]], paras)]
    queries = [r.query for r in reqs]
    due = instants(pop, n, seconds)
    order = np.random.default_rng(seed).permutation(n)
    window = [(float(t), queries[n_warm + int(i)])
              for t, i in zip(due, order)]
    return prefill, queries[:n_warm], window
