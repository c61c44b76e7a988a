"""Generator ``swe``: SWE-bench-style code-file reads in Poisson tasks
(``traffic/swe.json``)."""
from __future__ import annotations

import numpy as np

from traffic.gen.mix import instants
from traffic.gen.workloads import swe_workload
from traffic.gen.world import SemanticWorld


def generate(spec: dict, world: SemanticWorld, seed: int, seconds: float):
    """SWE-bench-style file accesses, one lookup per access; tasks (each
    a burst of 3-8 file reads) arrive Poisson. The cache starts with no
    file entry: the warm-up tasks admit them. The window holds whole
    tasks, the first ones past ``warmup_tasks`` up to the rate's count."""
    pop = int(spec["population_seed"])
    target = int(round(spec["rate_per_s"] * seconds))
    n_warm = int(spec["warmup_tasks"])
    pool = n_warm + target          # more tasks than the window can use
    reqs = swe_workload(world, pool, files_per_task=tuple(
        spec["files_per_task"]), n_paraphrases=spec["n_paraphrases"],
        tail_files=spec["tail_files"], seed=pop)
    tasks: dict = {}
    for r in reqs:
        tasks.setdefault(r.session, []).append(r)
    tasks = [sorted(v, key=lambda r: r.arrival) for _, v in
             sorted(tasks.items())]
    warm = [r.query for t in tasks[:n_warm] for r in t]
    body, count = [], 0
    for t in tasks[n_warm:]:
        if count >= target:
            break
        body.append([(r.arrival - t[0].arrival, r.query) for r in t])
        count += len(t)
    m = len(body)
    starts = instants(pop, m, seconds)
    order = np.random.default_rng(seed).permutation(m)
    # a task running past the close wraps to the window's start, so every
    # seed offers the same lookups
    window = sorted((float((s + off) % seconds), q)
                    for s, i in zip(starts, order) for off, q in body[int(i)])
    return [], warm, window
