"""The one mix builder every traffic file is read by.

A traffic file (``traffic/<mix>.json``) names a ``generator`` and its
parameters, a fixed offered rate ``rate_per_s`` (lookups per second),
and a ``population_seed``. The generator is found by name: the file
``traffic/gen/<generator>.py`` beside this one, whose function
``generate(spec, world, seed, seconds)`` returns the prefilled queries,
the warm-up stream and the window's (due_s, query) list. A new generator
is a new file there, never an edit.

The population seed fixes WHAT is asked: the world (its intents'
embeddings, staticities, value sizes and tool prices, which decide how
often a lookup misses and fetches), the multiset of queries, the gaps
between arrivals, the prefilled entries and the untimed warm-up stream.
The run's ``--seed`` fixes the ORDER: it permutes the window's queries
(whole tasks, for the coding mix). So every seed offers the same amount
and kind of work, and runs with different seeds differ only as
different days of the same traffic would.

Arrivals are open-loop: the window's N = round(rate * seconds) arrivals
are the points of a Poisson process on [0, seconds) conditioned on N.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import re

import numpy as np

from traffic.gen.world import SemanticWorld

_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


@dataclasses.dataclass
class Mix:
    world: SemanticWorld
    prefill: list          # queries admitted in bulk before the warm-up
    warmup: list           # queries replayed untimed, in order
    window: list           # (due_s, query), due ascending, all < seconds
    remote: dict           # RemoteDataService arguments


def instants(pop: int, n: int, seconds: float) -> np.ndarray:
    """n Poisson arrival instants on [0, seconds), conditioned on n."""
    return np.sort(np.random.default_rng(pop + 2).uniform(0.0, seconds, n))


def generator(name: str, where: str = os.path.dirname(__file__)):
    """The ``generate`` function of ``<where>/<name>.py``, by default
    ``traffic/gen/<name>.py`` beside this file."""
    if not _NAME.match(name):
        raise ValueError(f"bad generator name {name!r}")
    spec = importlib.util.spec_from_file_location(
        "traffic_generator_" + name, os.path.join(where, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


def build(spec: dict, *, dim: int, seed: int, seconds: float,
          generate=None) -> Mix:
    """The traffic of one run: ``spec`` is a traffic file's contents,
    ``generate`` its generator (found by its name when not given)."""
    world = SemanticWorld(n_intents=int(spec["world"]["n_intents"]),
                          dim=dim, seed=int(spec["population_seed"]))
    generate = generate or generator(spec["generator"])
    prefill, warmup, window = generate(spec, world, seed, seconds)
    return Mix(world, prefill, warmup, window, dict(spec["remote"]))
