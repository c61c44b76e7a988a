#!/usr/bin/env python3
"""The program's own wall spans and transfer counters over a cell's window:
one ``Tracer`` bound to the served system's cache, embedder and judge
model (``bind_tracer``), read as the window's share.

    python3 benchmarks/chip/program_spans.py --workload <cell> \\
        --seconds <s> --seeds 1,2 [--profile]

Each seed is a whole run as ``run.py`` makes it (set-up, window,
reference, ``correct``), with the tracer bound from the start; one JSON
line per seed on stdout: the run's own result, and under ``program``

- ``spans``: per program span name, ``n``, ``total_s`` and ``self_s``
  over the window (``obs.analyze.wall_breakdown``);
- ``passes``, ``h2d_bytes``, ``pairs_scored``, ``pairs_padded``: the
  window's stage-1 passes and counter deltas;
- with ``--profile`` (the run's ``--trace 1``): ``idle_self_s``, device
  idle seconds in each program span's own time by exact interval
  intersection, ``idle_outside_s`` the idle outside every program span,
  and ``idle_s`` / ``window_s``;
- the readings ``stage1_h2d_ms`` (``seri.h2d`` seconds per pass),
  ``stage1_h2d_mb`` (``ScanMetrics.h2d_bytes`` per pass / 1e6),
  ``stage1_h2d_idle_share`` (idle in ``seri.h2d`` over the window, %,
  with ``--profile``) and ``judge_pad_share`` (pad pairs over all pairs
  the judge prefilled, %).

A bound tracer changes the pass it times: ``seri.h2d`` waits for the
copy to land before the kernel is dispatched, which the unbound pass
overlaps. The run's own numbers (its end-to-end metrics, or under
``--profile`` its per-layer metrics: ``stage1_ms``,
``device_idle_share`` and ``lookup_mfu`` most) are those of the bound
program; the benchmark's own runs never bind it.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import gc
import json
import sys
import time

import run
import served
import tracing


def charge_idle(idle, spans) -> tuple[dict, float]:
    """Idle seconds in each span name's own time (its time less its
    children's), and the idle outside every span. ``idle`` is sorted
    disjoint [start, end) stretches; ``spans`` (start, end, name) nest
    properly, as one host thread's annotations do."""
    starts = [s for s, _ in idle]

    def inside(s, e):
        t, i = 0.0, max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(idle) and idle[i][0] < e:
            t += max(0.0, min(e, idle[i][1]) - max(s, idle[i][0]))
            i += 1
        return t

    spans = sorted(spans, key=lambda p: (p[0], -p[1]))
    tot = [inside(s, e) for s, e, _ in spans]
    own = list(tot)
    stack: list = []
    roots = 0.0
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= tot[i]
        else:
            roots += tot[i]
        stack.append(i)
    out: dict = {}
    for (_, _, name), t in zip(spans, own):
        out[name] = out.get(name, 0.0) + t
    return out, sum(e - s for s, e in idle) - roots


def idle_by_program_span(pd, names) -> dict:
    """Device idle of the benchmark's ``window`` span charged to the
    program's host annotations named in ``names`` (same xplane, same
    clock)."""
    dev, host = tracing.read_events(pd)
    (lo, hi), = [(s, e) for s, e, nm in host if nm == "window"]
    busy: list = []
    for ops in dev.values():
        busy.extend(tracing.clip([(s, e) for s, e, _, _ in ops], lo, hi))
    idle = tracing.gaps(tracing.union(busy), lo, hi)
    prog = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in names:
                        s = ev.start_ns * 1e-9
                        e = s + ev.duration_ns * 1e-9
                        if e > lo and s < hi:
                            prog.append((max(s, lo), min(e, hi), ev.name))
    own, outside = charge_idle(idle, prog)
    return dict(idle_self_s=own, idle_outside_s=outside,
                idle_s=tracing.total(idle), window_s=hi - lo)


def readings(prog: dict) -> dict:
    """The four per-pass and per-pair readings, where there is something
    to read."""
    out = {}
    n, sp = prog["passes"], prog["spans"]
    if n:
        out["stage1_h2d_mb"] = prog["h2d_bytes"] / 1e6 / n
        if "seri.h2d" in sp:
            out["stage1_h2d_ms"] = 1e3 * sp["seri.h2d"]["total_s"] / n
    if "idle_self_s" in prog and "seri.h2d" in sp:
        out["stage1_h2d_idle_share"] = 100.0 * prog["idle_self_s"].get(
            "seri.h2d", 0.0) / prog["window_s"]
    pairs = prog["pairs_scored"] + prog["pairs_padded"]
    if pairs:
        out["judge_pad_share"] = 100.0 * prog["pairs_padded"] / pairs
    return out


@contextlib.contextmanager
def bound(into: dict):
    """While open, ``run.set_up`` builds a served system with one tracer
    bound, its window fills ``into``, and the profile ``run.run`` loads
    is kept in ``into["profile"]``."""
    from repro.obs.analyze import wall_breakdown
    from repro.obs.trace import Tracer

    base, load = served.Served, tracing.load

    class Bound(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.tracer = Tracer()
            self.cache.bind_tracer(self.tracer)
            self.embedder.bind_tracer(self.tracer)
            # the cache binds its pipeline's compute, the benchmark's
            # TimedJudge, which hides the model
            self.judge.model.bind_tracer(self.tracer)

        def window(self, seconds):
            tr, jm, sc = self.tracer, self.judge.model, self.cache.scan
            s0, h0 = len(tr.wall_spans), sc.h2d_bytes
            p0, d0 = jm.pairs_scored, jm.pairs_padded
            out = super().window(seconds)
            into.update(
                spans=wall_breakdown(tr.wall_spans, s0),
                passes=len(self.log.passes), h2d_bytes=sc.h2d_bytes - h0,
                pairs_scored=jm.pairs_scored - p0,
                pairs_padded=jm.pairs_padded - d0)
            return out

    def keep(path):
        into["profile"] = load(path)
        return into["profile"]

    served.Served, tracing.load = Bound, keep
    try:
        yield
    finally:
        served.Served, tracing.load = base, load


def measure(workload: str, seed: int, seconds: float, profile: bool, *,
            root: str = run.ROOT, chips: bool = True) -> dict:
    """One run of ``workload`` with the program's tracer bound."""
    prog: dict = {}
    with bound(prog):
        out = run.run(workload, seed, seconds, profile, root=root,
                      chips=chips, t_start=time.time())
    pd = prog.pop("profile", None)
    if pd is not None:
        prog.update(idle_by_program_span(pd, set(prog["spans"])))
    prog.update(readings(prog))
    out["program"] = prog
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--profile", action="store_true")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = measure(args.workload, seed, args.seconds, args.profile)
        out["seed"] = seed
        print(json.dumps(out), flush=True)
        del out
        gc.collect()      # the index's wrapped methods hold cycles
    return 0


if __name__ == "__main__":
    sys.exit(main())
