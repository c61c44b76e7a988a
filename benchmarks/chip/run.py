#!/usr/bin/env python3
"""Chip benchmark of the Cortex lookup path: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the root of the checkout: its configuration file (``configs/``), its
traffic file (``traffic/<traffic>.json``) and the generator it names
(``traffic/gen/<generator>.py``, driven by ``traffic/gen/mix.py``), the
family file of its judge's and its embedder's ``model_type``
(``models/<model_type>.py``, see ``model_ref.py``) and, in a traced run,
one reader per per-layer metric (``layer_metrics/<metric>.py``). A new
cell, configuration, architecture, mix or metric is new files and new
entries, never an edit.

A run: make the inputs and weights from ``--seed``; fill the index; warm
every shape the window uses and replay the traffic's head (all of that is
``setup_s``); serve the window open-loop for ``--seconds``; read the peak
device memory; then free the program's state and compare what the window
produced with the plain reference (``check.py``). The last stdout line is
one JSON object; the numbers compared, each beside its limit, are the
last stderr lines and the result's last key. With ``--trace 1`` the
window runs under the profiler and the metrics are the per-layer ones.

The run needs the chips the cell asks for: on anything but a TPU, or
with too few chips, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import check  # noqa: E402
import counts  # noqa: E402
import model_ref  # noqa: E402
import served  # noqa: E402
import tracing  # noqa: E402
from traffic.gen import mix as traffic_mix  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# an unanswered lookup is infinitely late; JSON has no infinity
UNANSWERED_MS = 1e12


# -------------------------------------------------------------- the cell

def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` and everything it is made of, found by name."""
    chip = os.path.join(root, os.path.relpath(HERE, ROOT))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(chip, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    readers = {}
    for m in bench["per_layer"]:
        if mine(m):
            path = os.path.join(chip, "layer_metrics", m["name"] + ".py")
            spec = importlib.util.spec_from_file_location(
                "layer_metric_" + m["name"].replace(".", "_")
                .replace("-", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            readers[m["name"]] = (m, mod.read)
    generate = traffic_mix.generator(traffic["generator"],
                                     os.path.join(chip, "traffic", "gen"))
    models = model_ref.served_models(cfg, os.path.join(chip, "models"))
    return dict(cell=cell, cfg=cfg, traffic=traffic, generate=generate,
                models=models,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=readers)


def require_chips(n: int) -> dict:
    """The device stamp; no TPU or too few chips ends the run."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        log(f"this benchmark runs on a TPU; JAX found {dev}")
        raise SystemExit(2)
    if dev["count"] < n:
        log(f"this cell needs {n} chips; JAX found {dev}")
        raise SystemExit(2)
    return dev


class CompileCounter:
    """Compilations (tracing, lowering, backend compiles) while ``on``."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, *_args, **_kw):
        if self.on and name.startswith("/jax/core/compile/"):
            self.n += 1


# ------------------------------------------------------------------ run

def set_up(c: dict, seed: int, seconds: float, *, trace: bool = False,
           t_start: float = T_START):
    """Everything before the window, all of it ``setup_s``: the compile
    cache, the traffic, the background rows, the models, the fill and
    the warm-up. Returns the warmed system, its spans and the set-up's
    marks (seconds since ``t_start``)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    marks = {}

    def mark(what):
        marks[what] = time.time() - t_start

    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dep = c["cfg"]["deployment"]
    mix = traffic_mix.build(c["traffic"], dim=dep["index"]["dim"],
                            seed=seed, seconds=seconds,
                            generate=c["generate"])
    mark("traffic")
    n_bg = dep["index"]["rows"] - len(mix.prefill)
    bg = served.background_rows(n_bg, dep["index"]["dim"], seed + 3)
    mark("background rows")
    spans = served.Spans(annotate=trace)
    sut = served.Served(c["cfg"], c["models"], mix, seed, spans)
    mark("models")
    sut.fill(bg)
    del bg
    mark("fill")
    sut.warm()
    mark("warm-up")
    return sut, spans, marks


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, control: bool = False, chips: bool = True,
        t_start: float = T_START) -> dict:
    """One run of cell ``name``; returns the result object. ``chips``
    False skips the look for a TPU (for tests of the rest of a run).
    ``control`` puts the reference, one precision step down, in the
    program's place in the comparison (the control, never the
    benchmark's own runs): ``correct`` and ``compared`` are then the
    control's, and ``program`` holds the program's own verdict and
    numbers from the same window."""
    import jax

    c = load_cell(name, root)
    cfg, cell, dep = c["cfg"], c["cell"], c["cfg"]["deployment"]
    dev = require_chips(cell["chips"]) if chips else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": jax.device_count()}
    counter = CompileCounter()
    sut, spans, marks = set_up(c, seed, seconds, trace=trace,
                               t_start=t_start)
    mix = sut.mix
    ix = sut.index
    snap = dict(emb=ix.emb.copy(), active=ix.active.copy(),
                row_se=ix.row_se.copy())
    rt = ix.router
    router = None
    if rt is not None:
        router = dict(centroids=rt.centroids.copy(), live=rt.counts > 0,
                      nprobe=rt.cfg.n_clusters if rt.cfg.nprobe is None
                      else min(rt.cfg.nprobe, rt.cfg.n_clusters),
                      refreshes=rt.refreshes)
    stats0 = sut.cache.seri.pipeline.stats
    pairs0, batches0 = stats0.judged_pairs, stats0.judge_batches
    n_spans0, n_judge0 = len(spans.rows), len(sut.judge.calls)
    setup_s = time.time() - t_start
    marks["snapshot"] = setup_s
    log("set-up: " + ", ".join(f"{k} at {v:.1f} s" for k, v in marks.items()))

    tdir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(tdir.name)
    counter.on = True
    win = sut.window(seconds)
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    dev["memory_peak_bytes"] = int(peak)

    # --- end-to-end
    n = len(win["due"])
    lat = (win["decided"] - win["due"]) * 1e3
    answered = np.isfinite(lat)
    lat = np.where(answered, lat, UNANSWERED_MS)
    t_close = max(seconds, float(np.nanmax(win["decided"]))
                  if answered.any() else seconds)
    e2e = {
        "lookup_p50_ms": float(np.percentile(lat, 50)) if n else None,
        "lookup_p95_ms": float(np.percentile(lat, 95)) if n else None,
        "lookups_per_s": float(answered.sum()) / t_close,
        "fetches_per_lookup": win["fetches"] / n if n else None,
        "setup_s": setup_s,
    }
    late = win["lateness"]
    log(f"window: {n} lookups due in {seconds:.0f} s, {int(answered.sum())}"
        f" answered, {win['fetches']} fetches, closed at {t_close:.2f} s; "
        f"micro-batches {int(win['batch_of'].max()) + 1 if n else 0}; "
        f"stage-1 passes {len(sut.log.passes)}; generator wake-ups "
        f"{len(late)}, late by p50 "
        f"{1e3 * float(np.median(late)) if len(late) else 0:.3f} ms, max "
        f"{1e3 * float(late.max()) if len(late) else 0:.3f} ms")

    # --- per-layer
    stats = sut.cache.seri.pipeline.stats
    wspans = spans.rows[n_spans0:]
    judge_calls = sut.judge.calls[n_judge0:]
    run_rec = dict(
        spans=wspans, cfg=cfg, passes=[p["shape"] for p in sut.log.passes],
        judge=c["models"]["judge"], embedder=c["models"]["embedder"],
        embedded=n_embedded(sut.embeds),
        judge_calls=[len(q) for q, _, _ in judge_calls],
        judged_pairs=stats.judged_pairs - pairs0,
        judge_batches=stats.judge_batches - batches0,
        peaks=counts.peaks(dev["kind"]) if dev["platform"] == "tpu"
        else None, trace=None)
    run_rec["span_s"] = lambda nm: float(sum(
        e - s for x, s, e in wspans if x == nm))
    result_extra = {}
    if trace:
        summ = tracing.summarize(*tracing.read_events(
            tracing.load(tdir.name)))
        tdir.cleanup()
        run_rec["trace"] = summ
        dev["busy_s"], dev["window_s"] = summ["busy_s"], summ["window_s"]
        result_extra["breakdown"] = tracing.breakdown(summ)

    # --- correctness: program-side numbers first, then free the state
    lost = check.inserts_lost(ix, sut.cache.rows, sut.inserted,
                              sut.log.muts)
    if router is not None and rt.refreshes != router["refreshes"]:
        raise RuntimeError("the router refreshed inside the window: raise "
                           "refresh_every in the configuration")
    dec_bad = check.decisions(mix.world, dep, sut.decisions.calls, win)
    ilog, embeds = sut.log, sut.embeds
    judge_w, embed_w = sut.judge_w, sut.embed_w
    sut.cache = sut.index = ix = None
    t_ref = time.time()
    rng = np.random.default_rng(seed + 7)
    s1, s1_n = check.stage1(snap, ilog, router, control)
    del snap
    emb = check.embed_gap(c["models"]["embedder"], dep["embed_max_len"],
                          embed_w, embeds, rng, control)
    jud = check.judge_gap(c["models"]["judge"], dep["judge_max_len"],
                          judge_w, judge_calls, rng, control)
    limits = dict(cfg["limits"], stage1_rows=0, decisions=0,
                  inserts_lost=0, compiles=0)

    def numbers(side):
        return {"stage1_rows": s1[side][0], "stage1_dsim": s1[side][1],
                "embed_gap": emb[side], "judge_gap": jud[side],
                "decisions": dec_bad, "inserts_lost": lost,
                "compiles": counter.n}

    correct, compared = check.verdict(numbers(0), limits)
    if control:
        program = dict(correct=bool(correct), compared=compared)
        correct, compared = check.verdict(numbers(1), limits)
        program["stage1_default"] = dict(zip(("stage1_rows",
                                              "stage1_dsim"), s1[2]))
    log(f"reference: {s1_n} stage-1 queries over {len(ilog.passes)} "
        f"passes, {min(check.SAMPLE, n_embedded(embeds))} embeddings, "
        f"{min(check.SAMPLE, sum(len(q) for q, _, _ in judge_calls))} "
        f"judge pairs, {time.time() - t_ref:.1f} s")

    if trace:
        metrics = {}
        for mname, (m, read) in c["per_layer"].items():
            v = read(run_rec)
            if v is not None:
                metrics[mname] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"] if e2e[m["name"]] is not None}
    out = {"correct": bool(correct), "attempted": n,
           "failed": int(n - answered.sum()), "metrics": metrics,
           "device": dev}
    out.update(result_extra)
    if control:
        out["program"] = program
    out["compared"] = compared
    return out


def n_embedded(embeds) -> int:
    return sum(len(t) for t, _ in embeds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for k, v in out["compared"].items():
        log(f"compared {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
