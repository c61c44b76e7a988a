#!/usr/bin/env python3
"""Find a cell's knee once: one set-up, then a window at each offered
rate in turn, on the same warmed system.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 2,4,8

One JSON line per rate: lookups due and answered, p50/p95 latency, and
how long past the close the last answer came (a backlog that grows
through the window shows as a drain that grows with the rate). The
cell's traffic file then fixes its rate at about 0.8 of the knee; the
benchmark itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    t0 = time.time()
    c = run.load_cell(args.workload)
    run.require_chips(c["cell"]["chips"])
    sut = run.set_up(c, args.seed, args.seconds, t_start=t0)[0]
    print(json.dumps({"setup_s": time.time() - t0}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        spec = dict(c["traffic"], rate_per_s=rate)
        sut.mix.window = c["generate"](spec, sut.world, args.seed, args.seconds)[2]
        win = sut.window(args.seconds)
        lat = (win["decided"] - win["due"]) * 1e3
        ok = np.isfinite(lat)
        print(json.dumps({
            "rate": rate, "due": len(lat), "answered": int(ok.sum()),
            "p50_ms": float(np.percentile(lat[ok], 50)),
            "p95_ms": float(np.percentile(lat[ok], 95)),
            "drain_s": float(np.nanmax(win["decided"])) - args.seconds,
            "batches": int(win["batch_of"].max()) + 1,
            "fetches": win["fetches"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
