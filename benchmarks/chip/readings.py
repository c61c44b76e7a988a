#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the numbers compared, per seed,
for the program or for its control, several seeds in one process.

    python3 benchmarks/chip/readings.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control]

Each seed is a whole run (set-up, window, reference), as ``run.py`` makes
it; one JSON line per seed on stdout. ``--control`` also puts the reference,
one precision step down, in the program's place in the same window: its
numbers (``control``) must come out not correct, beside the program's
own (``program``). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def values(compared: dict) -> dict:
    return {k: v["value"] for k, v in compared.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run(args.workload, seed, args.seconds, False,
                      control=args.control, t_start=time.time())
        prog = out.get("program", out)
        line = {"seed": seed, "correct": prog["correct"],
                "program": values(prog["compared"]),
                "metrics": {k: v["value"] for k, v in
                            out["metrics"].items()}}
        if args.control:
            line.update(control_correct=out["correct"],
                        control=values(out["compared"]),
                        stage1_default=prog["stage1_default"])
        print(json.dumps(line), flush=True)
        del out
        gc.collect()      # the index's wrapped methods hold cycles
    return 0


if __name__ == "__main__":
    sys.exit(main())
