"""Operations and bytes that the served path's work needs, from shapes.

These are the yardstick for ``scan_roofline``, ``judge_roofline`` and
``lookup_mfu``: they count what the algorithm requires for the shapes a
run actually asked for, never what a particular implementation happens
to do (padding rows or recomputing a causal mask's upper half count for
nothing). A model's counts are its family file's (``models/``:
``forward_flops``, ``weight_bytes``); the stage-1 scans' are here.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def ann_topk(B: int, N: int, D: int, itemsize: int = 4) -> tuple:
    """Brute scan: every row read once, each scored against B queries.
    Returns (flops, bytes)."""
    return 2.0 * B * N * D, float(N * D * itemsize + N * 4 + B * D * 4)


def ann_topk_ivf(B: int, nprobe: int, cap: int, D: int,
                 itemsize: int = 4) -> tuple:
    """Routed scan: each query reads its nprobe buckets of cap rows (and
    their valid masks). Returns (flops, bytes)."""
    rows = B * nprobe * cap
    return 2.0 * rows * D, float(rows * (D * itemsize + 4) + B * D * 4)


def ann_topk_quant(B: int, N: int, D: int) -> tuple:
    """int8 brute coarse scan: int8 rows, a float32 scale and an active
    flag per row. Returns (int8 ops, bytes)."""
    return 2.0 * B * N * D, float(N * (D + 4 + 4) + B * (D + 4))


def ann_topk_ivf_quant(B: int, nprobe: int, cap: int, D: int) -> tuple:
    """int8 routed coarse scan. Returns (int8 ops, bytes)."""
    rows = B * nprobe * cap
    return 2.0 * rows * D, float(rows * (D + 4 + 4) + B * (D + 4))


def scan_work(shape: dict) -> tuple:
    """(flops, bytes, int8) of one logged stage-1 pass."""
    k = shape["kind"]
    if k == "ann_topk":
        return (*ann_topk(shape["B"], shape["N"], shape["D"],
                          shape["itemsize"]), False)
    if k == "ann_topk_ivf":
        return (*ann_topk_ivf(shape["B"], shape["nprobe"], shape["cap"],
                              shape["D"], shape["itemsize"]), False)
    raise ValueError(f"no count for scan kind {k!r}")


def least_time(flops: float, nbytes: float, peak: dict,
               int8: bool = False) -> tuple:
    """(seconds, bound): the larger of compute time at the chip's peak
    (bf16, or int8 for int8 work; fp32 work is charged at the bf16 peak,
    which no fp32 pass can beat) and HBM time at its bandwidth."""
    t_c = flops / (peak["int8_ops_per_s"] if int8
                   else peak["bf16_flops_per_s"])
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "memory")
