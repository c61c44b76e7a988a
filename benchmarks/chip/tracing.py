"""Reduction of a JAX profiler trace to the numbers a traced run reports.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. Device planes
are ``/device:TPU:<n>``; their op events (line ``XLA Ops`` where the
plane has one) are what ran on the chip. The benchmark's spans are
``TraceAnnotation`` events on the host planes, on the same clock.

``summarize`` keeps only what the readers need: the traced window (the
benchmark's ``window`` span), the union of device-op intervals inside it
averaged over the chips, device time per "module:op" (innermost ops
only), which of those are Pallas kernels, and each idle stretch charged
to the innermost benchmark span the host was in during it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE = re.compile(r"^/device:TPU:\d+$")
SPANS = ("window", "batch", "embed", "lookup", "stage1", "judge", "insert")


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy, lo: float, hi: float) -> list:
    """Idle [start, end) stretches of [lo, hi) between busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, s])
        t = max(t, e)
    if t < hi:
        out.append([t, hi])
    return out


def load(trace_dir: str):
    """The one xplane file a traced window wrote."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file, found {paths}")
    return ProfileData.from_file(paths[0])


def _op(ev_name: str) -> tuple:
    """(op name, is a Pallas kernel) of an ``XLA Ops`` event, whose name
    is the HLO instruction: ``%name = type op(...), ...``."""
    return ev_name.split(" = ")[0].lstrip("%"), \
        'custom_call_target="tpu_custom_call"' in ev_name


def read_events(pd):
    """(device ops per chip, host spans): device ops are (start_s, end_s,
    "module:op", is_kernel), the module being the XLA module (jitted
    function) the op ran in; host spans are (start_s, end_s, name)."""
    dev: dict = {}
    host: list = []
    for plane in pd.planes:
        if DEVICE.match(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(")[0])
                          for ev in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            ops = dev.setdefault(plane.name, [])
            for ev in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = mods[i][2] if i >= 0 and ev.start_ns < mods[i][1] \
                    else "?"
                name, kern = _op(ev.name)
                s = ev.start_ns * 1e-9
                ops.append((s, s + ev.duration_ns * 1e-9,
                            f"{mod}:{name}", kern))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in SPANS:
                        s = ev.start_ns * 1e-9
                        host.append((s, s + ev.duration_ns * 1e-9,
                                     ev.name))
    return dev, host


def leaves(ops) -> list:
    """The ops that hold no other op: a while loop's events enclose the
    ops of its body, which would otherwise count twice."""
    ops = sorted(ops)
    return [o for i, o in enumerate(ops)
            if not (i + 1 < len(ops) and ops[i + 1][0] < o[1]
                    and ops[i + 1][1] <= o[1])]


def summarize(dev: dict, host: list) -> dict:
    """The traced window's numbers; see the module docstring."""
    win = [h for h in host if h[2] == "window"]
    if len(win) != 1 or not dev:
        raise RuntimeError("the trace holds no window span or no device "
                           f"plane (window spans {len(win)}, devices "
                           f"{sorted(dev)})")
    lo, hi = win[0][0], win[0][1]
    busy_per_chip, op_s, kernels = [], defaultdict(float), set()
    merged: list = []
    for ops in dev.values():
        inside = [o for o in ops if o[1] > lo and o[0] < hi]
        b = union(clip([(o[0], o[1]) for o in inside], lo, hi))
        busy_per_chip.append(total(b))
        merged.extend(b)
        for s, e, name, kern in leaves(inside):
            op_s[name] += min(e, hi) - max(s, lo)
            if kern:
                kernels.add(name)
    # idle: no chip busy; each stretch goes to the innermost span open
    # at its middle (or "no span" between the benchmark's calls)
    idle = gaps(union(merged), lo, hi)
    inner = sorted((h for h in host if h[2] != "window"),
                   key=lambda h: h[1] - h[0])
    idle_by: dict = defaultdict(float)
    for s, e in idle:
        mid = 0.5 * (s + e)
        who = next((h[2] for h in inner if h[0] <= mid < h[1]), "no span")
        idle_by[who] += e - s
    return dict(window_s=hi - lo,
                busy_s=sum(busy_per_chip) / len(busy_per_chip),
                op_s=dict(op_s), kernels=sorted(kernels),
                idle_by=dict(idle_by), n_chips=len(dev))


def kernel_seconds(summary: dict, pattern: str) -> float:
    """Summed device time of the Pallas kernels whose "module:op" name
    matches ``pattern``."""
    rx = re.compile(pattern)
    return float(sum(summary["op_s"][k] for k in summary["kernels"]
                     if rx.search(k)))


def op_seconds(summary: dict, pattern: str) -> float:
    """Summed device time of every op, kernel or not, whose "module:op"
    name matches ``pattern``: with ``^<module>:``, what a jitted
    function's calls kept the chip busy."""
    rx = re.compile(pattern)
    return float(sum(s for k, s in summary["op_s"].items() if rx.search(k)))


def breakdown(summary: dict) -> dict:
    """The ten device ops that took most time and the idle time by what
    the host was doing, as ``[[name, seconds], ...]``."""
    ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(summary["idle_by"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
