"""Request-lifecycle tracing in virtual time, and wall-clock spans of the
served path (DESIGN.md §15).

A :class:`Tracer` keeps two span lists that never mix.

``spans`` are *virtual-time* spans: half-open ``[t0, t1)`` segments of
virtual time attributed to one request (``rid >= 0``) or to background
work (``rid == BACKGROUND``). Spans are emitted by the serving layers at
the instants they already know exactly — the engine's stage-1 flush, the
judge dispatcher, the federation router's response handler, the
freshness manager's refetch — so tracing never pushes clock events, never
draws randomness, and a traced run is bit-identical in virtual time to an
untraced one.

Span taxonomy (request-scoped unless noted):

  ``agent_think`` / ``agent_answer``  accelerator lane time for a think
                                      round / the final answer
  ``stage1_queue_wait``   tool-call arrival -> its stage-1 pass opening
                          (host busy-wait serialization, §12)
  ``stage1_scan``         the pass itself: fixed host cost + RTT to a
                          non-local cache + per-row scan streaming
  ``warm_consult``        extra WARM-tier access latency (§10)
  ``band_bypass``         zero-duration marker: hit served without judge
                          latency (admission-band trust, §14)
  ``judge_queue_wait``    stage-1 resolve -> judge micro-batch submit
                          (the backlog + admission-guardrail wait)
  ``judge_compute``       micro-batch submit -> completion on the judge
                          lane (lane queueing + processor sharing)
  ``peek_rtt``            federation broadcast -> winning response (or
                          the last NAK) (§9)
  ``lease_transfer``      winning response -> transferred value arrival
  ``origin_fetch``        origin WAN fetch incl. rate-limiter wait
  ``refresh``             background: revalidation fetch in flight (§11)
  ``invalidation_drop``   background marker: entry dropped by a
                          change-feed notice (§11)
  ``lease_validate``      background marker: holder-side judge score on
                          an in-band federation lease (§14)

**Conservation law**: for every completed request, its request-scoped
spans — sorted by start time — tile ``[rec.arrival, rec.t_done]``
exactly: the first span starts at the arrival instant, every span ends
where the next begins (float ``==``, no tolerance), and the last ends at
completion. The telescoped sum of the segments is therefore *exactly*
``rec.latency``. :func:`repro.obs.analyze.check_conservation` verifies
this per request; a gap or overlap names the offending boundary.

The disabled path is a zero-allocation no-op: :data:`NULL_TRACER` is a
singleton whose ``span`` is an empty method, and every instrumentation
site either calls it directly (cold paths) or guards a loop with
``tracer.enabled`` (the per-batch hot paths), so an untraced engine does
no per-span work at all.

``wall_spans`` are *wall-clock* spans of the served lookup path, drawn
by the program around its own layer boundaries with ``tracer.wall(name)``
once a tracer is bound (``CortexCache.bind_tracer``,
``ModelEmbedder.bind_tracer``). Each is a :class:`WallSpan`: its name,
start and end on ``time.perf_counter``, the index of its parent (the
innermost wall span open when it started, -1 for none) and a call id
shared by every span under one root span (one call into the cache or
the embedder). Each also opens a ``jax.profiler.TraceAnnotation`` of the
same name, so under the profiler its host event shares the device
trace's clock and device idle time can be charged to it. Wall span
names (``cache.lookup_batch``, ``seri.h2d``, ``judge.prefill``, ...) are
dotted ``<layer>.<step>`` and never reuse a virtual-time span's name.
They are kept out of ``spans``, so the conservation law and the JSONL /
Chrome exports are unchanged by them. ``NullTracer.wall`` returns one
shared no-op context: no allocation, no annotation, no clock read.
"""
from __future__ import annotations

from time import perf_counter
from typing import NamedTuple, Optional

# rid used for spans that belong to no request (refresh-ahead fetches,
# invalidation drops, holder-side lease validation)
BACKGROUND = -1

# tuple field offsets of one span record (plain tuples: the enabled-path
# cost is one append, nothing else)
RID, NAME, T0, T1, REGION, TAG = range(6)


class WallSpan(NamedTuple):
    """One closed wall-clock span; ``parent`` indexes ``wall_spans``."""
    name: str
    t0: float
    t1: float
    parent: int
    call: int


class _Wall:
    """The context of one open wall span (enabled tracer only)."""

    __slots__ = ("tr", "name", "i", "parent", "call", "ann", "t0")

    def __init__(self, tr: "Tracer", name: str):
        self.tr, self.name = tr, name

    def __enter__(self):
        tr = self.tr
        if tr._open:
            self.parent, self.call = tr._open[-1]
        else:
            self.parent, self.call = -1, tr._calls
            tr._calls += 1
        # the slot is taken at the start, so a parent's index is known
        # to its children; it holds None until the span closes
        self.i = len(tr.wall_spans)
        tr.wall_spans.append(None)
        tr._open.append((self.i, self.call))
        self.ann = tr._annotation(self.name)
        self.ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = perf_counter()
        self.ann.__exit__(*exc)
        tr = self.tr
        tr._open.pop()
        tr.wall_spans[self.i] = WallSpan(self.name, self.t0, t1,
                                         self.parent, self.call)
        return False


class Tracer:
    """Span sink shared by every layer of one run (one per engine, or one
    per federation — spans carry the region id either way)."""

    enabled = True
    __slots__ = ("spans", "wall_spans", "_open", "_calls", "_annotation")

    def __init__(self):
        from jax.profiler import TraceAnnotation

        # (rid, name, t0, t1, region, tag) in emission order — which is
        # deterministic (clock event order), making the exported JSONL
        # byte-identical across same-seed runs
        self.spans: list[tuple] = []
        # WallSpan per wall span, in start order (None while open)
        self.wall_spans: list = []
        self._open: list[tuple[int, int]] = []    # (index, call) stack
        self._calls = 0
        self._annotation = TraceAnnotation

    def wall(self, name: str) -> _Wall:
        """Context manager: a wall-clock span ``name`` around the block,
        nested under the innermost wall span open."""
        return _Wall(self, name)

    def span(self, rid: int, name: str, t0: float, t1: float,
             region: int = 0, tag: Optional[str] = None) -> None:
        self.spans.append((rid, name, t0, t1, region, tag))

    def marker(self, rid: int, name: str, t: float, region: int = 0,
               tag: Optional[str] = None) -> None:
        """Zero-duration span: an event worth seeing on the timeline that
        consumes no virtual time (band bypass, invalidation drop)."""
        self.spans.append((rid, name, t, t, region, tag))

    def request_spans(self) -> dict[tuple[int, int], list[tuple]]:
        """Request-scoped spans grouped by ``(region, rid)`` — the pair is
        the unique request key under federation, where per-region
        workloads reuse rid ranges."""
        out: dict[tuple[int, int], list[tuple]] = {}
        for s in self.spans:
            if s[RID] >= 0:
                out.setdefault((s[REGION], s[RID]), []).append(s)
        return out


class _NullWall:
    """The one shared no-op wall span of the disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_WALL = _NullWall()


class NullTracer:
    """Disabled tracer: every method is a no-op, no state, no allocation.
    The singleton :data:`NULL_TRACER` is the default everywhere a tracer
    can be threaded."""

    enabled = False
    __slots__ = ()

    def wall(self, name: str) -> _NullWall:
        return _NULL_WALL

    def span(self, rid, name, t0, t1, region=0, tag=None) -> None:
        return None

    def marker(self, rid, name, t, region=0, tag=None) -> None:
        return None


NULL_TRACER = NullTracer()
