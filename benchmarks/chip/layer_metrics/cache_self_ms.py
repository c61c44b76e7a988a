"""The cache's own time per lookup: the ``lookup_batch`` and ``insert``
spans less the stage-1 and judge spans inside them (LCFU, SoA, admission
and eviction bookkeeping), over the window's lookups."""


def read(run):
    n = run["embedded"]
    if not n:
        return None
    own = run["span_s"]("lookup") + run["span_s"]("insert") \
        - run["span_s"]("stage1") - run["span_s"]("judge")
    return 1e3 * own / n
