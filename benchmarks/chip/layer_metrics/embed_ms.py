"""Embedder time per lookup: the benchmark's span around
``ModelEmbedder.embed_batch`` (it returns host numpy, so the span holds
the device work), summed over the window, over the lookups embedded."""


def read(run):
    n = run["embedded"]
    if not n:
        return None
    return 1e3 * run["span_s"]("embed") / n
