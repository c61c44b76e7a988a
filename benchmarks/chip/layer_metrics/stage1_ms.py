"""Stage-1 time per pass: the span around the index's ``search_batch``
(host layout, copies and kernel together), over the window's passes."""


def read(run):
    n = len(run["passes"])
    if not n:
        return None
    return 1e3 * run["span_s"]("stage1") / n
