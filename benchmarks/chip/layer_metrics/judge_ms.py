"""Judge time per judged pair: the span around ``ModelJudge.score_pairs``
(prefill, returning host numpy), over the real pairs it scored."""


def read(run):
    n = run["judged_pairs"]
    if not n:
        return None
    return 1e3 * run["span_s"]("judge") / n
