"""Judged pairs per judge micro-batch, pad pairs excluded: the
pipeline's own counters (``PipelineStats.judged_pairs`` over
``judge_batches``) across the window."""


def read(run):
    if not run["judge_batches"]:
        return None
    return run["judged_pairs"] / run["judge_batches"]
