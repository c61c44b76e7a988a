"""The judge prefill's share of its roofline: the least time the chip
needs for the window's judge prefill calls over the device time of the
``jit_score`` ops in the trace.

``ModelJudge.score_pairs`` dispatches one ``jit_score`` call per chunk of
up to CHUNK pairs. A call's least time is the larger of its real pairs'
FLOPs (the judge family's ``forward_flops`` at ``judge_max_len``; pad
pairs count for nothing) at the bf16 peak and one read of the judge's
weights (``weight_bytes``) at HBM bandwidth."""
import counts
import tracing

# the program's largest pair bucket (src/repro/core/judge.py PAIR_BUCKETS)
CHUNK = 32
MODULE = r"^jit_score:"


def read(run):
    tr = run["trace"]
    if tr is None or not run["judge_calls"]:
        return None
    spent = tracing.op_seconds(tr, MODULE)
    if spent <= 0:
        return None
    jm = run["judge"]
    flops = jm.family.forward_flops(
        jm.hf, run["cfg"]["deployment"]["judge_max_len"])
    nbytes = jm.family.weight_bytes(jm.hf)
    need = 0.0
    for n in run["judge_calls"]:
        for off in range(0, n, CHUNK):
            real = min(CHUNK, n - off)
            need += counts.least_time(real * flops, nbytes, run["peaks"])[0]
    return 100.0 * need / spent
