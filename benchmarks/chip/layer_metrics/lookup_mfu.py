"""Model FLOP utilization of the whole served path: the FLOPs the window's
work needs (embedder forward at embed_max_len for every real query,
judge prefill at judge_max_len for every real pair, each counted by its
own family's ``forward_flops``, and the stage-1 scans) over the time any
micro-batch was running (the union of the ``batch`` spans) at the chip's
bf16 peak. Divided by busy time, not by the window, so it can move at a
fixed offered rate."""
import counts
import tracing


def read(run):
    busy = tracing.total(tracing.union(
        [(s, e) for name, s, e in run["spans"] if name == "batch"]))
    if busy <= 0:
        return None
    dep = run["cfg"]["deployment"]
    em, jm = run["embedder"], run["judge"]
    flops = run["embedded"] * em.family.forward_flops(
        em.hf, dep["embed_max_len"])
    flops += run["judged_pairs"] * jm.family.forward_flops(
        jm.hf, dep["judge_max_len"])
    flops += sum(counts.scan_work(p)[0] for p in run["passes"]
                 if p is not None)
    return 100.0 * flops / (busy * run["peaks"]["bf16_flops_per_s"])
