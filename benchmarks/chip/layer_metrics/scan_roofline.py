"""Stage-1 kernels' share of their roofline: the least time the chip
needs for the passes the window ran (``counts.scan_work`` of each pass's
shapes at the peaks of ``peaks.json``; the fp32 scans are memory-bound
at these batch sizes) over the summed device time of the ``ann_topk``
kernel events in the trace."""
import counts
import tracing

# the stage-1 Pallas kernels, by the XLA module or op they run as
KERNELS = r"ann_topk"


def read(run):
    tr = run["trace"]
    shapes = [p for p in run["passes"] if p is not None]
    if tr is None or not shapes:
        return None
    spent = tracing.kernel_seconds(tr, KERNELS)
    if spent <= 0:
        return None
    need = 0.0
    for shape in shapes:
        flops, nbytes, int8 = counts.scan_work(shape)
        need += counts.least_time(flops, nbytes, run["peaks"], int8)[0]
    return 100.0 * need / spent
