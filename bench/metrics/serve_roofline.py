"""Kernel layer: the share, in %, of device busy time that the work the
served requests define would take at the chip's roofline.

For every completed request the least time is the larger of its flops
over peak FLOP/s and its bytes over peak HBM bytes/s (``bench/work.py``:
work counted from the request's operands, whatever body serves it).
Busy time is the union of device operations in the window, summed over
the chips used, so the share cannot pass 100 %."""
import work


def read(run):
    if run.trace is None or not run.trace.has_device_ops or not run.completed:
        return None
    busy = run.trace.busy_seconds_total()
    if busy <= 0:
        return None
    least = sum(work.least_seconds(r.flops, r.bytes, run.peaks)
                for r in run.completed)
    return 100.0 * least / busy
