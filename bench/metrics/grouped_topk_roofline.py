"""The grouped top-k kernel's share of its roofline, in percent: the
least time the chip needs for the live int8 rows the kernel scanned
(bound by HBM bytes), over the kernel's device time in the trace."""
from bench.lib.roofline import grouped_topk_work, least_seconds


def read(run):
    red, work = run.reduction, run.kernel_work
    if red is None or run.peaks is None or not work.get("rows"):
        return None
    device_s = red.kernel_s.get("grouped_topk", 0.0)
    if device_s <= 0:
        return None
    nbytes, ops = grouped_topk_work(work["rows"], work["groups"], run.cfg["dim"])
    return 100.0 * least_seconds(nbytes, ops, run.peaks) / device_s
