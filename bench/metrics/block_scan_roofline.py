"""block_scan_roofline: the least time the chip could read the scan's
bytes in -- u of every answer the device computed, times one plane's
bytes, over the HBM bandwidth -- over the device time of the block-scan
kernel's events, in percent.  Padded lanes and speculative blocks read
bytes that count as waste."""

from bench import roofline
from bench import trace_reduce as tr

KERNEL = "block_scan_pruned"


def read(run):
    if not run.trace:
        return None
    ops = [e for e in run.trace["ops"] if KERNEL in e.name
           or any(KERNEL in str(v) for v in (e.stats or {}).values())]
    kernel_s = tr.busy_ns(ops, run.trace["lo"], run.trace["hi"]) * 1e-9
    if kernel_s <= 0:
        return None
    u = sum(int(r.result.u) for r in run.window.completed
            if not r.result.cached)
    return roofline.roofline_pct(u, int(run.config["widths"]["block_docs"]),
                                 kernel_s, run.device_kind)
