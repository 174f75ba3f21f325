"""h2d_mb: mean megabytes (1e6 B) that ``System.batch_inputs`` copies to
the device per micro-batch (occupancy bitmaps, term mask and idf), from
the ``bytes`` arg of the program's ``h2d`` spans in the window.
Layer: input build, host part."""

import numpy as np


def read(run):
    if not run.spans:
        return None
    t0 = run.window.t0
    sent = [s["args"]["bytes"] for s in run.spans
            if s["name"] == "h2d" and s["t0"] >= t0]
    if not sent:
        return None
    return float(np.mean(sent)) / 1e6
