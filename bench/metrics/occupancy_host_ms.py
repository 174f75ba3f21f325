"""occupancy_host_ms: mean host time of the program's ``occupancy`` spans
in the window, one per micro-batch: the per-query scatter of postings
into bool planes over every document, their bit-packing, and the stack
of the batch (the host part of ``System.batch_inputs`` before its copy
to the device).  Layer: input build, host part."""

import numpy as np


def read(run):
    if not run.spans:
        return None
    t0 = run.window.t0
    ms = [s["t1"] - s["t0"] for s in run.spans
          if s["name"] == "occupancy" and s["t0"] >= t0]
    if not ms:
        return None
    return 1e3 * float(np.mean(ms))
