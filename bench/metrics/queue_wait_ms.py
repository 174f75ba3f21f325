"""queue_wait_ms: median, over the window's requests, of the time from
the cluster's ticket opening (submit) to the end of its ``queue`` span
(the drain into a micro-batch), from the program's tracer spans.  Layer:
cluster front door and batcher."""

import numpy as np


def read(run):
    if not run.spans:
        return None
    t0 = run.window.t0
    opened = {s["id"]: s["t0"] for s in run.spans
              if s["name"] == "ticket" and s["t0"] >= t0}
    waits = [s["t1"] - opened[s["parent"]] for s in run.spans
             if s["name"] == "queue" and s["parent"] in opened]
    if not waits:
        return None
    return 1e3 * float(np.median(waits))
