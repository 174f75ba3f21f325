"""device_wait_ms: mean host time of the program's ``device_wait`` spans
in the window, one per micro-batch: how long the host stays blocked in
``jax.block_until_ready`` on the serve executable's outputs.  It is host
time, not device time: device work that runs while the host still
dispatches (the eager L1 scoring during ``l1_dispatch``) is not in it,
so it also falls when that dispatch grows slower.  Layer: serve
executable, host wait."""

import numpy as np


def read(run):
    if not run.spans:
        return None
    t0 = run.window.t0
    ms = [s["t1"] - s["t0"] for s in run.spans
          if s["name"] == "device_wait" and s["t0"] >= t0]
    if not ms:
        return None
    return 1e3 * float(np.mean(ms))
