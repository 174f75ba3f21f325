"""Readings of the comparison for the program and for its control.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: set the cell up, run its traffic for
``--seconds``, and compare every answer with the plain reference twice:

- ``program``: the answers the timed path produced (the lower reading
  of each limit in the configuration's ``correct`` section);
- ``control``: the reference itself put in the program's place, with
  the L1 ranker's matmuls at ``high`` precision (three bfloat16 passes)
  -- the nearest precision below the float32 at ``highest`` that the
  configuration serves at.  ``bench/check.py`` must refuse it (its
  readings are the upper ends of the score limits).

One JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from .spec import load_cell


class _Served:
    """A reference answer in the shape of a served response."""

    def __init__(self, ans):
        self.u, self.cand_cnt = ans.u, len(ans.cand)
        self.doc_ids, self.scores = ans.ids, ans.top


def readings(h, records) -> dict:
    from . import check

    keep = int(h.cell.config["widths"]["keep"])
    ref = h.answers(records)
    ctl = h.answers(records, l1_precision="high")
    return {"program": check.compare(zip((r.result for r in records), ref), keep),
            "control": check.compare(zip(map(_Served, ctl), ref), keep)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, run one after another")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    from .device import NoAccelerator, device_stamp

    try:
        stamp = device_stamp(cell.chips)
    except NoAccelerator as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 2
    import jax

    from .run import Harness

    for seed in (int(s) for s in args.seeds.split(",")):
        h = Harness(cell, seed, False, jax.devices()[:cell.chips])
        try:
            window, *_ = h.measure(cell.traffic, args.seconds)
        finally:
            h.close()
        out = readings(h, window.completed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "device": stamp, "failed": window.failed, **out}),
              flush=True)
        del h
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
