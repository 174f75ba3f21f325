"""Find the highest rate an open-loop cell sustains, by a sweep.

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds <s> \
        --rates 6,9,12 [--log-queries N]

One set-up, then one window per rate (lowest first) of the cell's mix
with ``rate_qps`` replaced, each on queries no earlier window sent.  A
rate is sustained when the backlog does not grow through the window:
the last third of requests waits no longer than the first third, and
the drain after the close takes no longer than the longest latency of
the first third.  One JSON line per rate.  The benchmark's runs never
run this: it fixes the rate a cell's traffic file states.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

from .spec import load_cell


def backlog(window) -> dict:
    import numpy as np

    from .loadgen import nearest_rank

    lat = window.latencies_s()
    n = len(lat)
    first, last = lat[: n // 3], lat[-(n // 3):]
    drain = max((r.done for r in window.records if r.done is not None),
                default=window.t_close) - window.t_close
    return {"offered": n, "failed": window.failed,
            "p50_ms": 1e3 * nearest_rank(lat, 0.5),
            "p95_ms": 1e3 * nearest_rank(lat, 0.95),
            "first_third_median_ms": 1e3 * float(np.median(first)),
            "last_third_median_ms": 1e3 * float(np.median(last)),
            "drain_s": drain,
            "sustained": bool(window.failed == 0
                              and np.median(last) <= 1.25 * np.median(first)
                              and drain <= max(first.max(), 1e-9))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--log-queries", type=int, default=None)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    if args.log_queries:
        cell.config = copy.deepcopy(cell.config)
        cell.config["querylog"]["n_queries"] = args.log_queries
    from .device import NoAccelerator, device_stamp

    try:
        stamp = device_stamp(cell.chips)
    except NoAccelerator as e:
        print(f"bench.sweep: {e}", file=sys.stderr)
        return 2
    import jax

    from .run import Harness

    h = Harness(cell, args.seed, False, jax.devices()[:cell.chips])
    try:
        for rate in sorted(float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_qps=rate)
            window, *_ = h.measure(traffic, args.seconds)
            print(json.dumps({"workload": cell.name, "rate_qps": rate,
                              "device": stamp, **backlog(window)}), flush=True)
    finally:
        h.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
