"""On-chip benchmark of the served match-planning path.

Everything a cell needs is found by name from data files:
``BENCHMARK.json`` names the cells, ``bench/configs/<config>.json`` holds
a deployment, ``bench/traffic/<traffic>.json`` a traffic mix, and
``bench/metrics/<metric>.py`` the reader of one per-layer metric.  Run a
cell with ``python3 -m bench.run --workload <cell> --seed <n> --seconds
<s> --trace <0|1>`` from the root of a checkout.
"""
