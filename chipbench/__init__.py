"""Chip benchmark of the served R-tree query path.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``BENCHMARK.json`` at the repo
root names the cells; ``configs/<config>.json`` holds a deployment,
``traffic/<mix>.json`` the parameters of a traffic mix that ``gen.py``
reads, and ``metrics/<metric>.py`` (or ``metrics/<quantity>.py`` for a
metric named ``<quantity>.<group>``) the reader of a per-layer metric.
"""
