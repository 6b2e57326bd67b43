"""The chip benchmark: ``python3 chipbench/run.py --workload <cell> ...``.

See ``run.py`` for the command and ``spec.py`` for how a cell's
configuration, traffic mix and per-layer metrics are found by name.
"""
