"""The benchmark: everything the yardstick owns lives under this directory.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` is the one command; ``BENCHMARK.json`` at the repository
root names the cells, configurations and metrics, and every one of them is
a file here that the harness finds by that name (``spec.py``).
"""
