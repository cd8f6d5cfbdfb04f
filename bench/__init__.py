"""On-chip benchmark of the robust serving and training paths.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once; see ``bench/README.md``.
"""
