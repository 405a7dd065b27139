"""The chip benchmark of this repository: one command, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip it finds. See ``PERF.md``.
"""
