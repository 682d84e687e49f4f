"""chipbench: the benchmark of bluefog-tpu on the chip, driven by BENCHMARK.json.

One process, one cell, once::

    python -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

See README.md in this directory.
"""
