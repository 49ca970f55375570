"""The chip benchmark of this repository: ``python3 bench/run.py --workload
<name> --seed <n> --seconds <s> --trace <0|1>``, driven by
``BENCHMARK.json`` at the root of the checkout."""
