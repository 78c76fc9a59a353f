"""On-chip benchmark of the persistent exchange engine and the models that use it.

`python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once and prints one JSON result line.
"""
