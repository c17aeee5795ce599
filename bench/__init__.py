"""The chip benchmark of this repository: cells named in ``BENCHMARK.json``,
run one at a time by ``python3 bench/run.py --workload <cell> ...``."""
