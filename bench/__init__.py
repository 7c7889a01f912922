"""On-chip serving benchmark: one cell of BENCHMARK.json per run.

Run: python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
