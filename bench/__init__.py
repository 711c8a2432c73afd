"""On-chip benchmark of this repository: one command runs one cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, configurations, traffic kinds and per-layer metrics are files under
this directory, found by the names ``BENCHMARK.json`` gives them.
"""
