"""On-chip serving benchmark: one cell (configuration x traffic) per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
