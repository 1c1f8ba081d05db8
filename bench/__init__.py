"""The chip benchmark: one command, cells and metrics found by name.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
