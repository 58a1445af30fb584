"""The PIQUE benchmark: one cell (configuration x traffic) per run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
