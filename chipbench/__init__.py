"""The cell benchmark: `python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`."""
