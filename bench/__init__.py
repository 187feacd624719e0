"""The benchmark: cells, traffic, references, trace reduction and metrics.

Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.
"""
