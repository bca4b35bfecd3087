"""On-chip benchmark of the coded-execution engine (see ``BENCHMARK.json``).

Run one cell from the checkout root on a machine with a TPU::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
