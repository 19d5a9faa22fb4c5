"""Serving benchmark of the SC datapath engine on one TPU chip.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``; BENCHMARK.json at the checkout's root names the cells,
and every configuration, traffic mix and metric lives in a file of its
own under this directory, found by name (bench/spec.py).
"""
