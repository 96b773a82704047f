"""On-chip serving benchmark: one cell per (model configuration, traffic mix).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU and prints one JSON line.
Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in its own file under ``bench/configs``,
``bench/traffic`` and ``bench/layer_metrics``, found by name.
"""
