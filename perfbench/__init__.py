"""Chip benchmark of the training loop: env-steps/s around the K-ary PER.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything a cell
needs is found by name: its configuration in ``configs/``, its traffic
in ``traffic/``, its comparison limits in ``limits/`` and each per-layer
metric's reader in ``metrics/``.
"""
