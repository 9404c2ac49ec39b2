"""Chip benchmark of MEMHD: one cell (configuration x traffic) per run.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line. Everything a cell needs is found by name: the
configuration in ``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json``, the correctness limits in
``bench/limits/<workload>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""
