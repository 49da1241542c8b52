"""Chip benchmark for ScalePool: one data-driven harness.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on
and prints one JSON result line.  Configurations, traffic mixes and
per-layer metrics are files of their own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``; the harness finds them by name.
"""
