"""Chip benchmark of START's per-interval decision path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything
that belongs to one configuration, traffic mix or per-layer metric is a
file of its own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the name ``BENCHMARK.json`` gives it.
"""
