"""Chip benchmark of the FL round: one cell per run, driven by data.

``python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the checkout root.  ``BENCHMARK.json`` names the
cells; each cell's configuration, traffic mix and per-layer metrics are
JSON files under ``configs/``, ``traffic/`` and ``metrics/``, and the
model its configuration trains is a module under ``families/``.
"""
