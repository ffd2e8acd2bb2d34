"""On-chip benchmark of the DiSCO solver and the GLM scoring engine.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip it
is started on. Configurations (``configs/``), traffic mixes
(``traffic/``) and per-layer metrics (``metrics/``) are found by name.
"""
