"""On-chip benchmark of the multiplier bank, driven by ``BENCHMARK.json``.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell.  Everything that belongs to one
configuration, traffic mix or metric is a file of its own:
``configs/<name>.json``, ``traffic/<name>.json`` and
``metrics/<name>.py``.
"""
