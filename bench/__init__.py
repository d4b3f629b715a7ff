"""Chip benchmark of paper-FFN training (see BENCHMARK.json and PERF.md).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the chips of the machine it starts on.
Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
BENCHMARK.json gives it: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``workloads/<cell>.json`` (the cell's
correctness limits) and ``metrics/<metric>.py``.
"""
