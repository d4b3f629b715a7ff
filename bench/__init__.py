"""Chip benchmark of training (see BENCHMARK.json and PERF.md).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the chips of the machine it starts on.
Everything that belongs to one configuration, traffic mix, cell,
per-layer metric or program is a file of its own, found by the name that
BENCHMARK.json or a configuration gives it: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``workloads/<cell>.json`` (the cell's
correctness limits), ``metrics/<metric>.py`` and
``programs/<program>.py`` (the train step, inputs, plain reference and
operation count of the program that a configuration names).
"""
