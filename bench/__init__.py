"""Chip benchmark of the paged-KV serving engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything a cell is made of is
found by name: ``configs/<config>.json`` (and the architecture module
``references/<reference>.py`` it names), ``traffic/<mix>.json`` (read by
the generator ``traffic/<kind>.py`` that the mix names) and one reader
``metrics/<metric>.py`` per per-layer metric.
"""
