"""Chip benchmark of the ContiguousKV serving path: one harness, driven by data.

``chipbench/run.py`` runs one cell of ``BENCHMARK.json``.  A cell names a
model configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); each metric is a reader in ``metrics/<name>.py``.
A configuration's weights, reference and FLOP counts come from the
architecture module that its file names (``reference.py`` without the key).
"""
