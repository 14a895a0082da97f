"""Chip benchmark of the SpMV library: the cells named in ``BENCHMARK.json``.

``bench/run.py`` is the entry point.  Everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own,
found by name: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and the matrix generator ``matrices/<generator>.py``.
"""
