"""Chip benchmark of the served Hippo path: the cells of ``BENCHMARK.json``.

``run.py`` is the entry point; ``catalog`` finds a cell's files by name.
"""
