"""The chip benchmark: ``run.py`` runs one cell of ``BENCHMARK.json``."""
