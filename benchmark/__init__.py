"""The repository's benchmark: one command (``benchmark/run.py``), driven
by ``BENCHMARK.json`` and the data files beside this one. See README.md."""
