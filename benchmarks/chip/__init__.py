"""The chip benchmark: one harness (``run.py``) driven by the data in
``BENCHMARK.json`` and the files beside it. See ``run.py``."""
