"""Per-layer metric readers, one file per metric named as in
``BENCHMARK.json``. Each has ``read(rec)``, returning the number or None
when the run holds nothing to read."""
