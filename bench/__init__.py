"""Chip benchmark: cells, traffic, references and trace reduction.

Entry point: ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``. Everything a cell needs is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``, its
traffic in ``bench/traffic/<traffic>.json`` (generated by
``bench/arrivals/<kind>.py``), its driver in ``bench/drivers/<driver>.py`` and
each per-layer metric in ``bench/metrics/<metric>.py``.
"""
