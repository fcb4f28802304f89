"""Plain references the benchmark compares served outputs against.

Nothing here imports the system under test (``repro``): the multiplier is
rebuilt from its partial-product matrix, and the edge path is written out
in numpy / ``jax.numpy``.
"""
