"""One driver per kind of system under test, named by a configuration's
``driver`` key. Each has ``setup``, ``measure`` and ``check``; all three
read and extend one record dict that the metric readers consume."""
