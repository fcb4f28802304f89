"""Faults planted under the timed path, to show that ``correct`` catches
them: each is a context manager that patches the system under test for its
duration. ``bench/calibrate.py --fault`` reads them on the chip and
``tests/bench`` on the CPU."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(cls, name, make):
    inner = getattr(cls, name)
    setattr(cls, name, make(inner))
    try:
        yield
    finally:
        setattr(cls, name, inner)


def altered_answer():
    """One pixel of the first map of every batch flipped as it is delivered."""
    from repro.serving.edge_service import EdgeDetectService

    def make(inner):
        def finalize(self, bucket, raw):
            maps = inner(self, bucket, raw)
            maps[0] = maps[0].copy()
            maps[0][0, 0] ^= 1
            return maps
        return finalize

    return _patched(EdgeDetectService, "_finalize", make)


def half_batch():
    """The first half of every padded batch (where its frames sit) left
    out: those maps come back as zeros."""
    from repro.serving.edge_service import EdgeDetectService

    def make(inner):
        def compute(self, batch):
            out = inner(self, batch)
            return out.at[:out.shape[0] // 2].set(0)
        return compute

    return _patched(EdgeDetectService, "_compute", make)


FAULTS = {f.__name__: f for f in (altered_answer, half_batch)}
