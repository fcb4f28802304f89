"""Share of the traced window in which the device runs no operation while
the serving worker is inside ``batch.process`` or ``batch.finalize`` (the
program's spans, placed on the trace's clock), in %. What
``idle_share.edge`` holds beyond it is idle while the worker waits for
frames."""
from bench.metrics import _program_clock
from bench.metrics._common import traced


def merged(intervals):
    """The union of (start, end) intervals, as disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a, b):
    """Length of the intersection of two lists of disjoint sorted
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(rec):
    t = traced(rec)
    if t is None or not t[0].devices:
        return None
    found = [_program_clock.spans(rec, n)
             for n in ("batch.process", "batch.finalize")]
    if any(f is None for f in found):
        return None
    tr, lo, hi = t

    def clipped(pairs):
        return merged((max(s, lo), min(e, hi)) for s, e in pairs
                      if e > lo and s < hi)

    host = clipped((s, e) for f in found for s, e, _ in f)
    host_ns = sum(e - s for s, e in host)
    idle = [host_ns - overlap(host, clipped((s, e) for _, s, e, _ in evs))
            for evs in tr.devices.values()]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
