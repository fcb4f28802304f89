"""The program's own spans (``repro.obs.trace``, kept in ``rec["spans"]``)
placed on the profiler trace's clock.

The program's tracer stamps its spans in microseconds on its own clock; the
trace holds nanoseconds on the profiler's. Every ``batch.process`` span
holds the ``bench.edge_dispatch`` annotation that the edge driver opens
within microseconds of the span's start, so each batch gives one offset
between the two clocks. The median of them places every span; the largest
distance of an offset from the median, the residual, says how closely the
two clocks run together over the window.
"""
from __future__ import annotations

import bisect
import statistics

from bench.metrics._common import traced


def _process_spans(rec):
    """[(start_ns, end_ns)] of the program's ``batch.process`` spans, on the
    program's clock, in order."""
    return sorted((s["ts"] * 1e3, (s["ts"] + s["dur"]) * 1e3)
                  for s in rec.get("spans") or []
                  if s["name"] == "batch.process")


def _holders(dispatches, procs, offset):
    """For each dispatch start, shifted by ``offset``, the index of the
    process span that starts nearest to it, if no farther than that span
    lasts; else None."""
    starts = [s for s, _ in procs]
    out = []
    for d in dispatches:
        t = d - offset
        i = bisect.bisect_left(starts, t)
        near = min((j for j in (i - 1, i) if 0 <= j < len(procs)),
                   key=lambda j: abs(starts[j] - t))
        s, e = procs[near]
        out.append(near if abs(t - s) <= e - s else None)
    return out


def alignment(rec):
    """(offset_ns, residual_ns, batches): the trace clock minus the
    program's clock, the largest distance of one batch's offset from it,
    and the number of batches matched; None without both a trace and
    ``batch.process`` spans."""
    t = traced(rec)
    procs = _process_spans(rec)
    if t is None or not procs:
        return None
    dispatches = [s for s, _, _ in t[0].spans("bench.edge_dispatch")]
    if not dispatches:
        return None
    # coarse: the shift that matches the most dispatches to a span
    candidates = {d - s for d in dispatches[:3] for s, _ in procs}
    coarse = max(sorted(candidates), key=lambda off: sum(
        i is not None for i in _holders(dispatches, procs, off)))
    offsets = [d - procs[i][0] for d, i in
               zip(dispatches, _holders(dispatches, procs, coarse))
               if i is not None]
    offset = statistics.median(offsets)
    return offset, max(abs(o - offset) for o in offsets), len(offsets)


def spans(rec, name):
    """[(start_ns, end_ns, span)] of the program's spans called ``name``,
    on the trace's clock, in order; None when the clocks cannot be
    aligned."""
    a = alignment(rec)
    if a is None:
        return None
    offset = a[0]
    return sorted(((s["ts"] * 1e3 + offset, (s["ts"] + s["dur"]) * 1e3
                    + offset, s)
                   for s in rec.get("spans") or [] if s["name"] == name),
                  key=lambda x: x[0])


def in_window(rec, name):
    """The spans called ``name`` that end inside the traced window, on its
    clock; None when the clocks cannot be aligned."""
    found = spans(rec, name)
    if found is None:
        return None
    _, lo, hi = traced(rec)
    return [x for x in found if lo <= x[1] <= hi]
