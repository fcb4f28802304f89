"""Slots used over slots flushed by the micro-batcher in the window, in %
(``ServingMetrics`` counters)."""


def read(rec):
    total = rec.get("slots_total")
    return 100.0 * rec["slots_used"] / total if total else None
