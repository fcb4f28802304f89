"""Mean time per batch of the device-to-host copy of its result (the
program's ``edge.fetch`` span, inside ``edge.wait``), over the batches
delivered in the traced window, in ms."""
from bench.metrics._program_clock import in_window


def read(rec):
    found = in_window(rec, "edge.fetch")
    if not found:
        return None
    return sum(s["dur"] for _, _, s in found) / len(found) / 1e3
