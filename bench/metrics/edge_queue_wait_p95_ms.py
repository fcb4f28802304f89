"""95th percentile of every frame's wait in the micro-batcher's queue, from
enqueue to the pop of its batch, over the batches popped in the traced
window, in ms (the ``waits_ms`` of the program's ``batch.queue_wait``
spans)."""
import numpy as np

from bench.metrics._program_clock import in_window


def read(rec):
    found = in_window(rec, "batch.queue_wait")
    waits = [w for _, _, s in found or ()
             for w in s["args"].get("waits_ms", ())]
    return float(np.percentile(waits, 95)) if waits else None
