"""Median wall time of the window's ``serve.decode_step`` spans (the
compiled step, the logits' fetch and the sampling), in ms."""
import numpy as np


def read(rec):
    d = [s["dur"] for s in rec.get("spans") or []
         if s["name"] == "serve.decode_step"]
    return float(np.median(d)) / 1e3 if d else None
