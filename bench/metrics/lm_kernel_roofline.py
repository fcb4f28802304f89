"""Roofline time of every projection of the window's decode steps (counted
from shapes by ``bench.lm_work``: the real rows only, int8 operands, the
weights read once a step), over the summed device time of the window's
``closed_form_matmul`` Mosaic events, in %."""
from bench import lm_work, work
from bench.metrics._common import traced


def read(rec):
    t = traced(rec)
    if t is None:
        return None
    tr, lo, hi = t
    dev = sum(e - s for name, s, e, k in tr.ops(lo, hi)
              if k and "closed_form_matmul" in name)
    steps = [s["args"]["occupancy"] for s in rec.get("spans") or []
             if s["name"] == "serve.decode_step"]
    if not dev or not steps:
        return None
    cfg = rec["model_cfg"]
    bound = sum(work.roofline_s(*lm_work.step(cfg, rows), rec["peak"], "int8")
                for rows in steps)
    return 100.0 * bound / (dev / 1e9)
