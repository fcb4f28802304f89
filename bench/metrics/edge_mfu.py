"""Useful operations of the frames served (their own pixels, not padding),
over the summed wall time of their compiled calls (``edge.execute`` start to
``edge.wait`` end), over the chip's int8 peak, in %."""
from bench import work
from bench.metrics._common import pairs, true_size


def read(rec):
    spans = sorted(rec.get("spans") or [], key=lambda s: s["ts"])
    by = {n: [(s["ts"], s) for s in spans if s["name"] == n]
          for n in ("edge.pad", "edge.execute", "edge.wait")}
    ops = secs = 0.0
    kh, kw = (len(rec["config"]["kernel"]), len(rec["config"]["kernel"][0]))
    for (_, pad), (_, ex) in pairs(by["edge.pad"], by["edge.execute"]):
        wait = next((s for t, s in by["edge.wait"] if t >= ex["ts"]), None)
        size = true_size(rec, tuple(int(v) for v in
                                     pad["args"]["bucket"].split("x")))
        if wait is None or size is None:
            continue
        ops += work.conv(pad["args"]["size"], *size, kh, kw)[0]
        secs += (wait["ts"] + wait["dur"] - ex["ts"]) / 1e6
    if not secs:
        return None
    return 100.0 * ops / secs / rec["peak"]["int8_ops_per_s"]
