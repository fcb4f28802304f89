"""Roofline time of the frames' logical work (a 3x3 'same' conv over each
frame served, at its own size: int8 pixels in, int32 responses out; the
padding of partial batches and of the bucket is not work), over the summed
device time of the Mosaic kernel events that ran their batches, in %.

The reader takes whatever Pallas kernel serves an edge batch: today that is
``closed_form_matmul`` over im2col patches, and a kernel that replaces it
(the fused conv) is read the same way, against the same count. A batch's
work is set against the kernel events that start inside its
dispatch-to-delivery span, however many calls the kernel makes for it; the
cell runs no other Mosaic kernel."""
from bench import work
from bench.metrics._common import pairs, traced, true_size


def read(rec):
    t = traced(rec)
    if t is None:
        return None
    tr, lo, hi = t
    batches = pairs([(s, e, a) for s, e, a in tr.spans("bench.edge_dispatch")],
                    [(s, e, a) for s, e, a in tr.spans("bench.edge_deliver")])
    kernels = [(s, e) for _, s, e, k in tr.ops(lo, hi) if k]
    kh, kw = len(rec["config"]["kernel"]), len(rec["config"]["kernel"][0])
    bound = dev = 0.0
    for (ds, _, args), (_, de, _) in batches:
        inside = sum(e - s for s, e in kernels if ds <= s < de)
        size = true_size(rec, [int(v) for v in args["shape"].split("x")[1:]])
        if inside and size:
            bound += work.roofline_s(
                *work.conv(int(args["frames"]), *size, kh, kw), rec["peak"],
                "int8")
            dev += inside / 1e9
    return 100.0 * bound / dev if dev else None
