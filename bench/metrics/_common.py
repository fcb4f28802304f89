"""Shared pieces of the readers: the traced window and its kernels."""
from bench import trace as btrace


def traced(rec):
    """(trace, lo_ns, hi_ns) or None without a trace."""
    tr, win = rec.get("trace"), rec.get("trace_window")
    if tr is None or win is None:
        return None
    return tr, win[0], win[1]


def idle_share(rec):
    t = traced(rec)
    if t is None or not t[0].devices:
        return None
    tr, lo, hi = t
    return 100.0 * (1.0 - btrace.busy_ns(tr, lo, hi) / (hi - lo))


def pairs(first, second):
    """Match each interval of ``first`` to the next of ``second`` that
    starts after it: [(first, second)]."""
    out, j = [], 0
    for a in first:
        while j < len(second) and second[j][0] < a[0]:
            j += 1
        if j == len(second):
            break
        out.append((a, second[j]))
        j += 1
    return out


def true_size(rec, bucket):
    """(height, width) of the traffic's frames that pad to ``bucket``, or
    None."""
    g = rec["config"]["service"]["bucket_granularity"]
    for f in rec["traffic"]["frames"]:
        h, w = f["height"], f["width"]
        if (-(-h // g) * g, -(-w // g) * g) == tuple(bucket):
            return h, w
    return None
