"""Profiler trace → device and host intervals, and what is read from them.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps:

* the device operations of each TPU (the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane), as ``(name, start_ns, end_ns, kernel)``,
  where ``kernel`` marks a Mosaic custom call (a Pallas kernel);
* the benchmark's own host annotations (names starting ``bench.``),
  as ``(name, start_ns, end_ns, args)``.

All times are on the profiler's clock, so host spans and device operations
compare directly.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

ANNOTATION_PREFIX = "bench."


def _is_kernel(name: str, stats: Dict[str, object]) -> bool:
    """A Mosaic (Pallas) kernel: the op is a ``tpu_custom_call``. The op
    name is its HLO text, which names the call's target."""
    text = " ".join([name, str(stats.get("long_name", ""))])
    return 'custom_call_target="tpu_custom_call"' in text


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Tuple[str, int, int, bool]]]
    annotations: List[Tuple[str, int, int, dict]]

    # -- the window -----------------------------------------------------------

    def window(self, name: str = "bench.window") -> Optional[Tuple[int, int]]:
        spans = [(s, e) for n, s, e, _ in self.annotations if n == name]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)

    def spans(self, name: str) -> List[Tuple[int, int, dict]]:
        return sorted((s, e, a) for n, s, e, a in self.annotations if n == name)

    def ops(self, lo: int, hi: int, device: Optional[str] = None):
        """Device operations that start in [lo, hi), clipped to it."""
        out = []
        for dev, evs in self.devices.items():
            if device is not None and dev != device:
                continue
            for name, s, e, k in evs:
                if lo <= s < hi:
                    out.append((name, s, min(e, hi), k))
        return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, int, int, bool]]] = {}
    annotations = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    stats = {k: v for k, v in ev.stats}
                    evs.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                _is_kernel(ev.name, stats)))
            devices[plane.name] = sorted(evs, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((ev.name, int(ev.start_ns),
                                            int(ev.end_ns),
                                            {k: v for k, v in ev.stats}))
    return Trace(devices, sorted(annotations, key=lambda a: a[1]))


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


# -- reductions ---------------------------------------------------------------


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Device busy time in [lo, hi), averaged over the devices traced."""
    if not trace.devices:
        return 0.0
    per = [union_ns((max(s, lo), min(e, hi)) for _, s, e, _ in evs
                    if e > lo and s < hi)
           for evs in trace.devices.values()]
    return sum(per) / len(per)


def leaf_ops(ops):
    """The operations that hold no other: a loop op (``while``) whose body
    ops run inside it is left out, so no time is counted twice."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[2] or nxt[2] > o[2]]


def breakdown(trace: Trace, lo: int, hi: int, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps
    labelled by the innermost benchmark annotation open at the gap's middle."""
    by_name: Dict[str, int] = {}
    for name, s, e, _ in leaf_ops(trace.ops(lo, hi)):
        name = name.split(" = ")[0].lstrip("%")
        by_name[name] = by_name.get(name, 0) + (e - s)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for evs in trace.devices.values():
        cursor = lo
        for _, s, e, _ in evs:
            if e <= lo or s >= hi:
                continue
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < hi:
            gaps.append((cursor, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        open_ = [(a_s, n) for n, a_s, a_e, _ in trace.annotations
                 if a_s <= mid < a_e and n != "bench.window"]
        label = max(open_)[1] if open_ else "no benchmark span"
        labelled.append([label, (e - s) / 1e9])
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": labelled}
