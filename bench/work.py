"""Operations and bytes of the logical work, counted from shapes.

A contraction is counted as what it must do whatever implements it: two
operations per multiply-accumulate, its operands read once at their stated
width, and its int32 (or float32) result written once. Replacing a kernel
therefore cannot change a count.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; KeyError if the
    kind is not in the table."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE}")
    return table[device_kind]


def matmul(m: int, k: int, n: int, operand_bytes: int = 1,
           result_bytes: int = 4) -> tuple[float, float]:
    """(ops, bytes) of an (m, k) @ (k, n) contraction."""
    return (2.0 * m * k * n,
            float(operand_bytes * (m * k + k * n) + result_bytes * m * n))


def conv(b: int, h: int, w: int, kh: int, kw: int) -> tuple[float, float]:
    """(ops, bytes) of a 'same' kh×kw conv over b int8 frames of h×w,
    int32 result."""
    px = b * h * w
    return 2.0 * px * kh * kw, float(px + kh * kw + 4 * px)


def roofline_s(ops: float, nbytes: float, peak: dict, dtype: str) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(ops / peak[f"{dtype}_ops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

