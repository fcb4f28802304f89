"""Plain reference for the edge-detection service: a 'same' 3×3 convolution
over zero-padded 8-bit grey frames, each pixel×coefficient product taken
from the proposed multiplier's table, summed exactly and clipped to 0..255.

Pixels enter the signed 8-bit operand domain as ``p >> 1`` (0..127).
"""
from __future__ import annotations

import numpy as np

from bench.reference import multiplier


def edge_map(img: np.ndarray, taps: np.ndarray) -> np.ndarray:
    """(H, W) uint8 frame → (H, W) uint8 edge map."""
    taps = np.asarray(taps, np.int64)
    kh, kw = taps.shape
    px = np.pad(img.astype(np.int64) >> 1, ((kh // 2,) * 2, (kw // 2,) * 2))
    h, w = img.shape
    table = multiplier.table()
    raw = np.zeros((h, w), np.int64)
    for di in range(kh):
        for dj in range(kw):
            raw += table[px[di:di + h, dj:dj + w] + 128, taps[di, dj] + 128]
    return np.clip(raw, 0, 255).astype(np.uint8)
