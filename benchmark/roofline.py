"""The chip's peaks and the bytes a conjugate-gradient iteration must move.

The yardstick counts the work whatever engine runs it: the three Krylov
vectors x, r and p persist from one iteration to the next, so each
iteration has to stream whatever part of them the chip's VMEM cannot
hold. Per chip that is max(0, 3·A − V) bytes, where A is one float32 node
array of the chip's block and V the chip's VMEM. No conjugate-gradient
loop that keeps x, r and p moves fewer bytes, so no engine can read over
100% of the HBM roofline on it.
"""

from __future__ import annotations

import json
import math
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
KRYLOV_VECTORS = 3
F32_BYTES = 4


def peaks(device_kind: str) -> dict:
    """The table's row for ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS} (known: {', '.join(sorted(table))})")
    return table[device_kind]


def block_nodes(grid, chips: int) -> tuple[int, int]:
    """The node block one chip holds of an M×N grid on a square mesh."""
    M, N = grid
    side = math.isqrt(chips)
    if side * side != chips:
        raise ValueError(f"{chips} chips make no square mesh")
    return -(-(M + 1) // side), -(-(N + 1) // side)


def krylov_bytes_per_iter(block, vmem_bytes: int,
                          itemsize: int = F32_BYTES) -> int:
    """Bytes one iteration must stream from HBM on one chip."""
    array = block[0] * block[1] * itemsize
    return max(0, KRYLOV_VECTORS * array - vmem_bytes)
