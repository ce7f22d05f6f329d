"""The work a kernel call needs, whatever implements it.

``window_reduce`` over a replay: M (event, window) memberships that the
window spec gives the data and S (key, window) slots.  It must read each
membership's value and slot id once (4 + 4 bytes), and write five lanes
per slot (count, sum, sumsq, max, min: 5 x 4 bytes); it must apply five
operations per membership (one per lane).  The counts come from the data
and the spec, never from a grid, a block size or padding, so a sorted
layout, a fused five-lane kernel or a fan-out inside the kernel is held
to the same yardstick.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "device" / "peaks.json"


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_s(self, peak: dict) -> float:
        """The least time the chip could take: the larger of the
        operations over peak FLOP/s and the bytes over peak bytes/s."""
        return max(self.flops / peak["flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def window_reduce_work(memberships: int, slots: int) -> Work:
    return Work(flops=5.0 * memberships,
                bytes=8.0 * memberships + 20.0 * slots)


def device_peak(kind: str) -> dict:
    """The peaks of ``kind``; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[kind]
