"""The work the fused query kernel's job needs, counted from shapes.

A row is one (query, table) pair that reaches the kernel; a probe is one
valid bucket of that row (the exact bucket and each near bucket the
row's plan asks for); a slot is one of the bucket's `capacity` places.
The count takes only real rows and valid probes, so it does not change
with the kernel's block shape (TB, KC), with batch padding, or with the
rows a kernel scores redundantly: a later kernel that does less work is
measured against the same yardstick.

  bytes  the payload (d float32) and id (int32) of every probed slot,
         each row's query (d float32), and its m (id, score) outputs;
  flops  one multiply and one add per payload element of every probed
         slot (the dot product).
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def fused_query(rows: int, probes: int, capacity: int, d: int,
                m: int) -> Work:
    slots = rows * probes * capacity
    return Work(
        flops=2.0 * slots * d,
        bytes=4.0 * (slots * (d + 1) + rows * d + rows * 2 * m),
    )


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_pct(work: Work, kernel_s: float, peak: dict) -> float:
    """Least time the chip could take for the work, over the time the
    kernel took, in percent; bound by the larger of the two floors."""
    least = max(work.flops / peak["flops_per_s"],
                work.bytes / peak["bytes_per_s"])
    return 100.0 * least / kernel_s
