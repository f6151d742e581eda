"""The least time a chip could take for a batch of TCAM matches.

The work is counted from the deployment's real rows and columns (the
reference's view of each tree: one row per leaf, the decoder bit and every
feature's code), and from the batch's real request count: never from an
engine's placed arrays, its padding or its dtype, so a kernel that is
replaced, fused or re-laid is measured against the same work.

* bytes: every cell at 2 bits (a ternary state), plus the search words at 1
  bit per column;
* operations: 2 per (request, row, column) compare-and-count.

For a forest the banks' work adds up.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS.name}")
    return table[device_kind]


def match_work(banks, batch: int) -> tuple[float, float]:
    """(bytes, operations) of matching ``batch`` requests against banks of
    (rows, columns)."""
    nbytes = sum(r * c * 2 / 8 + batch * c / 8 for r, c in banks)
    ops = sum(2.0 * batch * r * c for r, c in banks)
    return nbytes, ops


def least_seconds(banks, batch: int, pk: dict) -> float:
    nbytes, ops = match_work(banks, batch)
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["int8_ops_per_s"])
