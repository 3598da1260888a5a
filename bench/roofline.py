"""Peaks and the least work of a call, kept with the benchmark.

The peak table (``peaks.json``) is keyed by ``device_kind`` as JAX
reports it; a kind that is not in the table is an error, never a
default.  The least-bytes functions count the work a call must do
whatever implements it, from the batch and the table's shape alone.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add them with their source")
    return table[device_kind]


def touched_blocks(distinct_keys: int, nblocks: int) -> float:
    """Expected number of distinct blocks that ``distinct_keys`` keys
    land on under a uniform hash over ``nblocks`` blocks."""
    if distinct_keys <= 0:
        return 0.0
    return -nblocks * math.expm1(distinct_keys * math.log1p(-1.0 / nblocks))


def probe_least_bytes(op: str, distinct_keys: int, queries: int,
                      nblocks: int, block: int, key_lanes: int,
                      value_lanes: int) -> float:
    """Least HBM bytes of one owner-side probe of a batch.

    Every block the batch touches is read once (keys, values and the
    status word of each of its ``block`` slots, u32 each) and, for an
    insert, written once; the query rows (block index, key lanes, and
    for an insert the value lanes) are read and each query's answer
    (an ok word; or value lanes and a found word) is written.
    """
    blk_bytes = (key_lanes + value_lanes + 1) * block * 4
    touched = touched_blocks(distinct_keys, nblocks)
    if op == "insert":
        table = 2 * touched * blk_bytes
        rows = queries * (1 + key_lanes + value_lanes + 1) * 4
    elif op == "find":
        table = touched * blk_bytes
        rows = queries * (1 + key_lanes + value_lanes + 1) * 4
    else:
        raise ValueError(f"unknown probe op {op!r}")
    return table + rows
