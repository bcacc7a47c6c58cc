"""Work that a kernel needs, reckoned from the shapes of its inputs.

Independent of how the program implements the kernel: no probe rounds,
no copy of the store, no 64-bit emulation.  These are the numerators of
the `*_roofline` shares."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The device's row of the peak table; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def upsert_bytes(edge_lanes: int, key_bytes: int) -> int:
    """HBM bytes that one commit's two lookup-or-insert passes need.

    The node pass covers 2 * edge_lanes lanes (every endpoint of the
    table's edges), the edge pass edge_lanes.  Per lane: read the key and
    its valid flag, read the one table slot the key hashes to, write that
    slot, and write the slot index (int32) and the is-new flag."""
    lanes = 3 * edge_lanes
    return lanes * (3 * key_bytes + 4 + 1 + 1)
