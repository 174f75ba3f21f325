"""Peaks by device kind, and the bytes the block scan needs from u.

The scan's work is priced the way the paper prices it: u counts
(block, term-field plane) reads, and one plane of a block is
``block_docs / 32`` uint32 words.  Bytes come from u, so the roofline
reads the same work whatever implements the scan; the kernel's own
shapes (speculative blocks, padded lanes) never enter the numerator.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
WORD_BYTES = 4
WORD_BITS = 32


class UnknownDevice(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} "
                            f"in {path}; known: {sorted(table)}")
    return table[device_kind]


def plane_bytes(block_docs: int) -> int:
    """Bytes of one (term, field) plane of one block."""
    return block_docs // WORD_BITS * WORD_BYTES


def scan_bytes(u_total: int, block_docs: int) -> int:
    """Bytes the scan must read for ``u_total`` plane reads."""
    return int(u_total) * plane_bytes(block_docs)


def roofline_pct(u_total: int, block_docs: int, kernel_s: float,
                 device_kind: str) -> float:
    """Least time the chip could read the scan's bytes in, over the
    time the kernel took, in percent (bandwidth bound: the scan does
    no arithmetic worth a FLOP bound)."""
    least_s = scan_bytes(u_total, block_docs) / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
