"""Published per-chip peaks, keyed by `device_kind` — the ONE table behind
MFU (benchmarks/train_bench.py) and the roofline verdict (tools/ptdoctor.py,
which loads this file by path: stdlib only, no package imports).

Source: Google Cloud TPU documentation, the per-generation system
architecture pages. The v5e row ("TPU v5e": 197 TFLOP/s bf16, 819 GB/s
HBM) is the chip this repo is measured on; the other rows are the same
documentation's figures and have not been exercised here. A device that is
not in the table is an error for the caller to raise, never a default."""
from __future__ import annotations

from typing import Optional, Tuple

#: device_kind substring (lowercase, FIRST match wins — "v5p" must precede
#: "v5") -> (peak dense bf16 TFLOP/s, peak HBM GB/s) per chip
PEAKS = (
    ("v6", (918.0, 1640.0)),
    ("v5p", (459.0, 2765.0)),
    ("v5", (197.0, 819.0)),      # v5e reports device_kind "TPU v5 lite"
    ("v4", (275.0, 1228.0)),
)


def lookup(device_kind: Optional[str]) -> Optional[Tuple[float, float]]:
    """(peak TFLOP/s, peak GB/s) for a device kind, or None when the
    table has no row for it."""
    low = (device_kind or "").lower()
    for sub, peaks in PEAKS:
        if sub in low:
            return peaks
    return None
