"""Published peaks of a chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import dataclasses
import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


@dataclasses.dataclass(frozen=True)
class Peaks:
    device_kind: str
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str

    def least_time(self, flops: float, bytes_moved: float) -> tuple:
        """The least time the chip needs for the work, and which of
        ``"flops"`` or ``"bytes"`` bounds it."""
        tf = flops / self.bf16_flops_per_s
        tb = bytes_moved / self.hbm_bytes_per_s
        return (tf, "flops") if tf >= tb else (tb, "bytes")


def for_device(device_kind: str, path: str = PEAKS_FILE) -> Peaks:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return Peaks(device_kind=device_kind, **table[device_kind])
