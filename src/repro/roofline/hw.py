"""Target-hardware constants (TPU v5e-class, per assignment)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HwSpec:
    name: str = "tpu-v5e"
    ici_link_bw: float = 50e9            # bytes/s per link (one direction)


V5E = HwSpec()
