"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.

A device that is not in the table is an error, never a default: a
roofline share against the wrong peak is a wrong number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """One chip's published peaks."""

    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes: float       # bytes of device memory
    hbm_bw: float          # bytes/s
    ici_bw: float          # bytes/s of chip-to-chip interconnect
    source: str


V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
            hbm_bw=819e9, ici_bw=1600e9 / 8,
            source='Google Cloud documentation, "TPU v5e"')

TABLE = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def for_kind(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a chip the
    table does not know."""
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(TABLE)}") from None
