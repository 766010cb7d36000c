"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of
HBM at 819 GB/s.  A float32 matmul at JAX's default precision runs as
one bfloat16 pass on the MXU, so the bfloat16 figure is the ceiling for
the programs measured here.

A kind that is not in the table is an error, never a default: a roofline
share against the wrong peak would be a wrong number that looks right.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops_per_s: float   # dense bfloat16 matmul peak
    bytes_per_s: float   # HBM bandwidth
    source: str


PEAKS: dict[str, Peak] = {
    "TPU v5 lite": Peak(
        flops_per_s=197e12,
        bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peak_for(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None


def least_time_s(flops: float, nbytes: float, peak: Peak) -> tuple[float, str]:
    """The least time the chip could take for ``flops`` operations and
    ``nbytes`` bytes moved, and which of the two bounds it."""
    t_flops = flops / peak.flops_per_s
    t_bytes = nbytes / peak.bytes_per_s
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
