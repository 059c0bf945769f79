"""Peak rates of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s).  A device that is not in
the table is an error, never a default: a roofline or utilization share
against a guessed peak means nothing.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float          # dense bf16 FLOP/s
    hbm_bytes_s: float    # HBM bandwidth, bytes/s
    hbm_bytes: int        # HBM capacity, bytes
    source: str


_V5E = Peak(flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16 * 2**30,
            source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_s(peak: Peak, ops: float, nbytes: float) -> tuple[float, str]:
    """Least time the chip could take for ``ops`` operations moving
    ``nbytes`` bytes, and which of the two bounds it."""
    t_ops, t_bytes = ops / peak.flops, nbytes / peak.hbm_bytes_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
