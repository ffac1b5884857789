"""Published peaks per accelerator, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect).  Copied from the store's
``roofline/constants.py`` so that the yardstick stays with the benchmark.
A device kind missing from the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float     # FLOP/s
    hbm_bytes: int        # device memory
    hbm_bw: float         # bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bytes=16 * 10**9, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(kind: str) -> DevicePeaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "it to bench/peaks.py with its source") from None
