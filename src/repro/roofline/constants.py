"""Published peaks per accelerator, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect over 4 links).  A device kind missing from the
table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import functools


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float     # FLOP/s
    hbm_bytes: int        # device memory
    hbm_bw: float         # bytes/s
    ici_link_bw: float    # bytes/s per chip-to-chip link
    source: str


PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bytes=16 * 2**30,
        hbm_bw=819e9, ici_link_bw=1600e9 / 8 / 4,
        source='Google Cloud documentation, "TPU v5e"'),
}

#: The chip that roofline models describe when no TPU is attached.
MODEL_KIND = "TPU v5 lite"

#: Host<->device copy rate (bytes/s) the models assume: a PCIe-class host
#: link.  An assumption, not a published peak; the source above gives none.
HOST_LINK_BW_ASSUMED = 50e9


def peaks(kind: str | None = None) -> DevicePeaks:
    """Peaks of ``kind``, by default of the device JAX runs on.  A host
    without a TPU gets the modelled chip ``MODEL_KIND``; a TPU whose kind
    is not in ``PEAKS`` raises ``KeyError``."""
    kind = kind if kind is not None else _default_kind()
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "it to repro.roofline.constants.PEAKS") from None


@functools.lru_cache(maxsize=None)
def _default_kind() -> str:
    import jax
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else MODEL_KIND
