"""The one place a chip's roofline peaks live.

Every audit that quotes "% of floor" (``scripts/decode_audit.py``, the
trainer-side byte accounting in PROFILE.md) divides by the same peak.
The peaks are keyed by the ``device_kind`` JAX reports
(``jax.devices()[0].device_kind``), each with its source, and a kind the
table does not hold is an error, never a default: a floor quoted
against the wrong chip's bandwidth is a wrong number that looks right.

A floor computed from a peak is only a *position* on the chip it
describes — off-TPU callers must label it analytic (``decode_audit``
names the chip it assumed and emits ``pct_of_floor: None`` on CPU for
exactly this reason).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    hbm_gbps: float  # HBM bandwidth, GB/s
    bf16_tflops: float  # dense bf16 matmul peak, TFLOP/s
    source: str


# What one TPU v5e chip reports as its device_kind (read on the chip by
# chip_smoke.py).
V5E = "TPU v5 lite"

PEAKS = {
    V5E: ChipPeaks(
        hbm_gbps=819.0,
        bf16_tflops=197.0,
        source='Google Cloud documentation, "TPU v5e" (per chip)',
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Published peaks of the chip that reports ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no roofline peaks for device_kind {device_kind!r} (have "
            f"{sorted(PEAKS)}): add the chip's published peaks and their "
            f"source to utils/roofline.PEAKS"
        ) from None


def floor_basis(device_kind: str) -> str:
    """Label carried by every record that quotes a floor, so an archived
    number can never be misread against another chip's bandwidth."""
    return f"{device_kind}-hbm-{peaks(device_kind).hbm_gbps:.0f}GBps"

