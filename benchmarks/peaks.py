"""The table of peaks: ``device_kind`` (as JAX reports it) -> what one
chip can do at best, with the source of each figure. A kind that is not
here is an error, never a default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r} "
            f"(have {sorted(PEAKS)}); add it to benchmarks/peaks.py with "
            "its source"
        ) from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> Dict[str, object]:
    """The least time one chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, and which of the two."""
    p = peaks_for(device_kind)
    t_flops = flops / float(p["flops_per_s"])
    t_bytes = nbytes / float(p["hbm_bytes_per_s"])
    return {
        "seconds": max(t_flops, t_bytes),
        "bound_by": "flops" if t_flops >= t_bytes else "bytes",
    }
