"""Published peaks of the chips the benchmark runs on, by `device_kind`.

A kind that is not in the table is an error, never a default: a share of
an unknown peak means nothing.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB of HBM at 819 GB/s. JAX reports the chip as "TPU v5 lite".
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud TPU v5e documentation (bf16 peak, HBM "
                  "bandwidth)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for this work: the larger of
    operations over peak FLOP/s and bytes over peak bandwidth."""
    p = peaks_for(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
