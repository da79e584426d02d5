"""Published peaks per chip, keyed by the exact ``device_kind`` JAX
reports.  A device that is not in the table is an error, never a
default (``bench.py:_PEAK_TFLOPS`` had the same rule; this copy adds
memory and interconnect)."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
    # 1,600 Gbit/s of chip-to-chip interconnect per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it with its source before reporting a "
            f"utilization or a roofline share on this chip")
    return PEAKS[device_kind]


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take for ``flops`` operations over
    ``nbytes`` bytes of memory traffic, and which of the two bounds it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return ((t_flops, "compute") if t_flops >= t_bytes
            else (t_bytes, "memory"))
