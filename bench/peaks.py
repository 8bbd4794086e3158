"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393
TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect per chip. The numbers are the same as the program's
``analysis/roofline.py`` table; this copy is the yardstick, so a change to
the program cannot move it.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 MXU
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,     # 1,600 Gbit/s
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device kind {device_kind!r}; "
                         f"known kinds: {sorted(PEAKS)}") from None
