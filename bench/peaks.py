"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default.  The
rows copy ``repro.launch.mesh.HW`` (v5e: 197 TFLOP/s bf16, 819 GB/s), whose
source is the vendor's documentation.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,        # FLOP/s, bf16 matrix units
        "ops_int8": 393e12,          # OP/s
        "hbm_bytes_per_s": 819e9,    # B/s
        "hbm_bytes": 16e9,           # B
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


class UnknownDevice(KeyError):
    """The chip's ``device_kind`` has no row in :data:`PEAKS`."""


def peaks(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


__all__ = ["PEAKS", "UnknownDevice", "peaks"]
