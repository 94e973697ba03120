"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A device that is not in the table is
an error, never a default: a roofline or utilization against a guessed peak
is not a measurement.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip.  JAX reports the chip as "TPU v5 lite".
"""
from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9, "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
