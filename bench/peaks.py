"""Published peaks of the chips the benchmark may run on, by device kind.

Source for every number: Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s.  JAX reports a v5e chip's
``device_kind`` as "TPU v5 lite".  A device that is not in the table is
an error, never a default.
"""

from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, TPU v5e (cloud.google.com/tpu/docs/v5e)",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises KeyError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
